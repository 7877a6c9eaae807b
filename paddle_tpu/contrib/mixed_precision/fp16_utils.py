"""Program rewriting for mixed precision: insert casts by op lists.

Reference parity:
/root/reference/python/paddle/fluid/contrib/mixed_precision/fp16_utils.py
(rewrite_program: walk ops, insert cast ops on inputs per white/black
list).  Master weights stay fp32; casts are inserted per use and XLA fuses
them into the consuming matmul/conv (free on the MXU's bf16 multiply path).
"""

from __future__ import annotations

from paddle_tpu.analysis.passes import checked_pass
from paddle_tpu.core.program import OpDesc
from paddle_tpu.contrib.mixed_precision.fp16_lists import follow_x_list \
    as _FOLLOW_X

_FLOATS = {"float32", "float64"}

# white-list ops whose numerically sensitive slots must NOT be cast to
# the low dtype: conv2d_bn_train's BN statistics/params stay fp32 (the
# unfused graph's batch_norm is gray-listed, so its Scale/Bias/Mean/
# Variance are never cast — the fused op must match, or running stats
# would accumulate in bf16)
_WHITE_KEEP_FP32 = {
    "conv2d_bn_train": frozenset(
        {"Scale", "BNBias", "Mean", "Variance"}),
    # the router's gates weigh the experts' outputs in float32
    "moe_experts": frozenset({"TopkWeight"}),
    # a hyper-connection's norm scale, projections, gains and biases,
    # and the coefficients mhc_pre hands mhc_post: float32 end to end
    "mhc_pre": frozenset({"NormScale", "Phi", "Alpha", "Bias"}),
    "mhc_post": frozenset({"HPost", "HRes"}),
    # the step sizes, the decay rates and the skip weights of a
    # state-space scan: exp(Dt A) over thousands of tokens in bfloat16
    # is another recurrence
    "ssd_scan": frozenset({"Dt", "A", "D"}),
    # a delta-rule scan's log-decays and write strengths: e^G over a
    # chunk in bfloat16 is another recurrence
    "kda_scan": frozenset({"G", "Beta"}),
    # the learned pooling vectors of a chunk's two softmaxes
    "eva_pool": frozenset({"Mu", "Phi"}),
}

# white-list ops with multiple outputs where only SOME are emitted in
# the low dtype (conv2d_bn_train: Output follows the bf16 inputs; the
# stat outputs MeanOut/VarianceOut/SavedMean/SavedVariance stay fp32,
# like batch_norm's non-Y outputs under the follow-X rule;
# flash_attention: Out follows the bf16 q/k/v, the saved row statistic
# LSE is float32 whatever the inputs are; moe_experts: so is Load)
_WHITE_LOWP_OUT = {
    "conv2d_bn_train": frozenset({"Output"}),
    "flash_attention": frozenset({"Out"}),
    "mhc_pre": frozenset({"U"}),
    # Load is a float32 count, whatever the rows' dtype
    "moe_experts": frozenset({"Out"}),
    # States, the running state at each chunk's start, is float32
    "ssd_scan": frozenset({"Y"}),
    "kda_scan": frozenset({"O"}),
    "eva_attention": frozenset({"Out"}),
}


@checked_pass("amp_rewrite")
def rewrite_program(program, amp_lists, dest_dtype="bfloat16"):
    """Rewrite the global block in place.  White-list ops get their float
    inputs cast to ``dest_dtype``; black-list (and unknown) ops get
    low-precision inputs cast back to fp32; gray ops follow their inputs.

    A var is "eligible" if its declared dtype is float (or undeclared);
    integer tensors (ids, indices) are never touched.  The set of vars
    currently in low precision is tracked while walking the op list."""
    block = program.global_block()

    def eligible(name):
        if not block.has_var(name):
            return True
        d = block.var(name).dtype
        return d is None or d in _FLOATS

    lowp = set()      # var names whose runtime value is dest_dtype
    new_ops = []
    # var -> {dtype: the cast of it}, for as long as no op writes var
    # again: ONE cast op however many ops read it.  A cast per reader
    # under the one name `<var>.cast_<dtype>` made the readers' grad
    # ops add into one gradient var that append_backward then handed
    # to EVERY one of those casts' grad ops: a float32 activation read
    # by q, k and v projections got 3 dv + 2 dk + dq (PERF.md, PR 27)
    casts = {}

    def insert_cast(name, dst):
        made = casts.setdefault(name, {})
        if dst not in made:
            made[dst] = f"{name}.cast_{dst}"
            shape = block.var(name).shape if block.has_var(name) else None
            block.create_var(name=made[dst], dtype=dst, shape=shape)
            new_ops.append(OpDesc("cast", {"X": [name]},
                                  {"Out": [made[dst]]},
                                  {"out_dtype": dst}))
        return made[dst]

    for op in block.ops:
        if op.type in amp_lists.white_list:
            keep = _WHITE_KEEP_FP32.get(op.type, frozenset())
            for slot, names in list(op.inputs.items()):
                if slot in keep:
                    continue
                out = []
                for n in names:
                    if eligible(n) and n not in lowp:
                        n = insert_cast(n, dest_dtype)
                        lowp.add(n)
                    out.append(n)
                op.inputs[slot] = out
            out_lowp = True
        elif op.type in amp_lists.gray_list or op.type in _FOLLOW_X:
            if op.type in _FOLLOW_X:
                # norm ops emit Y in X's dtype (stats stay fp32 inside)
                out_lowp = any(n in lowp for n in op.inputs.get("X", []))
            else:
                # conservative: jnp type promotion means the runtime
                # result is low-precision only if EVERY float operand is;
                # claiming lowp wrongly would make a later white-list op
                # skip its cast and feed a matmul mixed dtypes
                float_ins = [n for ns in op.inputs.values() for n in ns
                             if eligible(n)]
                out_lowp = bool(float_ins) and all(
                    n in lowp for n in float_ins)
        else:  # black or unlisted: numerically sensitive -> fp32
            for slot, names in list(op.inputs.items()):
                out = []
                for n in names:
                    if n in lowp:
                        n = insert_cast(n, "float32")
                    out.append(n)
                op.inputs[slot] = out
            out_lowp = False
        if op.type == "cast":
            out_lowp = str(op.attrs.get("out_dtype")) in (
                dest_dtype, str(dest_dtype))
        new_ops.append(op)
        for n in op.output_names():
            casts.pop(n, None)
        for slot, names in op.outputs.items():
            slot_lowp = out_lowp and (
                op.type not in _FOLLOW_X or slot == "Y")
            if op.type in _WHITE_LOWP_OUT:
                slot_lowp = out_lowp and \
                    slot in _WHITE_LOWP_OUT[op.type]
            for n in names:
                if slot_lowp and eligible(n):
                    lowp.add(n)
                else:
                    lowp.discard(n)
    block.ops = new_ops
    return program


def cast_parameters_to_fp16(program, scope=None):
    """Not used on TPU: master weights stay fp32 and per-use casts feed the
    MXU; kept for API parity with the reference fp16_utils."""
    return program

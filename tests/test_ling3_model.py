"""models/ling3.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/ling3.py on seeded weights: the loss,
the logits and EVERY parameter's gradient; the erase term and the group
limit, which the comparison has to see; the share test that ties the
one-chip cut to the whole layer; the scopes, the counters and the
number of scan kernels a step holds; and `xing4` and `deepseek-v2-lite`,
which share the attention function and the router and must not see
the gate nor the groups.

The reference runs the delta rule TOKEN BY TOKEN and marks the router's
groups over all experts; the program runs the chunked WY form (2
blocks of 2 chunks of 16 here) and top-k over masked scores.

Tolerances, and why.

* float32: the same mathematics in another order (a chunked scan
  against a token-by-token one, a triangular solve, fused ops, a sorted
  grouped matmul against a masked loop): loss to 1e-5, logits to 1e-5
  of the largest logit, gradients to 1e-4 of each parameter's largest
  entry.  bf16 anywhere fails this:
  `test_float32_tolerance_excludes_bf16`.
* AMP (bf16 matmul operands, attention, the scan's Q, K and V and the
  expert rows; the residual stream, the log-decays, beta, the inverse,
  the running state, router scores and the norms' statistics float32):
  logits to 3e-2 of the largest logit, the loss to 1e-3, gradients to
  0.15 of each parameter's largest entry (`xing4`'s bound; the worst,
  7-9%, are the query and key projections of a KDA layer and the
  router: the L2 norm keeps only the part of a gradient across its
  input, a difference of rounded terms).

`WIDE` draws every matrix from N(0, 0.2) in place of N(0, 0.02): at 128
channels the published 0.02 leaves the state's part of a KDA layer's
output too small for a test to see.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.ling3 import ling3_model
from paddle_tpu.ops import pallas_kernels as pk

from conftest import load_reference

ref = load_reference("ling3")

SEQ, BATCH = 64, 2

SMALL = {
    "hidden_size": 128, "num_attention_heads": 4, "head_dim": 32,
    "num_key_value_heads": 4, "q_lora_rank": None, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "rope_theta": 6000000, "max_position_embeddings": 4096,
    "rms_norm_eps": 1e-6, "intermediate_size": 256,
    "moe_intermediate_size": 64,
    "moe_shared_expert_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 5, 9],
    "num_experts_per_tok": 4, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "score_function": "sigmoid", "moe_router_enable_expert_bias": True,
    "num_hidden_layers": 3, "layer_group_size": 3,
    "first_k_dense_replace": 1, "short_conv_kernel_size": 4,
    "kda_lower_bound": -5, "kda_safe_gate": True, "linear_silu": True,
    "gated_attention_proj_granularity_type": "head_wise",
    "kda_chunk_size": 16, "kda_block_chunks": 2,
    "vocab_size": 96, "initializer_range": 0.02, "param_prefix": "ling3",
}
WIDE = dict(SMALL, initializer_range=0.2)
# one head a lane block: what the kernels tile
LANE = dict(WIDE, num_attention_heads=2, head_dim=128)

F32 = {"loss": 1e-5, "logits": 1e-5, "grad": 1e-4}
AMP = {"loss": 1e-3, "logits": 3e-2, "grad": 0.15}
# no expert layer, matrices from N(0, 0.1): the state matters and no
# selection can flip
AMP_DENSE = dict(SMALL, initializer_range=0.1, first_k_dense_replace=3)


def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _build(config, amp, recompute, opt=None):
    _fresh()
    np.random.seed(0)
    model = ling3_model(config, seq_len=SEQ)
    opt = opt or optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    return model, opt


def _batch(config, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (BATCH, SEQ, 1), dtype=np.int64)
    return ids, np.roll(ids, -1, axis=1)


def _scope_params(config):
    # copies: the step donates the weights
    return jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))


def _set_router_biases(config, sd=0.1, seed=5):
    """The selection bias starts at zero, where it would select
    nothing: the tests give it values of the scores' spread (sd 0.1:
    the selection differs from token to token), or, under AMP, ten
    times that: a selection that a bf16 rounding upstream does not
    flip (a flipped pair moves an expert's gradient by 20-30% of its
    largest entry at 128 tokens)."""
    rng = np.random.default_rng(seed)
    for layer in ref.param_names(config)["layers"]:
        if "router_bias" in layer:
            var = global_scope().find_var(layer["router_bias"])
            var.set(jnp.asarray(rng.normal(0, sd, np.shape(var.get())),
                                jnp.float32))


def _run(config, amp, recompute, bias_sd=0.1):
    """{loss, logits, grads} of the program (and `used`, the kernel
    impls its step counted) and of the reference."""
    model, opt = _build(config, amp, recompute)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _set_router_biases(config, sd=bias_sd)
    batch = _batch(config)
    params = _scope_params(config)
    ids32, labels32 = ref._split(batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids32, labels32, config))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jnp.stack([ref.sequence_logits(params, i, config)
                                 for i in ids32])
    names = ref.param_names(config)
    want = {"loss": float(want_loss), "logits": np.asarray(want_logits),
            "grads": dict(zip(jax.tree_util.tree_leaves(names),
                              jax.tree_util.tree_leaves(want_grads)))}
    # the selection bias selects and is not trained: no gradient
    for layer in names["layers"]:
        want["grads"].pop(layer.get("router_bias"), None)
    before = _impl_counts()
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"], model["logits"]]
                   + [g for _, g in params_grads])
    got = {"used": _since(before),
           "loss": float(np.asarray(outs[0]).reshape(-1)[0]),
           "logits": np.asarray(outs[1], np.float32),
           "grads": {p.name: np.asarray(o, np.float32)
                     for (p, _), o in zip(params_grads, outs[2:])}}
    return got, want, params


def _grad_errors(got, want):
    """|got - want| at its largest over the parameter's largest
    |want|, by parameter."""
    return {n: float(np.abs(got[n] - np.asarray(w)).max()
                     / np.abs(np.asarray(w)).max())
            for n, w in want.items()}


def _check(got, want, tol):
    assert set(got["grads"]) == set(want["grads"])
    assert got["loss"] == pytest.approx(want["loss"], rel=tol["loss"])
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        <= tol["logits"] * scale
    errors = _grad_errors(got["grads"], want["grads"])
    assert max(errors.values()) <= tol["grad"], \
        sorted(errors.items(), key=lambda kv: -kv[1])[:5]
    # every parameter has a gradient that is not zero: the reference's
    # too, so none of the comparisons above is of 0 with 0
    assert all(np.abs(np.asarray(w)).max() > 0
               for w in want["grads"].values())


@pytest.fixture
def interpret(monkeypatch):
    """The kernels' auto-impl resolves to their interpret mode: the
    program then runs the Pallas scan, flash and grouped-matmul kernels
    on the CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


def _impl_counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}


CASES = {
    "f32": (SMALL, False, False, F32, 0.1),
    "f32_wide": (WIDE, False, False, F32, 0.1),
    "f32_wide_recompute": (WIDE, False, True, F32, 0.1),
    # latent attention first and last, every layer an expert layer
    "f32_period_2": (dict(WIDE, layer_group_size=2, num_hidden_layers=4,
                          first_k_dense_replace=0), False, True, F32, 0.1),
    "amp_recompute": (SMALL, True, True, AMP, 1.0),
    "amp_dense_recompute": (AMP_DENSE, True, True, AMP, 1.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol, bias_sd = CASES[case]
    got, want, _ = _run(config, amp, recompute, bias_sd)
    _check(got, want, tol)
    if config["initializer_range"] == 0.02:
        # random weights at this width give small logits
        assert 0.9 * np.log(96) < want["loss"] < 1.1 * np.log(96)
    if config["first_k_dense_replace"] < config["num_hidden_layers"]:
        groups = "%dof%d" % (config["topk_group"], config["n_group"])
        assert got["used"][("moe_route_groups", groups)] > 0


def test_kernels_in_interpret_mode_against_reference(interpret):
    """The same comparison with pt_kda_fwd, pt_kda_bwd, the flash and
    the grouped-matmul kernels in the program, inside recompute
    segments: heads of 128, one a lane block."""
    got, want, _ = _run(LANE, False, True)
    _check(got, want, F32)
    used = got["used"]
    assert used[("kda_scan", "interpret")] == 2
    assert used[("kda_scan_grad", "saved")] == 2
    assert used[("flash_attention", "interpret")] == 1
    assert used[("moe_gmm", "interpret")] > 0
    # the latent attention's q, 2 heads of 32 + 16, and its ONE shared
    # rotary key a token fill no lane tile: pt_rotary leaves both to
    # the XLA form (ISSUE 54; the cell's 32 heads of 192 are 48 tiles)
    assert [k for k in used if k[1] in ("xla", "recompute")] \
        == [("rotary", "xla")]


def test_float32_tolerance_excludes_bf16():
    got, want, _ = _run(AMP_DENSE, True, False)
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        > 20 * F32["logits"] * scale
    assert max(_grad_errors(got["grads"], want["grads"]).values()) \
        > 20 * F32["grad"]


@pytest.mark.parametrize("variant", ["no_erase", "no_group_limit"])
def test_the_comparison_sees_the_erase_term_and_the_group_limit(variant):
    """The reference without the delta rule's erase term, or with the
    selection over all experts, is another model: the program's loss,
    which equals the reference's to 1e-5, is 20 times further from
    it and more."""
    got, want, params = _run(WIDE, False, False)
    wrong = ref.loss(params, _batch(WIDE), WIDE, variant=variant)
    assert abs(got["loss"] - want["loss"]) <= F32["loss"] * want["loss"]
    assert abs(wrong - want["loss"]) > 20 * F32["loss"] * want["loss"]
    assert ref.loss(params, _batch(WIDE), WIDE) == pytest.approx(
        want["loss"], rel=1e-6)


def _layer_weights(rng, c=32, w=16, e=16):
    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"router": mat(c, e),
            "router_bias": jnp.asarray(rng.normal(0, 0.1, e), jnp.float32),
            "experts": {"gate": mat(e, c, w), "up": mat(e, c, w),
                        "down": mat(e, w, c)},
            "shared": {"gate": mat(c, w), "up": mat(c, w),
                       "down": mat(w, c)}}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_shares_add_up_to_the_whole_layer(impl):
    """16 experts in 4 groups over 4 chips, 4 held on each (a group a
    chip, as the cell holds group 0).  The routed parts the 4 shares
    give, plus the shared expert ONCE, are the uncut layer of the
    reference; and each share of the PROGRAM's ops (the group-limited
    router over all 16, then the held experts) equals the reference's
    share."""
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.default_rng(11)
    lw = _layer_weights(rng)
    config = {"num_experts_per_tok": 4, "norm_topk_prob": True,
              "routed_scaling_factor": 2.5, "num_experts": 16,
              "n_group": 4, "topk_group": 2}
    u = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_ffn(u, lw, config, held=list(range(16)))
        shared = ref.swiglu(u, lw["shared"])
    shares = [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15]]

    def stack_of(held):
        return {k: v[jnp.asarray(held)] for k, v in lw["experts"].items()}

    route, experts = get_op_def("moe_route"), get_op_def("moe_experts")
    r = route.compute(
        {"X": u, "W": lw["router"], "Bias": lw["router_bias"]},
        route.canonical_attrs({"k": 4, "norm_topk_prob": True,
                               "routed_scaling_factor": 2.5,
                               "n_group": 4, "topk_group": 2}))
    # a token's 4 experts lie in 2 groups: on 2 of the 4 chips
    groups_hit = {len(set(row // 4)) for row in np.asarray(r["TopkIdx"])}
    assert groups_hit <= {1, 2} and 2 in groups_hit
    total = shared
    for held in shares:
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(u, dict(lw, experts=stack_of(held)),
                                  config, held=held, shared=False)
        st = stack_of(held)
        mine = experts.compute(
            {"X": u, "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
             "WGate": st["gate"], "WUp": st["up"], "WDown": st["down"]},
            experts.canonical_attrs({"held": held, "block_m": 16,
                                     "impl": impl}))["Out"]
        np.testing.assert_allclose(mine, part, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    # the gates of a token sum to routed_scaling_factor over ALL chips
    np.testing.assert_allclose(np.asarray(r["TopkWeight"]).sum(-1), 2.5,
                               rtol=1e-5)


def test_program_is_verified_and_shape_checked():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes

    model, opt = _build(SMALL, True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    program = fluid.default_main_program()
    verifier.verify(program)
    _, diags = infer_program_shapes(program)
    assert not [d for d in diags if d.severity == "error"], diags
    assert len(model["checkpoints"]) == SMALL["num_hidden_layers"]
    block = program.global_block()
    types = {op.type for op in block.ops}
    assert {"kda_scan", "kda_gate", "head_l2_norm", "head_gated_rms_norm",
            "causal_conv1d", "rms_norm", "swiglu", "flash_attention",
            "rotary_embedding", "moe_route", "moe_experts",
            "recompute_segment_grad"} <= types
    names = {p.name for p in program.all_parameters()}
    # (the router's selection bias is persistable and no parameter)
    assert names == {n for n in jax.tree_util.tree_leaves(
        ref.param_names(SMALL)) if not n.endswith("router_bias.w")}
    # layers 0 and 1 KDA, layer 2 latent attention; layer 0 dense
    assert "ling3_l0_kda_q.w" in names and "ling3_l2_mla_kv_a.w" in names
    assert not [n for n in names if n.startswith("ling3_l2_kda")]
    assert "ling3_l0_down.w" in names and "ling3_l1_router.w" in names
    assert block.var("ling3_l0_kda_a.w").shape == (128, 4 * 32)
    assert block.var("ling3_l0_kda_beta.w").shape == (128, 4)
    assert block.var("ling3_l0_kda_q_conv.w").shape == (4 * 32, 4)
    assert block.var("ling3_l0_kda_norm.w").shape == (32,)
    assert block.var("ling3_l2_mla_gate.w").shape == (128, 4)
    assert block.var("ling3_l1_router.w").shape == (128, 16)
    assert block.var("ling3_l1_experts_gate.w").shape == (4, 128, 64)
    # no bias on the three convolutions of a KDA layer
    assert not [n for n in names if "conv_bias" in n]
    route, = {(op.attrs["n_group"], op.attrs["topk_group"])
              for op in block.ops if op.type == "moe_route"}
    assert route == (4, 2)
    # under AMP the scan's log-decays and write strengths stay float32
    for op in block.ops:
        if op.type == "kda_scan":
            for slot in ("G", "Beta"):
                assert block.var(op.inputs[slot][0]).dtype != "bfloat16"
            assert block.var(op.outputs["States"][0]).dtype == "float32"
    # the router's bias is persistable and no optimizer op writes it
    assert block.var("ling3_l1_router_bias.w").persistable
    assert not [op for op in block.ops
                if "ling3_l1_router_bias.w" in op.output_names()]


@pytest.mark.parametrize("key,value", [
    ("num_kv_heads_for_linear_attn", 8), ("group_norm_size", 4),
    ("use_kda_lora", True), ("kda_safe_gate", False),
    ("use_mla_nope", True), ("value_norm", True),
    ("score_function", "softmax"), ("q_lora_rank", 64),
    ("gated_attention_proj_granularity_type", "element_wise")])
def test_what_is_not_built_raises(key, value):
    _fresh()
    with pytest.raises(NotImplementedError, match=key):
        ling3_model(dict(SMALL, **{key: value}), seq_len=SEQ)


def test_a_clamped_swiglu_in_a_kept_layer_raises():
    _fresh()
    with pytest.raises(NotImplementedError, match="swiglu_limit"):
        ling3_model(dict(SMALL, expert_swiglu_limit_list=[0, 4, 0, 0]),
                    seq_len=SEQ)
    _fresh()
    # a limit past the layers that are kept builds
    ling3_model(dict(SMALL, expert_swiglu_limit_list=[0, 0, 0, 4]),
                seq_len=SEQ)


def test_a_length_that_is_no_multiple_of_the_block_raises():
    _fresh()
    with pytest.raises(ValueError, match="nothing is padded"):
        ling3_model(SMALL, seq_len=SEQ + 16)


def test_scopes_counters_and_one_forward_kernel_a_scan(interpret):
    """The compiled step of RecomputeOptimizer(Adam) under AMP: the
    computes' named scopes and the builder's name scopes are in its op
    metadata; every scan runs its forward kernel ONCE and its backward
    kernel once (the segment binds the saved O and block-start states
    on the op it replays: never a second forward for the grad op nor a
    third for the replay), and the attention layer its forward kernel
    once."""
    model, opt = _build(LANE, True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _impl_counts()
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(LANE)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    first, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    second, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    assert float(np.asarray(second).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])
    used = _since(before)
    assert used[("kda_scan", "interpret")] == 2
    assert used[("kda_scan_grad", "saved")] == 2
    assert ("kda_scan_grad", "recompute") not in used
    assert used[("flash_attention", "interpret")] == 1
    assert used[("flash_attention_grad", "saved")] == 1
    assert used[("moe_route_groups", "2of4")] > 0
    step, = [v for v in compiled._cache.values() if callable(v)]
    state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for n, v in
             ((n, global_scope().find_var(n).get())
              for n in compiled._persistable_names)}
    text = step.lower(state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in feed.items()}).as_text(
                                  debug_info=True)
    for scope in ("pt_kda", "pt_kda_gate", "pt_head_l2_norm",
                  "pt_head_gated_norm", "pt_causal_conv1d",
                  "pt_moe_route", "pt_moe_route_groups", "pt_moe_experts",
                  "pt_ling3_kda", "pt_ling3_mla", "pt_ling3_ffn",
                  "pt_ling3_head", "pt_rms_norm", "pt_swiglu", "pt_mla"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
    assert text.count("pt_kda_fwd") > 0 and text.count("pt_kda_bwd") > 0


def _gate_and_group_ops(model_fn, config):
    _fresh()
    np.random.seed(0)
    model_fn(config, seq_len=32)
    ops = fluid.default_main_program().global_block().ops
    return ([op.type for op in ops if op.type == "head_gated_rms_norm"],
            {(op.attrs["n_group"], op.attrs["topk_group"])
             for op in ops if op.type == "moe_route"})


def test_xing4_and_dsv2_see_neither_the_gate_nor_the_groups(monkeypatch):
    """The two models that share latent_attention() and moe_route build
    no gate op and route over all experts (n_group 1: no group step in
    the compute, no `moe_route_groups` count)."""
    from test_deepseek_v2_model import SMALL as DSV2
    from test_xing4_model import SMALL as XING
    from paddle_tpu.models.deepseek_v2 import deepseek_v2_model
    from paddle_tpu.models.xing4 import xing4_model

    before = _impl_counts()
    for fn, config in ((xing4_model, XING), (deepseek_v2_model, DSV2)):
        gates, groups = _gate_and_group_ops(fn, dict(config))
        assert gates == [] and groups == {(1, 1)}
    assert not [k for k in _since(before) if k[0] == "moe_route_groups"]


def test_the_benchmarks_reference_is_this_one():
    """benchmarks/reference/ling3.py, which decides the cell's `correct`
    on the chip, is a copy of the reference these tests compare the
    program with."""
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "reference",
                           "ling3.py")) as f:
        copy = f.read()
    with open(ref.__file__) as f:
        assert f.read() == copy

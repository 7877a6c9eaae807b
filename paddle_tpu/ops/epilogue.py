"""Unified epilogue-fusion framework: ONE composable stage grammar for
conv, matmul, and decode kernels (ISSUE 17).

The repo rebuilt "fuse the elementwise tail into the producing op" four
separate times — conv-epilogue (PR 1), conv+BN-stats (PR 4), the int8
requantize epilogue (PR 5), and the decode logits tail (PR 7) — each
with its own transpiler pass, flag, and parity suite.  This module is
the consolidation: a declarative :class:`EpilogueSpec` (an ordered list
of STAGES applied to the VMEM-resident accumulator), the two evaluators
every kernel/reference pair shares, and the NEW fused matmul/fc
epilogue kernel the transformer train graph was missing.

Stage grammar
-------------
A spec is an ordered subset of registered stage names::

    bias        per-channel bias add (the conv2d layer / fc bias, or
                the conv-bn fold's folded shift)
    bn_apply    train-mode BN normalize + scale/shift (conv2d_bn_train)
    stats_tap   per-channel sum(y)/sum(y*y) sibling outputs reduced
                from the resident accumulator (conv2d_bn_stats)
    residual    same-shape skip-connection add
    relu/gelu   activation tail
    requantize  int8 interlayer quantize-to-consumer-scale tail
                (conv2d_int8 / mul_int8 OutScale)
    argmax      the decode engines' greedy logits tail

Canonical order is bias -> stats_tap/bn_apply -> residual -> act ->
requantize -> argmax; ``EpilogueSpec.validate`` rejects anything else,
and the IR verifier (analysis/verifier.py rule ``epilogue-spec``)
checks every ``epilogue`` op attr parses against this grammar, so a
transpiler can never emit a stage list no kernel implements.

Ordering/rounding contract (the bit-parity rule PRs 1/4/5 proved
stage by stage, now stated once):

* ACCUMULATOR order (inside Pallas kernels, ``apply_acc_stages``):
  every stage runs on the f32 accumulator — bias f32, residual f32,
  act f32 — and the single cast to the output dtype happens LAST.
* CHAIN order (the unfused graph / XLA fallback,
  ``apply_chain_stages``): each stage mirrors the discrete op it
  replaces — bias/residual added in the tensor's dtype (with
  elementwise_add's promotion), act last.
* BN tail (``apply_bn_tail``, identical in kernel and XLA): normalize
  in f32, cast to the conv dtype, residual add in that dtype, act.
* requantize tail (``quantize_tail``): astype(f32) / OutScale * bnd,
  round, clip, int8 — the consumer quant's exact rounding point.

For f32 the two orders coincide bitwise; fused-vs-unfused parity is
asserted per legal spec in tests/test_epilogue.py (generated FROM the
grammar, so adding a stage auto-extends the matrix).

Adding a stage = one ``_stage`` entry + an arm in the evaluators +
(optionally) a matcher arm in transpiler/epilogue_transpiler.py.  The
legacy typed flags (``conv_epilogue``, ``conv_bn_stats``,
``int8_interlayer``) are aliases resolving into this path — see
docs/EPILOGUE.md for the flag-alias table.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability import device_trace as _obs_device
from paddle_tpu.ops.pallas_kernels import _count_impl, _kernel_scope

_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_FC_BLOCK_M = 256
_FC_BLOCK_N = 256


# ---------------------------------------------------------------------------
# stage registry
# ---------------------------------------------------------------------------

class EpilogueStage:
    """One registered stage: its canonical position, the operand slot
    it binds (if any), and whether it is an activation (at most one
    activation per spec)."""

    def __init__(self, name, order, operand=None, is_act=False,
                 terminal=False):
        self.name = name
        self.order = order
        self.operand = operand
        self.is_act = is_act
        self.terminal = terminal

    def __repr__(self):
        return f"EpilogueStage({self.name!r})"


# name -> EpilogueStage; canonical order index groups stages that can
# never co-occur at the same level (bn_apply vs stats_tap share a slot:
# conv2d_bn_train carries both semantics in one op)
STAGES = {
    "bias": EpilogueStage("bias", 0, operand="Bias"),
    "stats_tap": EpilogueStage("stats_tap", 1),
    "bn_apply": EpilogueStage("bn_apply", 1),
    "residual": EpilogueStage("residual", 2, operand="Residual"),
    "relu": EpilogueStage("relu", 3, is_act=True),
    "gelu": EpilogueStage("gelu", 3, is_act=True),
    "requantize": EpilogueStage("requantize", 4, operand="OutScale"),
    "argmax": EpilogueStage("argmax", 5, terminal=True),
}

_SEP = "+"


class EpilogueSpec:
    """An ordered, validated list of stage names — the value of the
    ``epilogue`` op attr (serialized via :meth:`to_attr`, a
    ``"bias+residual+relu"``-style string: JSON- and
    program-fingerprint-safe)."""

    def __init__(self, stages=()):
        self.stages = tuple(stages)
        self.validate()

    # -- construction / serialization -----------------------------------
    @classmethod
    def from_attr(cls, attr):
        """Parse the op-attr string form.  Empty string = empty spec
        (a fused op whose chain was all-default)."""
        if not attr:
            return cls(())
        return cls(tuple(attr.split(_SEP)))

    def to_attr(self):
        return _SEP.join(self.stages)

    # -- grammar --------------------------------------------------------
    def validate(self):
        """Raise ValueError unless the stage list is a legal epilogue:
        every name registered, canonical order respected, no duplicate
        stage, at most one activation, terminal stages last."""
        last_order = -1
        seen = set()
        n_act = 0
        for i, name in enumerate(self.stages):
            st = STAGES.get(name)
            if st is None:
                raise ValueError(
                    f"epilogue spec {self.stages!r}: unknown stage "
                    f"{name!r} (registered: {sorted(STAGES)})")
            if name in seen:
                raise ValueError(
                    f"epilogue spec {self.stages!r}: duplicate stage "
                    f"{name!r}")
            seen.add(name)
            if st.order < last_order:
                raise ValueError(
                    f"epilogue spec {self.stages!r}: stage {name!r} "
                    "out of canonical order (bias -> stats_tap/"
                    "bn_apply -> residual -> act -> requantize -> "
                    "argmax)")
            last_order = st.order
            if st.is_act:
                n_act += 1
                if n_act > 1:
                    raise ValueError(
                        f"epilogue spec {self.stages!r}: more than "
                        "one activation stage")
            if st.terminal and i != len(self.stages) - 1:
                raise ValueError(
                    f"epilogue spec {self.stages!r}: terminal stage "
                    f"{name!r} must come last")
        return self

    # -- queries --------------------------------------------------------
    def __contains__(self, name):
        return name in self.stages

    def __iter__(self):
        return iter(self.stages)

    def __len__(self):
        return len(self.stages)

    def __eq__(self, other):
        return isinstance(other, EpilogueSpec) and \
            self.stages == other.stages

    def __hash__(self):
        return hash(self.stages)

    def __repr__(self):
        return f"EpilogueSpec({self.to_attr()!r})"

    @property
    def act(self):
        """The activation stage name, or '' when none."""
        for name in self.stages:
            if STAGES[name].is_act:
                return name
        return ""


def spec_attr(*, bias=False, stats_tap=False, bn_apply=False,
              residual=False, act="", requantize=False, argmax=False):
    """Build the canonical attr string from the shape of a fused op —
    the one way transpilers stamp the ``epilogue`` attr, so emitted
    specs are valid by construction."""
    stages = []
    if bias:
        stages.append("bias")
    if stats_tap:
        stages.append("stats_tap")
    if bn_apply:
        stages.append("bn_apply")
    if residual:
        stages.append("residual")
    if act:
        if act not in STAGES or not STAGES[act].is_act:
            raise ValueError(f"unknown activation stage {act!r}")
        stages.append(act)
    if requantize:
        stages.append("requantize")
    if argmax:
        stages.append("argmax")
    return EpilogueSpec(stages).to_attr()


def enumerate_specs(anchor):
    """Every legal spec a given anchor can carry — drives the
    parametrized stage-matrix parity test (tests/test_epilogue.py), so
    a new stage extends the test matrix without hand-enumeration.

    anchors: 'conv' (conv2d_epilogue), 'conv_bn' (conv2d_bn_train),
    'fc' (fc_epilogue), 'int8' (conv2d_int8 interlayer fold)."""
    if anchor == "conv":
        choices = (("", "bias"), ("", "residual"), ("", "relu"))
    elif anchor == "conv_bn":
        # stats_tap+bn_apply always ride together on conv2d_bn_train
        choices = (("", "bias"), ("stats_tap",), ("bn_apply",),
                   ("", "residual"), ("", "relu"))
    elif anchor == "fc":
        choices = (("", "bias"), ("", "residual"),
                   ("", "relu", "gelu"))
    elif anchor == "int8":
        choices = (("", "bias"), ("", "residual"), ("", "relu"),
                   ("", "requantize"))
    else:
        raise ValueError(f"unknown epilogue anchor {anchor!r}")
    def _prod(choice_lists):
        if not choice_lists:
            yield ()
            return
        for rest in _prod(choice_lists[1:]):
            for c in choice_lists[0]:
                yield ((c,) if c else ()) + rest
    for stages in _prod(list(choices)):
        yield EpilogueSpec(stages)


# ---------------------------------------------------------------------------
# the two shared evaluators + tail helpers (the ordering/rounding
# contract, stated once and consumed by every kernel/reference pair)
# ---------------------------------------------------------------------------

def _act_fn_acc(act, approximate=False):
    """Activation on the f32 accumulator (kernel order)."""
    if not act:
        return lambda a: a
    if act == "relu":
        return lambda a: jnp.maximum(a, 0.0)
    if act == "gelu":
        return lambda a: jax.nn.gelu(a, approximate=approximate)
    raise ValueError(f"unknown activation stage {act!r}")


def _act_fn_chain(act, approximate=False):
    """Activation as the discrete op the chain ran (jax.nn.relu is
    jnp.maximum(x, 0); gelu is the registered gelu op's exact call)."""
    if not act:
        return lambda y: y
    if act == "relu":
        return lambda y: jnp.maximum(y, 0)
    if act == "gelu":
        return lambda y: jax.nn.gelu(y, approximate=approximate)
    raise ValueError(f"unknown activation stage {act!r}")


def apply_acc_stages(acc, *, bias=None, residual=None, act="",
                     approximate=False):
    """ACCUMULATOR-order epilogue: every stage on the f32 accumulator,
    caller casts to the output dtype afterwards.  ``bias``/``residual``
    must already be broadcastable against ``acc`` (the kernels hand in
    their VMEM-resident blocks); both are accumulated in f32.

    This is the in-kernel body of conv2d_epilogue's tail and the fc
    epilogue kernel — one definition, every kernel."""
    if bias is not None:
        acc = acc + bias.astype(jnp.float32)
    if residual is not None:
        acc = acc + residual.astype(jnp.float32)
    return _act_fn_acc(act, approximate)(acc)


def apply_chain_stages(y, *, bias=None, residual=None, act="",
                       approximate=False):
    """CHAIN-order epilogue: the exact op sequence the unfused graph
    runs (bias add in y's dtype, residual add in y's dtype, act last).
    This is the XLA fallback/reference every parity test compares the
    kernels against."""
    if bias is not None:
        y = y + bias.astype(y.dtype)
    if residual is not None:
        y = y + residual.astype(y.dtype)
    return _act_fn_chain(act, approximate)(y)


def apply_bn_tail(t, out_dtype, residual=None, act=""):
    """The BN-apply tail shared bit-for-bit by the Pallas normalize
    kernel and its XLA reference: cast the f32 normalized value to the
    conv dtype FIRST, then residual add in that dtype, then act — the
    unfused batch_norm -> elementwise_add -> relu chain's op order and
    rounding points."""
    t = t.astype(out_dtype)
    if residual is not None:
        t = t + residual.astype(out_dtype)
    return _act_fn_chain(act)(t)


def quantize_tail(y, out_scale, bnd):
    """The requantize stage: quantize the epilogue result to the
    CONSUMER's calibrated scale (symmetric, zero-point 0) — the int8
    interlayer boundary's exact rounding point, shared by conv2d_int8,
    mul_int8 and the standalone requantize op."""
    so = jnp.maximum(out_scale.reshape(()).astype(jnp.float32), 1e-8)
    return jnp.clip(jnp.round(y.astype(jnp.float32) / so * bnd),
                    -bnd, bnd).astype(jnp.int8)


def greedy_logits_tail(logits, axis=-1):
    """The argmax stage: the decode engines' greedy sampling tail over
    the model's logits — stated here so a future sampling flow
    (top-k/top-p) is a stage insertion, not a fourth copy of the
    decode loop (serving/decode_engine.py routes its step, draft, and
    verify-sweep tails through this)."""
    return jnp.argmax(logits, axis=axis)


# ---------------------------------------------------------------------------
# fused matmul/fc epilogue kernel (NEW kernel surface: the transformer
# Adam-tail sibling the batch-slide diagnosis needs)
# ---------------------------------------------------------------------------

def _fc_reference(x2, w2, bias, residual, act, approximate):
    """Unfused composite: exactly the op sequence the IR runs when the
    flag is off (mul -> elementwise_add(bias) -> elementwise_add(skip)
    -> act), on the 2-D flattened operands.  Elementwise adds commute
    bitwise with the surrounding reshapes, so 2-D parity IS graph
    parity."""
    return apply_chain_stages(x2 @ w2, bias=bias, residual=residual,
                              act=act, approximate=approximate)


def _fc_ep_kernel(*refs, act, approximate, has_bias, has_res):
    """One grid cell = one [bm, bn] output tile: full-K contraction on
    the MXU with an f32 accumulator, plus the whole epilogue while the
    tile is VMEM-resident.  refs: x[bm,K], w[K,bn], (bias[1,bn]),
    (residual[bm,bn]), out[bm,bn]."""
    x_ref, w_ref = refs[0], refs[1]
    i = 2
    b_ref = refs[i] if has_bias else None
    i += int(has_bias)
    r_ref = refs[i] if has_res else None
    o_ref = refs[-1]

    ct = jnp.promote_types(x_ref.dtype, w_ref.dtype)
    acc = lax.dot_general(
        x_ref[...].astype(ct), w_ref[...].astype(ct),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    acc = apply_acc_stages(
        acc,
        bias=b_ref[0][None, :] if has_bias else None,
        residual=r_ref[...] if has_res else None,
        act=act, approximate=approximate)
    o_ref[...] = acc.astype(o_ref.dtype)


def _fc_vmem_estimate(m, k, n, bm, bn, has_bias, has_res, x_item,
                      w_item, o_item):
    x_b = bm * k * x_item
    w_b = k * bn * w_item
    o_b = bm * bn * o_item
    b_b = bn * 4 if has_bias else 0
    r_b = bm * bn * o_item if has_res else 0
    acc_b = bm * bn * 4
    return 2 * (x_b + w_b + o_b + b_b + r_b) + acc_b


def _fc_ep_pallas(x2, w2, bias, residual, act, approximate,
                  interpret=False):
    """x2: [M, K]; w2: [K, N]; bias: [N] or None; residual: [M, N] or
    None.  Tiles M and N only (full-K contraction per cell), so the
    accumulation order matches the unfused matmul's."""
    m, k = x2.shape
    _, n = w2.shape
    out_dtype = jnp.promote_types(x2.dtype, w2.dtype)
    bm = min(m, _FC_BLOCK_M)
    bn = min(n, _FC_BLOCK_N)
    if not interpret:
        est = _fc_vmem_estimate(
            m, k, n, bm, bn, bias is not None, residual is not None,
            x2.dtype.itemsize, w2.dtype.itemsize,
            jnp.dtype(out_dtype).itemsize)
        if est > _VMEM_BUDGET_BYTES:
            _count_impl("fc_epilogue", "xla")
            return _fc_reference(x2, w2, bias, residual, act,
                                 approximate)
    _count_impl("fc_epilogue", "interpret" if interpret else "pallas")

    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    in_specs = [
        pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
        pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)),
    ]
    operands = [x2, w2]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)))
        operands.append(bias.reshape(1, n))
    if residual is not None:
        in_specs.append(pl.BlockSpec((bm, bn),
                                     lambda mi, ni: (mi, ni)))
        operands.append(residual)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    kernel = functools.partial(
        _fc_ep_kernel, act=act, approximate=approximate,
        has_bias=bias is not None, has_res=residual is not None)
    return pl.pallas_call(
        kernel,
        name="pt_fc_ep",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        interpret=interpret,
        **params,
    )(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _fc_ep(x2, w2, bias, residual, act, approximate, impl):
    if impl in ("pallas", "interpret"):
        return _fc_ep_pallas(x2, w2, bias, residual, act, approximate,
                             interpret=impl == "interpret")
    _count_impl("fc_epilogue", "xla")
    return _fc_reference(x2, w2, bias, residual, act, approximate)


def _fc_ep_fwd(x2, w2, bias, residual, act, approximate, impl):
    y = _fc_ep(x2, w2, bias, residual, act, approximate, impl)
    return y, (x2, w2, bias, residual)


def _fc_ep_bwd(act, approximate, impl, res, g):
    """Backward via jax.vjp of the exact unfused composite — under jit
    the recomputed primal is DCE'd and the grads are bit-identical to
    the unfused graph's by construction (the conv-epilogue idiom,
    without hand-deriving the gelu backward)."""
    x2, w2, bias, residual = res
    args = [x2, w2]
    if bias is not None:
        args.append(bias)
    if residual is not None:
        args.append(residual)

    def comp(*a):
        i = 2
        b = a[i] if bias is not None else None
        i += int(bias is not None)
        r = a[i] if residual is not None else None
        return _fc_reference(a[0], a[1], b, r, act, approximate)

    _, vjp = jax.vjp(comp, *args)
    grads = list(vjp(g))
    dx, dw = grads[0], grads[1]
    i = 2
    db = grads[i] if bias is not None else None
    i += int(bias is not None)
    dres = grads[i] if residual is not None else None
    return dx, dw, db, dres


_fc_ep.defvjp(_fc_ep_fwd, _fc_ep_bwd)


def fc_epilogue(x, w, bias=None, residual=None, *, act=None,
                approximate=False, impl=None):
    """Fused matmul + bias + residual + act in one VMEM pass — the
    matmul sibling of conv2d_epilogue, covering the transformer train
    graph's fc+bias+relu/gelu chains.

    x: [M, K] (callers flatten leading dims like the mul op); w:
    [K, N]; bias: [N]; residual: [M, N]; act: None, "relu" or "gelu"
    (``approximate`` as in the gelu op).

    impl: None (auto: pallas on TPU, the exact unfused composite
    elsewhere), "pallas", "interpret", or "xla".  Differentiable in
    x/w/bias/residual via custom_vjp; the backward is jax.vjp of the
    unfused composite, so grads match the flag-off graph bit for
    bit."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    # one call line, flag on or off (see ops/pallas_kernels.py
    # flash_attention): the compiled module does not depend on the flag
    with _obs_device.annotate("fc_epilogue"), _kernel_scope():
        return _fc_ep(x, w, bias, residual, act or "",
                      bool(approximate), impl)


def _on_tpu():
    from paddle_tpu.ops.pallas_kernels import _on_tpu as _chip

    return _chip()


def _fc_impl_from_flag():
    """Map the fc_epilogue flag to an impl name ("off" still returns
    the exact unfused composite — a rewritten program loaded under a
    different flag state must stay bit-identical to the original).
    Same alias contract as conv_epilogue/_impl_from_flag."""
    from paddle_tpu.flags import get_flag

    mode = get_flag("fc_epilogue")
    if mode in ("pallas", "interpret", "xla"):
        return mode
    if mode == "on":
        return None                     # auto: pallas on TPU else xla
    return "xla"                        # "off" (or unknown): unfused


# ---------------------------------------------------------------------------
# IR op registration — the target of the fc arm of
# transpiler.fuse_epilogue
# ---------------------------------------------------------------------------

from paddle_tpu.core.registry import register_op  # noqa: E402

import numpy as np  # noqa: E402


@register_op("fc_epilogue",
             inputs=("X", "Y", "Bias", "Residual"),
             outputs=("Out",),
             optional=("Bias", "Residual"),
             attrs={"x_num_col_dims": 1, "y_num_col_dims": 1,
                    "act": "", "approximate": False, "epilogue": ""})
def _fc_epilogue_op(ins, attrs):
    """mul + channel bias + residual add + activation as ONE op —
    flattening semantics exactly as the mul op's (X at x_num_col_dims,
    Y at y_num_col_dims); Residual is read in the OUTPUT's shape and
    flattened alongside."""
    x, w = ins["X"], ins["Y"]
    bias = ins.get("Bias")
    residual = ins.get("Residual")
    xnc, ync = attrs["x_num_col_dims"], attrs["y_num_col_dims"]
    x2 = x.reshape((int(np.prod(x.shape[:xnc])), -1))
    w2 = w.reshape((int(np.prod(w.shape[:ync])), -1))
    out_shape = x.shape[:xnc] + w.shape[ync:]
    if residual is not None:
        residual = residual.reshape((x2.shape[0], w2.shape[1]))
    out = fc_epilogue(
        x2, w2, bias, residual,
        act=attrs.get("act") or None,
        approximate=attrs.get("approximate", False),
        impl=_fc_impl_from_flag())
    return {"Out": out.reshape(out_shape)}

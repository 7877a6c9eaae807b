"""Ops of a Kimi Delta Attention mixer (KDA; Kimi Linear,
arXiv:2510.26692): the chunked delta-rule recurrence `kda_scan` with its
registered grad op, the safe log-decay gate `kda_gate`, the per-head
L2 norm `head_l2_norm`, and the head-wise gated RMSNorm
`head_gated_rms_norm`.

Equations: docs/LING3_BLOCK.md.  models/ling3.py builds its mixer from
these through layers/kda.py; the kernels are ops/pallas_kda.py.

Precision under AMP (contrib/mixed_precision): the scan's MXU operands
Q, K and V are bfloat16; G, Beta, every decay, the inverse of a chunk's
triangular system, the running state and the saved block states are
float32 (fp16_utils._WHITE_KEEP_FP32).  The gate computes and writes
float32; the norms compute in float32 and write in X's dtype.

Every compute runs under a jax.named_scope (pt_kda, pt_kda_gate,
pt_head_l2_norm, pt_head_gated_norm); the kernels are the Mosaic calls
pt_kda_fwd and pt_kda_bwd.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import REQUIRED, register_op
from paddle_tpu.ops import pallas_kda
from paddle_tpu.ops import pallas_kernels as pk

_F32 = jnp.float32

_KDA_INPUTS = ("Q", "K", "V", "G", "Beta")
# decay: what the producer of G promises, which is which path the
# kernels take for the pairs inside a 16-row sub-block
# (ops/pallas_kda.py): "bounded", g >= pallas_kda.BOUNDED_G_MIN a token
# (kda_gate's sigmoid form at -5; the default, what a desc from before
# the attr came from), or "unbounded", exact for any g <= 0 (its
# softplus form).  layers.kda_scan sets it from the gate that made G.
_KDA_ATTRS = {"chunk_size": 64, "block_chunks": 4, "impl": "",
              "decay": "bounded"}


def _kda_impl(ins, attrs):
    """The impl the scan resolves to: the one asked for, else pallas on
    a TPU and xla elsewhere; xla too where the kernels cannot tile the
    sizes (pallas_kda.kernel_geom_ok).  Raises what does not fit."""
    d = pallas_kda.check_shapes(ins["Q"], ins["V"], ins["Beta"],
                                attrs["chunk_size"],
                                attrs["block_chunks"])[3]
    impl = attrs["impl"] or pk._auto_impl()
    if impl != "xla" and not pallas_kda.kernel_geom_ok(d):
        impl = "xla"
    return impl


_KDA_SAVED = ("O", "States", "Inverse")


def _decay(attrs):
    """The attr, or what an attr dict from before it means."""
    decay = attrs.get("decay", _KDA_ATTRS["decay"])
    if decay not in ("bounded", "unbounded"):
        raise ValueError("kda_scan: decay %r is neither bounded nor "
                         "unbounded" % (decay,))
    return decay


def _sizes(attrs):
    """(chunk, chunks a block, bounded): the kernels' static choices."""
    return (attrs["chunk_size"], attrs["block_chunks"],
            _decay(attrs) == "bounded")


@register_op("kda_scan", inputs=_KDA_INPUTS, outputs=_KDA_SAVED,
             attrs=_KDA_ATTRS)
def kda_scan(ins, attrs):
    """The delta-rule recurrence with a decay per key channel, by
    chunks of `chunk_size` tokens (ops/pallas_kda.py has the
    algorithm).  Per head, from a zero state:

        S_t = (I - Beta_t k_t k_t^T) Diag(exp G_t) S_{t-1}
              + Beta_t k_t v_t^T,      o_t = S_t^T q_t

    Q, K, V [B, T, H*D] token-major, G [B, T, H*D] the log-decay a key
    channel (float32, <= 0; attr `decay` says whether the gate keeps
    it >= -5.33 a token, "bounded", where a chunk kernel may form
    e^(-G) over the 16 tokens of a sub-block, or not, "unbounded",
    where it forms nothing above e^0), Beta [B, T, H] (in (0, 1) or,
    doubled by the caller, (0, 2)) -> O [B, T, H*D] in V's dtype and the two
    residuals kda_scan_grad reads, float32 whatever the operands are,
    with block = block_chunks chunk_size: States [B, T / block, H*D,
    D], the TRANSPOSED state each block of chunks starts from, and
    Inverse [B, H, T / block, chunk_size, block], the inverse T =
    (I + diag(Beta) M)^-1 of each chunk's triangular system, a block's
    side by side.  T % block != 0 raises; nothing is padded.
    chunk_size is a multiple of 16 up to 64.  impl: "" (pallas on a
    TPU, xla elsewhere), "pallas", "interpret", "xla" (the same chunked
    algorithm in jax.numpy)."""
    impl = _kda_impl(ins, attrs)
    pk._count_impl("kda_scan", impl)
    pk._count_impl("kda_scan_decay", _decay(attrs))
    args = tuple(ins[s] for s in _KDA_INPUTS)
    sizes = _sizes(attrs)
    with jax.named_scope("pt_kda"):
        if impl == "xla":
            # every decay a difference G_r - G_s of its own pair:
            # exact under either promise
            outs = pallas_kda.kda_chunked_xla(*args, *sizes[:2])
        else:
            outs = _scan_kernel(*args, sizes, impl)
    return dict(zip(_KDA_SAVED, outs))


def _grads_on_kept(args, states, inverse, g, sizes, impl):
    # see pallas_kernels._flash_attention_fwd: one call line
    with pk._obs_device.annotate("kda_scan_grad"), pk._kernel_scope():
        return pallas_kda.kda_bwd_pallas(
            *args, states, inverse, g, *sizes,
            interpret=impl == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _scan_kernel(q, k, v, g, beta, sizes, impl):
    """pt_kda_fwd, differentiated by pt_kda_bwd on the states and the
    inverses it wrote: what jax.vjp finds where a grad op's residuals
    are not all bound (a hand-built op, a desc from before Inverse),
    the op alone or in a recompute segment's replay."""
    with pk._obs_device.annotate("kda_scan"), pk._kernel_scope():
        return pallas_kda.kda_fwd_pallas(
            q, k, v, g, beta, *sizes, interpret=impl == "interpret")


def _scan_kernel_fwd(q, k, v, g, beta, sizes, impl):
    outs = _scan_kernel(q, k, v, g, beta, sizes, impl)
    return outs, ((q, k, v, g, beta), outs[1], outs[2])


def _scan_kernel_bwd(sizes, impl, kept, cts):
    # traced under the forward's name stack: the op's scope is on it
    return _grads_on_kept(*kept, cts[0], sizes, impl)


_scan_kernel.defvjp(_scan_kernel_fwd, _scan_kernel_bwd)


def _kda_grad_reads_saved(ins, attrs):
    """Whether kda_scan_grad runs the backward kernel on the forward's
    States and Inverse: both bound (with O, which a recompute segment
    takes in place of the op's replay) and the impl a kernel
    (OpDef.reads_saved).  A desc from before Inverse binds two of the
    three: the forward runs again."""
    return all(s in ins for s in _KDA_SAVED) \
        and _kda_impl(ins, attrs) != "xla"


@register_op("kda_scan_grad",
             inputs=_KDA_INPUTS + _KDA_SAVED + ("O@GRAD",),
             outputs=tuple(s + "@GRAD" for s in _KDA_INPUTS),
             optional=_KDA_SAVED, attrs=_KDA_ATTRS,
             differentiable=False, reads_saved=_kda_grad_reads_saved)
def kda_scan_grad(ins, attrs):
    """Hand-written, as ssd_scan_grad is and for its reason: the
    generic jax.vjp grad op would run pt_kda_fwd a second time in every
    layer, and a recompute segment's replay a third.

      * O, States and Inverse bound (append_backward binds them; a
        recompute segment binds them on the op it replays) and the
        impl a kernel: pt_kda_bwd on the saved block states and
        chunk inverses.  The forward kernel does not run again, and
        no chunk's triangular system is inverted a second time;
      * anything else (a hand-built op, a desc from before Inverse,
        the xla impl): jax.vjp over the forward op's compute, which on
        a kernel impl is the forward kernel again for both residuals
        and pt_kda_bwd on them (`_scan_kernel`).

    paddle_tpu_kernel_impl_total{kernel="kda_scan_grad"} says which:
    impl="saved" | "recompute"."""
    args = tuple(ins[s] for s in _KDA_INPUTS)
    g = ins["O@GRAD"]
    saved = _kda_grad_reads_saved(ins, attrs)
    pk._count_impl("kda_scan_grad", "saved" if saved else "recompute")
    if saved:
        with jax.named_scope("pt_kda"):
            grads = _grads_on_kept(
                args, ins["States"], ins["Inverse"], g, _sizes(attrs),
                _kda_impl(ins, attrs))
    else:
        _, vjp = jax.vjp(
            lambda *a: kda_scan(dict(zip(_KDA_INPUTS, a)), attrs)["O"],
            *args)
        grads = vjp(g)
    return {s + "@GRAD": v for s, v in zip(_KDA_INPUTS, grads)}


@register_op("kda_gate", inputs=("X", "ALog", "DtBias"), outputs=("G",),
             attrs={"lower_bound": -5.0, "form": "sigmoid_bound"})
def kda_gate(ins, attrs):
    """The log-decay gate: X [B, T, H*D] the decay projection, ALog
    [H], DtBias [H*D] -> G float32, by `form`:

        "sigmoid_bound"  G = lower_bound * sigmoid(exp(ALog_h) * (X + DtBias))
        "softplus"       G = -exp(ALog_h) * softplus(X + DtBias)

    The first (the safe gate) lies in (lower_bound, 0), so that exp G
    lies in (e^lower_bound, 1) a channel; lower_bound is negative.
    The second (Kimi Linear's) lies in (-inf, 0): no bound, and
    lower_bound is not read.  Counted in
    paddle_tpu_kernel_impl_total{kernel="kda_gate_form"}."""
    x = ins["X"]
    h = ins["ALog"].shape[0]
    form = attrs.get("form", "sigmoid_bound")
    if form not in ("sigmoid_bound", "softplus"):
        raise ValueError("kda_gate: form %r" % (form,))
    lower_bound = attrs.get("lower_bound", -5.0)
    if form == "sigmoid_bound" and lower_bound >= 0:
        raise ValueError("kda_gate: lower_bound %r is not negative"
                         % (lower_bound,))
    pk._count_impl("kda_gate_form", form)
    with jax.named_scope("pt_kda_gate"):
        rate = jnp.repeat(jnp.exp(ins["ALog"].astype(_F32)),
                          x.shape[-1] // h)
        shifted = x.astype(_F32) + ins["DtBias"].astype(_F32)
        if form == "softplus":
            return {"G": -rate * jax.nn.softplus(shifted)}
        return {"G": lower_bound * jax.nn.sigmoid(rate * shifted)}


def _head_indicator(width, n_head):
    """float32 [H*D, H]: 1 where a channel of the last axis belongs to
    a head.  The norms reduce a head and spread a head's factor back
    as two thin products against it, so X keeps its [.., H*D] layout:
    a reshape to [.., H, D] is a relayout of every operand on the chip
    and its reduce a lane reduce a head (0.70 -> 0.25 ms an L2 norm
    forward at 4,096 x 4,096 bfloat16; PERF.md section 6, PR 41)."""
    if width % n_head:
        raise ValueError("%d channels are no multiple of %d heads"
                         % (width, n_head))
    return (jnp.arange(width)[:, None] // (width // n_head)
            == jnp.arange(n_head)[None]).astype(_F32)


def _head_sum(x, indicator):
    """x float32 [.., H*D] -> its sum over each head's D, [.., H]."""
    return jnp.matmul(x, indicator, precision=lax.Precision.HIGHEST)


def _head_spread(per_head, indicator):
    """per_head float32 [.., H] -> [.., H*D], a head's value at each of
    its channels (exact: one term a channel, float32 `highest`)."""
    return jnp.matmul(per_head, indicator.T,
                      precision=lax.Precision.HIGHEST)


@register_op("head_l2_norm", inputs=("X",), outputs=("Y",),
             attrs={"n_head": REQUIRED, "scale": 1.0, "epsilon": 1e-6})
def head_l2_norm(ins, attrs):
    """Y = scale * X_h / sqrt(sum(X_h^2) + epsilon) for each of the
    n_head slices X_h of the last axis, float32 inside, Y in X's
    dtype."""
    x = ins["X"]
    with jax.named_scope("pt_head_l2_norm"):
        ind = _head_indicator(x.shape[-1], attrs["n_head"])
        xf = x.astype(_F32)
        inv = attrs["scale"] * lax.rsqrt(
            _head_sum(jnp.square(xf), ind) + attrs["epsilon"])
        return {"Y": (xf * _head_spread(inv, ind)).astype(x.dtype)}


@register_op("head_gated_rms_norm", inputs=("X", "Gate", "Scale"),
             outputs=("Y",), attrs={"epsilon": 1e-6, "n_head": 0},
             optional=("Gate", "Scale"))
def head_gated_rms_norm(ins, attrs):
    """X [.., H*D], Gate [.., H] (one logit a head), Scale [D] ->

        Y_h = sigmoid(Gate_h) * RMSNorm(X_h) * Scale

    the norm a head (the statistic over the head's D entries), the gate
    a head and AFTER the norm.  Gate [.., H*D], as wide as X: one
    logit a CHANNEL, Y = sigmoid(Gate) * RMSNorm_h(X) * Scale with H
    = n_head.  Scale unbound: no norm, Y = sigmoid(Gate) * X, a head
    (latent attention's output gate) or a channel (a grouped-KV
    attention layer's).  Gate unbound: no gate, Y_h = RMSNorm(X_h) *
    Scale with H = n_head (the norm on an attention layer's q and k;
    scope pt_head_rms_norm).  Float32 inside, Y in X's dtype."""
    x, gate, scale = ins["X"], ins.get("Gate"), ins.get("Scale")
    width = x.shape[-1]
    # as wide as X, and the heads said beside it or no norm to need
    # them: a gate a channel
    said = attrs.get("n_head", 0)
    channel = gate is not None and gate.shape[-1] == width \
        and (said > 0 or scale is None)
    n_head = gate.shape[-1] if gate is not None and not channel else said
    if (gate is None and scale is None) or (
            (scale is not None or not channel)
            and (n_head < 1 or width % n_head)):
        raise ValueError(
            "head_gated_rms_norm: %d channels in %d heads%s"
            % (width, n_head,
               "" if gate is not None else ", no gate and no norm"))
    with jax.named_scope("pt_head_rms_norm" if gate is None
                         else "pt_head_gated_norm"):
        xf = x.astype(_F32)
        if channel and scale is None:
            return {"Y": (xf * jax.nn.sigmoid(gate.astype(_F32))
                          ).astype(x.dtype)}
        ind = _head_indicator(width, n_head)
        per_head = 1.0 if gate is None or channel \
            else jax.nn.sigmoid(gate.astype(_F32))
        if scale is not None:
            per_head = per_head * lax.rsqrt(
                _head_sum(jnp.square(xf), ind) / (width // n_head)
                + attrs["epsilon"])
            xf = xf * jnp.tile(scale.astype(_F32), n_head)
        y = xf * _head_spread(per_head, ind)
        if channel:
            y = y * jax.nn.sigmoid(gate.astype(_F32))
        return {"Y": y.astype(x.dtype)}

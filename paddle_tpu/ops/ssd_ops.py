"""Ops of a Mamba-2 state-space mixer: the chunked scan `ssd_scan` with
its registered grad op, the depthwise causal convolution over time
`causal_conv1d`, and the gated RMSNorm `gated_rms_norm`.

Equations: docs/GRANITE4_BLOCK.md (arXiv:2405.21060; names after the
`granitemoehybrid` modelling code).  models/granite_hybrid.py builds
its mixer from these through layers/ssm.py; the kernels are
ops/pallas_ssd.py.

Precision under AMP (contrib/mixed_precision): the scan's MXU operands
X, B and C are bfloat16; Dt, A, D, the cumulative sums, every decay,
the running state and the saved chunk states are float32
(fp16_utils._WHITE_KEEP_FP32).  The convolution and the norm compute in
float32 and write in X's dtype.

Every compute runs under a jax.named_scope (pt_ssd, pt_causal_conv1d
or, a gated convolution, pt_gated_conv, pt_gated_rms_norm); the
kernels are the Mosaic calls pt_ssd_fwd and pt_ssd_bwd.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops import pallas_conv1d
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops import pallas_ssd

_F32 = jnp.float32

_SSD_INPUTS = ("X", "Dt", "A", "B", "C", "D")
_SSD_ATTRS = {"chunk_size": 256, "impl": ""}


def _ssd_impl(ins, attrs):
    """The impl the scan resolves to: the one asked for, else pallas on
    a TPU and xla elsewhere; xla too where the kernels cannot tile the
    sizes (pallas_ssd.kernel_geom_ok).  Raises what does not fit."""
    x, dt = ins["X"], ins["Dt"]
    if ins["B"].shape[-1] != ins["C"].shape[-1]:
        raise ValueError("ssd_scan: B and C differ in state size")
    p = pallas_ssd.check_shapes(x, dt, ins["B"], attrs["chunk_size"])[3]
    impl = attrs["impl"] or pk._auto_impl()
    if impl != "xla" and not pallas_ssd.kernel_geom_ok(
            p, attrs["chunk_size"]):
        impl = "xla"
    return impl


@register_op("ssd_scan", inputs=_SSD_INPUTS, outputs=("Y", "States"),
             attrs=_SSD_ATTRS)
def ssd_scan(ins, attrs):
    """The Mamba-2 recurrence over time, by chunks of `chunk_size`
    tokens (ops/pallas_ssd.py has the algorithm).  Per head h, from a
    zero state:

        S_t = exp(Dt_t A) S_{t-1} + Dt_t x_t B_t^T,  y_t = S_t C_t + D x_t

    X [B, T, H*P] token-major, Dt [B, T, H] (positive: after the
    softplus), A [H] (negative), B and C [B, T, N] (one group: every
    head reads the same B and C), D [H] -> Y [B, T, H*P] in X's dtype
    and States, float32 [B, T/chunk, H*P, N]: the state each chunk
    starts from, the residual ssd_scan_grad reads.  T % chunk_size != 0
    raises; nothing is padded.  impl: "" (pallas on a TPU, xla
    elsewhere), "pallas", "interpret", "xla" (the same chunked
    algorithm in jax.numpy)."""
    impl = _ssd_impl(ins, attrs)
    pk._count_impl("ssd_scan", impl)
    args = tuple(ins[s] for s in _SSD_INPUTS)
    with jax.named_scope("pt_ssd"):
        if impl == "xla":
            y, states = pallas_ssd.ssd_chunked_xla(
                *args, attrs["chunk_size"])
        else:
            # see pallas_kernels._flash_attention_fwd: one call line
            with pk._obs_device.annotate("ssd_scan"), pk._kernel_scope():
                y, states = pallas_ssd.ssd_fwd_pallas(
                    *args, chunk=attrs["chunk_size"],
                    interpret=impl == "interpret")
    return {"Y": y, "States": states}


def _ssd_grad_reads_saved(ins, attrs):
    """Whether ssd_scan_grad runs the backward kernel on the forward's
    States: bound (with Y, which a recompute segment takes in place of
    the op's replay) and the impl a kernel (OpDef.reads_saved)."""
    return "Y" in ins and "States" in ins \
        and _ssd_impl(ins, attrs) != "xla"


@register_op("ssd_scan_grad",
             inputs=_SSD_INPUTS + ("Y", "States", "Y@GRAD"),
             outputs=tuple(s + "@GRAD" for s in _SSD_INPUTS),
             optional=("Y", "States"), attrs=_SSD_ATTRS,
             differentiable=False, reads_saved=_ssd_grad_reads_saved)
def ssd_scan_grad(ins, attrs):
    """Hand-written, as flash_attention_grad is and for its reason: the
    generic jax.vjp grad op would run pt_ssd_fwd a second time in every
    layer, and a recompute segment's replay a third.

      * Y and States bound (append_backward binds them; a recompute
        segment binds them on the op it replays) and the impl a
        kernel: pt_ssd_bwd on the saved chunk states.  The forward
        kernel does not run again;
      * unbound (a hand-built op): the forward kernel again for the
        states, then pt_ssd_bwd;
      * the xla impl: jax.vjp over the forward op's compute.

    paddle_tpu_kernel_impl_total{kernel="ssd_scan_grad"} says which:
    impl="saved" | "recompute"."""
    args = tuple(ins[s] for s in _SSD_INPUTS)
    g = ins["Y@GRAD"]
    impl = _ssd_impl(ins, attrs)
    saved = impl != "xla" and "Y" in ins and "States" in ins
    pk._count_impl("ssd_scan_grad", "saved" if saved else "recompute")
    if impl == "xla":
        _, vjp = jax.vjp(
            lambda *a: ssd_scan(dict(zip(_SSD_INPUTS, a)), attrs)["Y"],
            *args)
        grads = vjp(g)
    else:
        states = ins["States"] if saved else ssd_scan(ins, attrs)["States"]
        with jax.named_scope("pt_ssd"), \
                pk._obs_device.annotate("ssd_scan_grad"), \
                pk._kernel_scope():
            grads = pallas_ssd.ssd_bwd_pallas(
                *args, states, g, chunk=attrs["chunk_size"],
                interpret=impl == "interpret")
    return {s + "@GRAD": v for s, v in zip(_SSD_INPUTS, grads)}


_CONV_ATTRS = {"activation": "silu", "impl": "", "gated": False}


def _conv_impl(ins, attrs):
    """The impl the convolution resolves to: the one asked for, else
    pallas on a TPU and xla elsewhere; xla too where the kernels cannot
    tile what the op reads (pallas_conv1d.tiles: C a multiple of 128,
    T a multiple of a row tile, K <= 8).  Raises an unknown
    activation, and a gated X that is not three times W's channels."""
    act = attrs["activation"]
    if act not in ("silu", ""):
        raise ValueError("causal_conv1d: activation %r is neither 'silu' "
                         "nor ''" % (act,))
    impl = attrs.get("impl") or pk._auto_impl()
    (_, t, width), (c, k) = ins["X"].shape, ins["W"].shape
    thirds = 3 if attrs.get("gated") else 1
    if width != thirds * c:
        raise ValueError(
            "causal_conv1d: X has %d channels for a filter of %d; it "
            "takes %d times the filter's%s"
            % (width, c, thirds, " ([Gb | Gc | x], gated)"
               if thirds == 3 else ""))
    if impl != "xla" and pallas_conv1d.tiles(t, c, k) is None:
        impl = "xla"
    return impl


def _conv_xla(x, w, bias, act, gated=False):
    k, t = w.shape[-1], x.shape[1]
    xf = x.astype(_F32)
    if gated:
        gb, gc, xf = jnp.split(xf, 3, axis=-1)
        xf = gb * xf
    xp = jnp.pad(xf, ((0, 0), (k - 1, 0), (0, 0)))
    wf = w.astype(_F32)
    y = sum(xp[:, i:i + t, :] * wf[:, i] for i in range(k))
    if bias is not None:
        y = y + bias.astype(_F32)
    if act == "silu":
        y = jax.nn.silu(y)
    if gated:
        y = gc * y
    return y.astype(x.dtype)


def _conv_grads(x, w, bias, g, act, gated, impl):
    """(dX, dW, dBias or None) by pt_conv1d_bwd (impl a kernel) or by
    jax.vjp of the XLA graph."""
    if impl == "xla":
        _, vjp = jax.vjp(lambda *a: _conv_xla(*a, act, gated), x, w, bias)
        return vjp(g)
    # see pallas_kernels._flash_attention_fwd: one call line
    with pk._obs_device.annotate("causal_conv1d_grad"), pk._kernel_scope():
        return pallas_conv1d.conv1d_bwd_pallas(
            x, w, bias, g, act=act, gated=gated,
            interpret=impl == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernel(x, w, bias, act, gated, impl):
    """pt_conv1d_fwd, differentiated by pt_conv1d_bwd: what jax.vjp of
    a recompute segment's replay finds where no grad op is bound."""
    with pk._obs_device.annotate("causal_conv1d"), pk._kernel_scope():
        return pallas_conv1d.conv1d_fwd_pallas(
            x, w, bias, act=act, gated=gated,
            interpret=impl == "interpret")


def _conv_kernel_fwd(x, w, bias, act, gated, impl):
    return _conv_kernel(x, w, bias, act, gated, impl), (x, w, bias)


def _conv_kernel_bwd(act, gated, impl, res, g):
    # traced under the forward's name stack: the op's scope is on it
    return _conv_grads(*res, g, act, gated, impl)


_conv_kernel.defvjp(_conv_kernel_fwd, _conv_kernel_bwd)


def _conv_scope(attrs):
    return jax.named_scope("pt_gated_conv" if attrs.get("gated")
                           else "pt_causal_conv1d")


@register_op("causal_conv1d", inputs=("X", "W", "Bias"), outputs=("Y",),
             attrs=_CONV_ATTRS, optional=("Bias",))
def causal_conv1d(ins, attrs):
    """Depthwise convolution over time that never reads ahead: X
    [B, T, C], W [C, K], Bias [C] ->

        Y[b, t, c] = act(Bias[c] + sum_k W[c, k] X[b, t - (K-1) + k, c])

    with X zero before t = 0 (left-padded by K - 1).  activation "silu"
    or "" (none).  `gated`: X is one projection [B, T, 3 C] whose
    thirds are [Gb | Gc | x]; the convolution reads Gb * x and Y is Gc
    times the above, [B, T, C] (an LFM2 layer's whole mixer between its
    two projections).  Float32 inside, Y in X's dtype.  impl: "" (the
    kernel pt_conv1d_fwd on a TPU where it can tile X, the XLA graph
    elsewhere), "pallas", "interpret", "xla"."""
    impl = _conv_impl(ins, attrs)
    pk._count_impl("causal_conv1d", impl)
    x, w, bias = ins["X"], ins["W"], ins.get("Bias")
    act, gated = attrs["activation"], bool(attrs.get("gated"))
    if gated:
        # which form applied the gates; an ungated op adds no series
        pk._count_impl("causal_conv1d_gates",
                       "xla" if impl == "xla" else "fused")
    with _conv_scope(attrs):
        if impl == "xla":
            return {"Y": _conv_xla(x, w, bias, act, gated)}
        return {"Y": _conv_kernel(x, w, bias, act, gated, impl)}


@register_op("causal_conv1d_grad", inputs=("X", "W", "Bias", "Y@GRAD"),
             outputs=("X@GRAD", "W@GRAD", "Bias@GRAD"),
             optional=("Bias",), attrs=_CONV_ATTRS, differentiable=False)
def causal_conv1d_grad(ins, attrs):
    """Hand-written, as ssd_scan_grad is and for its reason: the
    generic jax.vjp grad op would run pt_conv1d_fwd again.  Reads X, W,
    Bias and Y@GRAD only (pt_conv1d_bwd forms z again from X, and a
    gated op's Gb * x and act(z) too): no forward output is bound.
    X@GRAD has X's shape: of a gated op the whole projection's
    gradient, written once.  The xla impl: jax.vjp of the XLA graph.
    paddle_tpu_kernel_impl_total{kernel="causal_conv1d_grad"} says
    which."""
    impl = _conv_impl(ins, attrs)
    pk._count_impl("causal_conv1d_grad", impl)
    with _conv_scope(attrs):
        dx, dw, db = _conv_grads(
            ins["X"], ins["W"], ins.get("Bias"), ins["Y@GRAD"],
            attrs["activation"], bool(attrs.get("gated")), impl)
    out = {"X@GRAD": dx, "W@GRAD": dw}
    if db is not None:
        out["Bias@GRAD"] = db
    return out


@register_op("gated_rms_norm", inputs=("X", "Gate", "Scale"),
             outputs=("Y",), attrs={"epsilon": 1e-6})
def gated_rms_norm(ins, attrs):
    """Y = RMSNorm(X * silu(Gate)) * Scale over the last axis (the gate
    BEFORE the norm, one group: the statistic is over all of the last
    axis), float32 inside, Y in X's dtype."""
    from paddle_tpu.ops.llm_ops import _rms

    x = ins["X"]
    with jax.named_scope("pt_gated_rms_norm"):
        u = x.astype(_F32) * jax.nn.silu(ins["Gate"].astype(_F32))
        y = _rms(u, attrs["epsilon"]) * ins["Scale"].astype(_F32)
        return {"Y": y.astype(x.dtype)}

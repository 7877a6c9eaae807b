"""Pallas TPU kernel: fused convolution + epilogue (bias/residual/ReLU).

Capability anchor: the 2026-08-01 rn50 diagnosis (tools/hlo_traffic.py,
VERDICT round 5) showed the ResNet-50 train step is HBM-bound with
~9.3 GB/step of residual-add/ReLU/bias elementwise glue that XLA will
NOT fuse into its convolution custom-calls — every bottleneck block
writes the conv result to HBM, reads it back for the add, writes the
sum, reads it back for the ReLU.  This kernel computes

    out = act(conv(x, w) + bias + residual)

in ONE VMEM-resident pass: the conv accumulator never leaves VMEM
between the matmul and the epilogue, so the glue bytes disappear from
the HBM roofline entirely.

Layout: NHWC activations (the TPU fast path nhwc_transpile produces),
OIHW filters (the repo's layout-independent param convention; the
transpose to HWIO is folded by XLA into the weight layout).  The
kernel grid is (N, Cout/bco): each cell holds one image's padded input
and one Cout tile of the filter in VMEM and runs the KH*KW tap loop as
static MXU dot_generals over [OH*OW, Cin] patches — im2col without the
materialization (taps are strided VMEM slices of the resident image).
Stride is handled by strided slicing inside VMEM; padding is applied
once in XLA before the call.

Backward: `jax.custom_vjp`.  The epilogue backward is closed-form
(mask by the saved post-ReLU output, reduce for the bias), and dx/dw
reuse the existing XLA conv gradients via jax.vjp of the plain conv
core — under jit the unused primal is DCE'd, leaving exactly the two
transposed convolutions XLA already runs for the unfused graph.

Dispatch is behind the typed flag ``conv_epilogue`` (flags.py, default
"off"): ops/nn.py conv2d routes NHWC convs here when the flag is on,
and transpiler.fuse_conv_epilogue rewrites conv+bias+residual+ReLU IR
chains onto the registered ``conv2d_epilogue`` op.  ``interpret=True``
(impl="interpret") runs the same kernel under the Pallas interpreter
for CPU-parity tests (tests/test_pallas_conv.py).
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

# VMEM budget for the compiled kernel: one image block + filter tile +
# accumulator + residual tile, doubled for Pallas' input double
# buffering, must fit comfortably in ~16 MB/core.  Shapes over budget
# fall back to the XLA composite (still correct, just unfused).
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024
_DEFAULT_BLOCK_CO = 256


# ---------------------------------------------------------------------------
# reference (XLA) implementation — also the fallback path
# ---------------------------------------------------------------------------

def _conv_core(x, w, strides, padding):
    """Plain NHWC conv with OIHW filters — the op the unfused graph
    runs and the backward's gradient source."""
    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "OIHW", "NHWC"))
    return lax.conv_general_dilated(
        x, w, window_strides=strides, padding=list(padding),
        dimension_numbers=dn)


def _epilogue_xla(y, bias, residual, act):
    from paddle_tpu.ops.epilogue import apply_chain_stages

    return apply_chain_stages(y, bias=bias, residual=residual, act=act)


def _reference(x, w, bias, residual, strides, padding, act):
    """Unfused composite: exactly the op sequence the IR runs when the
    flag is off (conv -> bias add -> residual add -> act)."""
    return _epilogue_xla(_conv_core(x, w, strides, padding), bias,
                         residual, act)


# ---------------------------------------------------------------------------
# pallas forward kernel
# ---------------------------------------------------------------------------

def _conv_ep_kernel(*refs, kh, kw, sh, sw, oh, ow, act, has_bias,
                    has_res):
    """One grid cell = one (image, Cout-tile): full KH*KW*Cin reduction
    plus the whole epilogue, accumulator resident in VMEM throughout.

    refs: x[1,HP,WP,Cin], w[KH,KW,Cin,bco], (bias[1,bco]),
    (residual[1,OH,OW,bco]), out[1,OH,OW,bco]."""
    x_ref, w_ref = refs[0], refs[1]
    i = 2
    b_ref = refs[i] if has_bias else None
    i += int(has_bias)
    r_ref = refs[i] if has_res else None
    o_ref = refs[-1]

    x = x_ref[0]                                   # [HP, WP, Cin]
    cin = x.shape[-1]
    bco = o_ref.shape[-1]
    ct = jnp.promote_types(x_ref.dtype, w_ref.dtype)
    acc = jnp.zeros((oh * ow, bco), jnp.float32)
    # static tap loop: each (i, j) filter tap is a VMEM slice of the
    # resident image — [OH, OW, Cin] flattened onto the MXU as an
    # [OH*OW, Cin] x [Cin, bco] contraction (im2col with no
    # materialized patch matrix).  Stride > 1 is a contiguous slice +
    # reshape + unit-index, NOT a strided slice: Mosaic's
    # vector.extract_strided_slice only allows strides in [1, 2)
    # (caught by tools/tpu_lowering_check.py cross-lowering — never
    # cost a chip window)
    for ti in range(kh):
        for tj in range(kw):
            p = lax.slice(x, (ti, tj, 0),
                          (ti + oh * sh - (sh - 1),
                           tj + ow * sw - (sw - 1), cin))
            if sh > 1:
                # pad the tail so rows split evenly, then keep phase 0
                p = jnp.pad(p, ((0, sh - 1), (0, 0), (0, 0)))
                p = p.reshape(oh, sh, p.shape[1], cin)[:, 0]
            if sw > 1:
                p = jnp.pad(p, ((0, 0), (0, sw - 1), (0, 0)))
                p = p.reshape(oh, ow, sw, cin)[:, :, 0]
            acc = acc + lax.dot_general(
                p.reshape(oh * ow, cin).astype(ct),
                w_ref[ti, tj].astype(ct),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    # the accumulator-order epilogue (ops/epilogue.py): bias/residual
    # in f32 on the resident accumulator, act, ONE cast at the end
    from paddle_tpu.ops.epilogue import apply_acc_stages

    acc = apply_acc_stages(
        acc,
        bias=b_ref[0][None, :] if has_bias else None,
        residual=r_ref[0].reshape(oh * ow, bco) if has_res else None,
        act=act)
    o_ref[0] = acc.reshape(oh, ow, bco).astype(o_ref.dtype)


def _out_spatial(h, w, kh, kw, sh, sw, padding):
    (ph0, ph1), (pw0, pw1) = padding
    oh = (h + ph0 + ph1 - kh) // sh + 1
    ow = (w + pw0 + pw1 - kw) // sw + 1
    return oh, ow


def _block_co(cout):
    if cout <= _DEFAULT_BLOCK_CO:
        return cout
    return _DEFAULT_BLOCK_CO


def _vmem_estimate(xp_shape, w_shape, oh, ow, bco, has_res, x_itemsize,
                   w_itemsize, o_itemsize):
    _, hp, wp, cin = xp_shape
    kh, kw = w_shape[0], w_shape[1]
    x_b = hp * wp * cin * x_itemsize
    w_b = kh * kw * cin * bco * w_itemsize
    o_b = oh * ow * bco * o_itemsize
    r_b = oh * ow * bco * o_itemsize if has_res else 0
    acc_b = oh * ow * bco * 4
    # inputs/outputs are double buffered by the pipeline; the
    # accumulator lives once
    return 2 * (x_b + w_b + o_b + r_b) + acc_b


def _conv_ep_pallas(x, w, bias, residual, strides, padding, act,
                    interpret=False):
    """x: [N,H,W,Cin] NHWC; w: [O,Cin,KH,KW] OIHW."""
    n, h, wd, cin = x.shape
    cout, _, kh, kw = w.shape
    sh, sw = strides
    oh, ow = _out_spatial(h, wd, kh, kw, sh, sw, padding)
    (ph0, _), (pw0, _) = padding
    # pad once in XLA to exactly the span the tap loop reads:
    # HP = (OH-1)*sh + KH (bottom/right padding beyond what the conv
    # needs is sliced off so kernel slices stay in bounds)
    hp = (oh - 1) * sh + kh
    wp = (ow - 1) * sw + kw
    xp = jnp.pad(x, ((0, 0),
                     (ph0, max(hp - h - ph0, 0)),
                     (pw0, max(wp - wd - pw0, 0)),
                     (0, 0)))[:, :hp, :wp, :]
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))        # [KH,KW,Cin,O]
    out_dtype = jnp.promote_types(x.dtype, w.dtype)

    bco = _block_co(cout)
    if not interpret:
        est = _vmem_estimate(xp.shape, (kh, kw), oh, ow, bco,
                             residual is not None, xp.dtype.itemsize,
                             w_hwio.dtype.itemsize,
                             jnp.dtype(out_dtype).itemsize)
        if est > _VMEM_BUDGET_BYTES:
            _count_impl("conv2d_epilogue", "xla")
            return _reference(x, w, bias, residual, strides, padding,
                              act)
    _count_impl("conv2d_epilogue", "interpret" if interpret else "pallas")

    grid = (n, pl.cdiv(cout, bco))
    in_specs = [
        pl.BlockSpec((1, hp, wp, cin), lambda ni, co: (ni, 0, 0, 0)),
        pl.BlockSpec((kh, kw, cin, bco), lambda ni, co: (0, 0, 0, co)),
    ]
    operands = [xp, w_hwio]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bco), lambda ni, co: (0, co)))
        operands.append(bias.reshape(1, cout))
    if residual is not None:
        in_specs.append(pl.BlockSpec((1, oh, ow, bco),
                                     lambda ni, co: (ni, 0, 0, co)))
        operands.append(residual)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    kernel = functools.partial(
        _conv_ep_kernel, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh, ow=ow,
        act=act, has_bias=bias is not None,
        has_res=residual is not None)
    return pl.pallas_call(
        kernel,
        name="pt_conv_ep",
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, oh, ow, bco),
                               lambda ni, co: (ni, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((n, oh, ow, cout), out_dtype),
        interpret=interpret,
        **params,
    )(*operands)


# ---------------------------------------------------------------------------
# public differentiable entry
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _conv_ep(x, w, bias, residual, strides, padding, act, impl):
    if impl in ("pallas", "interpret"):
        return _conv_ep_pallas(x, w, bias, residual, strides, padding,
                               act, interpret=impl == "interpret")
    _count_impl("conv2d_epilogue", "xla")
    return _reference(x, w, bias, residual, strides, padding, act)


def _conv_ep_fwd(x, w, bias, residual, strides, padding, act, impl):
    y = _conv_ep(x, w, bias, residual, strides, padding, act, impl)
    return y, (x, w, bias, residual, y)


def _conv_ep_bwd(strides, padding, act, impl, res, g):
    x, w, bias, residual, y = res
    gf = g
    if act == "relu":
        # the saved output IS post-ReLU: y > 0 <=> pre-activation > 0
        gf = jnp.where(y > 0, g, jnp.zeros_like(g))
    # dx/dw via the existing XLA conv gradients: vjp of the plain conv
    # core — the unused primal conv is DCE'd under jit, leaving the
    # same transposed convs the unfused graph runs
    ct = jnp.promote_types(x.dtype, w.dtype)
    _, vjp = jax.vjp(
        lambda a, b: _conv_core(a, b, strides, padding), x, w)
    dx, dw = vjp(gf.astype(ct))
    db = None
    if bias is not None:
        db = jnp.sum(gf.astype(jnp.float32),
                     axis=(0, 1, 2)).astype(bias.dtype)
    dres = None
    if residual is not None:
        dres = gf.astype(residual.dtype)
    return dx, dw, db, dres


_conv_ep.defvjp(_conv_ep_fwd, _conv_ep_bwd)


def _norm_padding(paddings):
    """[ph, pw] or ((ph0,ph1),(pw0,pw1)) -> ((ph0,ph1),(pw0,pw1))."""
    p = tuple(paddings)
    if len(p) == 2 and not isinstance(p[0], (tuple, list)):
        return ((int(p[0]), int(p[0])), (int(p[1]), int(p[1])))
    return tuple((int(a), int(b)) for a, b in p)


# ---------------------------------------------------------------------------
# conv + BN-stats sibling outputs (the TRAIN-chain fusion, ISSUE 4)
#
# The train graph can't use the epilogue kernel's full fusion because
# BN *batch* statistics sit between the conv and the residual add: the
# unfused chain re-reads the whole conv output once for the moments
# reduction and once for the normalize.  Here the conv kernel emits
# per-channel partial sum(y)/sum(y*y) as SIBLING outputs while the
# accumulator is still VMEM-resident — each grid cell reduces its own
# [OH*OW, bco] tile, so the stats cost no extra HBM read at all — and a
# second one-pass kernel applies normalize+scale/shift+residual+ReLU.
# Together the activation is touched exactly once per kernel instead of
# three times.
# ---------------------------------------------------------------------------

def _conv_stats_kernel(*refs, kh, kw, sh, sw, oh, ow, has_bias):
    """The epilogue kernel's tap loop, plus per-grid-cell partial BN
    stats: s1[ni, co-tile] = sum over this image's OH*OW of y,
    s2 = sum of y*y, both f32, reduced from the VMEM-resident
    accumulator AFTER the cast to the output dtype (the unfused graph's
    BN sees the conv output post-cast, so the stats must too).
    refs: x[1,HP,WP,Cin], w[KH,KW,Cin,bco], (bias[1,bco]),
    y[1,OH,OW,bco], s1[1,bco], s2[1,bco]."""
    x_ref, w_ref = refs[0], refs[1]
    b_ref = refs[2] if has_bias else None
    o_ref, s1_ref, s2_ref = refs[-3], refs[-2], refs[-1]

    x = x_ref[0]
    cin = x.shape[-1]
    bco = o_ref.shape[-1]
    ct = jnp.promote_types(x_ref.dtype, w_ref.dtype)
    acc = jnp.zeros((oh * ow, bco), jnp.float32)
    for ti in range(kh):
        for tj in range(kw):
            p = lax.slice(x, (ti, tj, 0),
                          (ti + oh * sh - (sh - 1),
                           tj + ow * sw - (sw - 1), cin))
            if sh > 1:
                p = jnp.pad(p, ((0, sh - 1), (0, 0), (0, 0)))
                p = p.reshape(oh, sh, p.shape[1], cin)[:, 0]
            if sw > 1:
                p = jnp.pad(p, ((0, 0), (0, sw - 1), (0, 0)))
                p = p.reshape(oh, ow, sw, cin)[:, :, 0]
            acc = acc + lax.dot_general(
                p.reshape(oh * ow, cin).astype(ct),
                w_ref[ti, tj].astype(ct),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    from paddle_tpu.ops.epilogue import apply_acc_stages

    acc = apply_acc_stages(
        acc, bias=b_ref[0][None, :] if has_bias else None)
    y = acc.reshape(oh, ow, bco).astype(o_ref.dtype)
    o_ref[0] = y
    yf = y.reshape(oh * ow, bco).astype(jnp.float32)
    # the stat blocks are (1, 8, bco): f32 blocks need a sublane dim
    # divisible by 8 to lower under Mosaic (the [1, bq] lse lesson —
    # a bare (1, bco) spec is rejected), so the per-cell partials are
    # written sublane-replicated x8 and the host reads row 0
    s1_ref[0] = jnp.broadcast_to(jnp.sum(yf, axis=0)[None, :],
                                 (8, bco))
    s2_ref[0] = jnp.broadcast_to(jnp.sum(yf * yf, axis=0)[None, :],
                                 (8, bco))


def _conv_stats_pallas(x, w, bias, strides, padding, interpret=False):
    """Fused conv (+bias) with per-image partial-stat sibling outputs.

    Returns (y[N,OH,OW,Cout], s1[N,Cout] f32, s2[N,Cout] f32) with
    s1[n] = sum over (OH,OW) of y[n] and s2[n] the same for y*y.  The
    partials are finalized to mean/var on the host side of the call
    (one tiny [N,C] reduction XLA fuses); keeping the grid fully
    parallel beats sequentializing the N dimension for an in-kernel
    cross-step accumulator.  Falls back to the XLA composite when the
    VMEM estimate exceeds budget (same rule as the epilogue kernel)."""
    n, h, wd, cin = x.shape
    cout, _, kh, kw = w.shape
    sh, sw = strides
    oh, ow = _out_spatial(h, wd, kh, kw, sh, sw, padding)
    (ph0, _), (pw0, _) = padding
    hp = (oh - 1) * sh + kh
    wp = (ow - 1) * sw + kw
    xp = jnp.pad(x, ((0, 0),
                     (ph0, max(hp - h - ph0, 0)),
                     (pw0, max(wp - wd - pw0, 0)),
                     (0, 0)))[:, :hp, :wp, :]
    w_hwio = jnp.transpose(w, (2, 3, 1, 0))
    out_dtype = jnp.promote_types(x.dtype, w.dtype)

    bco = _block_co(cout)
    if not interpret:
        est = _vmem_estimate(xp.shape, (kh, kw), oh, ow, bco, False,
                             xp.dtype.itemsize, w_hwio.dtype.itemsize,
                             jnp.dtype(out_dtype).itemsize)
        # the stats blocks ride in the same budget (2 x (1, bco) f32,
        # double buffered)
        est += 4 * bco * 4 * 2
        if est > _VMEM_BUDGET_BYTES:
            return _conv_stats_xla(x, w, bias, strides, padding)
    _count_impl("conv2d_bn_stats", "interpret" if interpret else "pallas")

    grid = (n, pl.cdiv(cout, bco))
    in_specs = [
        pl.BlockSpec((1, hp, wp, cin), lambda ni, co: (ni, 0, 0, 0)),
        pl.BlockSpec((kh, kw, cin, bco), lambda ni, co: (0, 0, 0, co)),
    ]
    operands = [xp, w_hwio]
    if bias is not None:
        in_specs.append(pl.BlockSpec((1, bco), lambda ni, co: (0, co)))
        operands.append(bias.reshape(1, cout))
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    kernel = functools.partial(
        _conv_stats_kernel, kh=kh, kw=kw, sh=sh, sw=sw, oh=oh, ow=ow,
        has_bias=bias is not None)
    # stat arrays ride as [N, 8, Cout] (sublane-replicated x8 — see the
    # kernel comment); the finalization reads row 0
    stat_spec = pl.BlockSpec((1, 8, bco), lambda ni, co: (ni, 0, co))
    y, s1, s2 = pl.pallas_call(
        kernel,
        name="pt_conv_stats",
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, oh, ow, bco), lambda ni, co: (ni, 0, 0, co)),
            stat_spec,
            stat_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, oh, ow, cout), out_dtype),
            jax.ShapeDtypeStruct((n, 8, cout), jnp.float32),
            jax.ShapeDtypeStruct((n, 8, cout), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(*operands)
    return y, s1[:, 0, :], s2[:, 0, :]


def _conv_stats_xla(x, w, bias, strides, padding):
    """XLA fallback with the kernel's stat semantics: plain conv, then
    per-image partial sums of the (cast) output — multi-output fused by
    XLA into one read pass over y."""
    _count_impl("conv2d_bn_stats", "xla")
    y = _conv_core(x, w, strides, padding)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    yf = y.astype(jnp.float32)
    return y, jnp.sum(yf, axis=(1, 2)), jnp.sum(yf * yf, axis=(1, 2))


def _finalize_stats(s1, s2, m):
    """[N, C] partial sums -> per-channel (mean, var), f32.  Raw-moment
    finalization: var = E[y^2] - mean^2, clamped at 0.  The f32
    accumulation is over the already-rounded conv output, so the
    classic |mean| >> std cancellation only bites for channels far
    outside BN's operating regime (the unfused fallback keeps the
    shifted `_moments_1pass` for those paths)."""
    mean = jnp.sum(s1, axis=0) / m
    e2 = jnp.sum(s2, axis=0) / m
    return mean, jnp.maximum(e2 - mean * mean, 0.0)


def conv2d_bn_stats(x, w, bias=None, *, strides=(1, 1), paddings=(0, 0),
                    impl=None):
    """NHWC conv (+bias) that also returns the per-channel BN batch
    statistics of its output: (y, mean, var), stats f32.

    The stats are SIBLING outputs of the conv kernel — each grid cell
    reduces its VMEM-resident accumulator tile, so the moments cost no
    extra pass over y (the unfused train graph re-reads the whole conv
    output for `_moments_1pass`).  impl as in conv2d_epilogue."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    strides = tuple(int(s) for s in strides)
    padding = _norm_padding(paddings)
    if impl in ("pallas", "interpret"):
        with _kernel_scope():
            y, s1, s2 = _conv_stats_pallas(x, w, bias, strides, padding,
                                           interpret=impl == "interpret")
    else:
        y, s1, s2 = _conv_stats_xla(x, w, bias, strides, padding)
    m = float(y.shape[0] * y.shape[1] * y.shape[2])
    mean, var = _finalize_stats(s1, s2, m)
    return y, mean, var


# ------------------------- fused normalize + residual + ReLU kernel --


def _bn_apply_kernel(*refs, act, has_res):
    """One elementwise pass: out = act(((y - mean) * rstd) * scale +
    shift [+ residual]).  Normalize math in f32, cast to the output
    dtype BEFORE the residual add — the exact op order (and rounding
    points) of the unfused batch_norm -> elementwise_add -> relu chain,
    so interpret-mode parity vs that chain is bit-exact given the same
    stats.  refs: y[1,bh,OW,bc], mean[1,bc], rstd[1,bc], scale[1,bc],
    shift[1,bc], (res[1,bh,OW,bc]), out[1,bh,OW,bc]."""
    y_ref, m_ref, r_ref, s_ref, b_ref = refs[:5]
    res_ref = refs[5] if has_res else None
    o_ref = refs[-1]
    from paddle_tpu.ops.epilogue import apply_bn_tail

    yf = y_ref[0].astype(jnp.float32)              # [bh, OW, bc]
    t = (yf - m_ref[0][None, None, :]) * r_ref[0][None, None, :]
    t = t * s_ref[0][None, None, :] + b_ref[0][None, None, :]
    o_ref[0] = apply_bn_tail(t, o_ref.dtype,
                             res_ref[0] if has_res else None, act)


def _bn_apply_rows(oh, ow, bc, itemsize, n_bufs):
    """Largest spatial row-block that keeps the pipeline's double
    buffers under the VMEM budget."""
    per_row = ow * bc * itemsize * 2 * n_bufs      # double buffered
    bh = max(1, _VMEM_BUDGET_BYTES // max(per_row, 1))
    return min(oh, bh)


def _bn_apply_pallas(y, mean, rstd, scale, shift, residual, act,
                     interpret=False):
    n, oh, ow, c = y.shape
    bc = min(c, _DEFAULT_BLOCK_CO)
    bh = _bn_apply_rows(oh, ow, bc, jnp.dtype(y.dtype).itemsize,
                        3 if residual is not None else 2)
    grid = (n, pl.cdiv(oh, bh), pl.cdiv(c, bc))
    row_spec = pl.BlockSpec((1, bh, ow, bc),
                            lambda ni, hi, ci: (ni, hi, 0, ci))
    ch_spec = pl.BlockSpec((1, bc), lambda ni, hi, ci: (0, ci))
    in_specs = [row_spec, ch_spec, ch_spec, ch_spec, ch_spec]
    f32 = jnp.float32
    operands = [y, mean.astype(f32).reshape(1, c),
                rstd.astype(f32).reshape(1, c),
                scale.astype(f32).reshape(1, c),
                shift.astype(f32).reshape(1, c)]
    if residual is not None:
        in_specs.append(row_spec)
        operands.append(residual)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"))
    kernel = functools.partial(_bn_apply_kernel, act=act,
                               has_res=residual is not None)
    return pl.pallas_call(
        kernel,
        name="pt_bn_apply",
        grid=grid,
        in_specs=in_specs,
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(y.shape, y.dtype),
        interpret=interpret,
        **params,
    )(*operands)


def _bn_apply_xla(y, mean, rstd, scale, shift, residual, act):
    """The unfused chain's exact op order: normalize in f32, cast to
    y.dtype, add the residual in that dtype, relu last."""
    from paddle_tpu.ops.epilogue import apply_bn_tail

    f32 = jnp.float32
    shape = (1, 1, 1, y.shape[-1])
    t = (y.astype(f32) - mean.astype(f32).reshape(shape)) \
        * rstd.astype(f32).reshape(shape)
    t = t * scale.astype(f32).reshape(shape) \
        + shift.astype(f32).reshape(shape)
    return apply_bn_tail(t, y.dtype, residual, act)


def bn_normalize_epilogue(y, mean, var, scale, shift, residual=None, *,
                          epsilon=1e-5, act=None, impl=None):
    """Normalize + scale/shift + residual-add + act in ONE pass over y.

    y: [N, H, W, C] (NHWC); mean/var/scale/shift: [C]; residual:
    y-shaped or None.  The unfused train chain runs three elementwise
    passes over the activation here (normalize, add, relu) plus the
    moments re-read; paired with conv2d_bn_stats this touches y exactly
    once.  impl as in conv2d_epilogue."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    rstd = lax.rsqrt(var.astype(jnp.float32) + epsilon)
    if impl in ("pallas", "interpret"):
        return _bn_apply_pallas(y, mean, rstd, scale, shift, residual,
                                act or "", interpret=impl == "interpret")
    return _bn_apply_xla(y, mean, rstd, scale, shift, residual,
                         act or "")


def conv2d_epilogue(x, w, bias=None, residual=None, *, strides=(1, 1),
                    paddings=(0, 0), act=None, impl=None):
    """Fused NHWC conv + bias + residual + act in one VMEM pass.

    x: [N, H, W, Cin]; w: [O, Cin, KH, KW] (OIHW); bias: [O];
    residual: [N, OH, OW, O]; act: None or "relu".

    impl: None (auto: pallas on TPU, XLA composite elsewhere),
    "pallas", "interpret" (Pallas interpreter, for CPU tests), or
    "xla" (the unfused composite — the exact op sequence the flag-off
    graph runs).  Differentiable in x/w/bias/residual via custom_vjp;
    dx/dw reuse the XLA conv gradients.
    """
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    strides = tuple(int(s) for s in strides)
    padding = _norm_padding(paddings)
    # device-time attribution (ISSUE 10): a runtime annotation under
    # the `tracing` flag, else the null context; one call line either
    # way (see ops/pallas_kernels.py flash_attention)
    with _obs_device.annotate("conv2d_epilogue"), _kernel_scope():
        return _conv_ep(x, w, bias, residual, strides, padding,
                        act or "", impl)


def _conv_bn_unfused(x, w, bias, scale, shift, residual, strides,
                     padding, act, eps):
    """The EXACT op sequence the flag-off train graph runs: conv ->
    `_moments_1pass` batch stats -> normalize (f32, cast) -> residual
    add -> relu.  A program rewritten onto conv2d_bn_train but executed
    with conv_bn_stats off must be bit-identical to the never-rewritten
    graph, so this path mirrors ops/nn.py batch_norm term for term."""
    from paddle_tpu.ops.nn import _moments_1pass

    y = _conv_core(x, w, strides, padding)
    if bias is not None:
        y = y + bias.astype(y.dtype)
    xf = y.astype(scale.dtype)
    mean, var = _moments_1pass(xf, (0, 1, 2))
    shape = (1, 1, 1, y.shape[-1])
    t = (xf - mean.reshape(shape)) * lax.rsqrt(var.reshape(shape) + eps) \
        * scale.reshape(shape) + shift.reshape(shape)
    t = t.astype(y.dtype)
    if residual is not None:
        t = t + residual
    if act == "relu":
        t = jnp.maximum(t, 0)
    return t, mean, var, y


def _conv_bn_core(x, w, bias, scale, shift, residual, strides, padding,
                  act, eps, impl):
    """Dispatch for the fused train chain; returns (out, mean, var,
    y_conv)."""
    if impl in ("pallas", "interpret"):
        interp = impl == "interpret"
        y, s1, s2 = _conv_stats_pallas(x, w, bias, strides, padding,
                                       interpret=interp)
        m = float(y.shape[0] * y.shape[1] * y.shape[2])
        mean, var = _finalize_stats(s1, s2, m)
        rstd = lax.rsqrt(var + eps)
        out = _bn_apply_pallas(y, mean, rstd, scale.astype(jnp.float32),
                               shift.astype(jnp.float32), residual, act,
                               interpret=interp)
        return out, mean, var, y
    _count_impl("conv2d_bn_stats", "xla")
    return _conv_bn_unfused(x, w, bias, scale, shift, residual, strides,
                            padding, act, eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _conv_bn_act(x, w, bias, scale, shift, residual, strides, padding,
                 act, eps, impl):
    out, mean, var, _y = _conv_bn_core(x, w, bias, scale, shift,
                                       residual, strides, padding, act,
                                       eps, impl)
    return out, mean, var


def _conv_bn_act_fwd(x, w, bias, scale, shift, residual, strides,
                     padding, act, eps, impl):
    out, mean, var, y = _conv_bn_core(x, w, bias, scale, shift,
                                      residual, strides, padding, act,
                                      eps, impl)
    return (out, mean, var), (x, w, bias, scale, residual, y, mean, var,
                              out)


def _conv_bn_act_bwd(strides, padding, act, eps, impl, res, cts):
    """Closed-form BN-train backward, term-for-term the hand-written
    ops/nn.py batch_norm_grad formula evaluated on the SAVED batch
    stats (no moments recompute), composed with the ReLU mask from the
    saved post-act output and the existing XLA conv gradients via
    jax.vjp of the plain conv core — given equal stats the grads are
    bit-identical to the unfused graph's.  The mean/var sibling
    outputs' own cotangents (non-zero only when something downstream
    consumes SavedMean/SavedVariance) are folded in analytically:
    d mean/d y = 1/m, d var/d y = 2 (y - mean)/m."""
    x, w, bias, scale, residual, y, mean, var = res[:8]
    out = res[8]
    g, g_mean, g_var = cts
    if act == "relu":
        g = jnp.where(out > 0, g, jnp.zeros_like(g))
    dres = None
    if residual is not None:
        dres = g.astype(residual.dtype)
    f32 = scale.dtype
    m = float(y.shape[0] * y.shape[1] * y.shape[2])
    shape = (1, 1, 1, y.shape[-1])
    axes = (0, 1, 2)
    yf = y.astype(f32)
    dyf = g.astype(f32)
    rstd = lax.rsqrt(var + eps)
    x_hat = (yf - mean.reshape(shape)) * rstd.reshape(shape)
    dshift = jnp.sum(dyf, axis=axes)
    dscale = jnp.sum(dyf * x_hat, axis=axes)
    dy = (scale * rstd).reshape(shape) * (
        dyf - (dshift / m).reshape(shape)
        - x_hat * (dscale / m).reshape(shape))
    # sibling-stat cotangents (usually symbolic zeros, DCE'd)
    dy = dy + (g_mean / m).reshape(shape) \
        + (yf - mean.reshape(shape)) * (2.0 / m * g_var).reshape(shape)
    dy = dy.astype(y.dtype)
    ct = jnp.promote_types(x.dtype, w.dtype)
    _, vjp = jax.vjp(
        lambda a, b: _conv_core(a, b, strides, padding), x, w)
    dx, dw = vjp(dy.astype(ct))
    db = None
    if bias is not None:
        db = jnp.sum(dy.astype(jnp.float32),
                     axis=(0, 1, 2)).astype(bias.dtype)
    return dx, dw, db, dscale.astype(scale.dtype), \
        dshift.astype(scale.dtype), dres


_conv_bn_act.defvjp(_conv_bn_act_fwd, _conv_bn_act_bwd)


def conv2d_bn_act(x, w, scale, shift, bias=None, residual=None, *,
                  strides=(1, 1), paddings=(0, 0), act=None,
                  epsilon=1e-5, impl=None):
    """Fused NHWC conv + train-mode BN + residual + act: TWO one-pass
    kernels (conv with Σy/Σy² sibling outputs; normalize+add+ReLU)
    replacing the five-pass unfused chain.  Returns (out, batch_mean,
    batch_var) — the stats ride out so the caller can update running
    stats / emit SavedMean.  Differentiable in x/w/bias/scale/shift/
    residual via custom_vjp; dx/dw reuse the XLA conv gradients.

    x: [N, H, W, Cin]; w: [O, Cin, KH, KW]; scale/shift: [O] (BN
    gamma/beta, f32); bias: optional conv channel bias [O]; residual:
    [N, OH, OW, O] or None; act: None or "relu".  impl: None (auto:
    pallas on TPU, the exact unfused composite elsewhere), "pallas",
    "interpret", or "xla"."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    strides = tuple(int(s) for s in strides)
    padding = _norm_padding(paddings)
    with _obs_device.annotate("conv2d_bn_act"), _kernel_scope():
        return _conv_bn_act(x, w, bias, scale, shift, residual, strides,
                            padding, act or "", float(epsilon), impl)


def _on_tpu():
    from paddle_tpu.ops.pallas_kernels import _on_tpu as _chip

    return _chip()


def _impl_from_flag():
    """Map the conv_epilogue flag to an impl name ("off" still returns
    a correct impl — the op may exist in a program loaded under a
    different flag state)."""
    from paddle_tpu.flags import get_flag

    mode = get_flag("conv_epilogue")
    if mode in ("pallas", "interpret", "xla"):
        return mode
    if mode == "on":
        return None                     # auto: pallas on TPU else xla
    return "xla"                        # "off" (or unknown): unfused


# ---------------------------------------------------------------------------
# IR op registration — the target of transpiler.fuse_conv_epilogue
# ---------------------------------------------------------------------------

from paddle_tpu.core.registry import register_op  # noqa: E402


@register_op("conv2d_epilogue",
             inputs=("Input", "Filter", "Bias", "Residual"),
             outputs=("Output",),
             optional=("Bias", "Residual"),
             attrs={"strides": [1, 1], "paddings": [0, 0], "act": "",
                    "groups": 1, "data_format": "NCHW",
                    "epilogue": ""})
def _conv2d_epilogue_op(ins, attrs):
    """conv2d + channel bias + residual add + activation as ONE op.
    NCHW programs are normalized to NHWC internally (the layout
    transpiler rewrites the op to native NHWC on the TPU path, making
    these transposes vanish)."""
    x, w = ins["Input"], ins["Filter"]
    bias = ins.get("Bias")
    residual = ins.get("Residual")
    fmt = attrs.get("data_format", "NCHW")
    if fmt == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
        if residual is not None:
            residual = jnp.transpose(residual, (0, 2, 3, 1))
    out = conv2d_epilogue(
        x, w, bias, residual,
        strides=attrs.get("strides", [1, 1]),
        paddings=attrs.get("paddings", [0, 0]),
        act=attrs.get("act") or None,
        impl=_impl_from_flag())
    if fmt == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    return {"Output": out}


def _bn_impl_from_flag():
    """Map the conv_bn_stats flag to an impl name ("off" still returns
    the exact unfused composite — a rewritten program loaded under a
    different flag state must stay bit-identical to the original)."""
    from paddle_tpu.flags import get_flag

    mode = get_flag("conv_bn_stats")
    if mode in ("pallas", "interpret", "xla"):
        return mode
    if mode == "on":
        return None                     # auto: pallas on TPU else xla
    return "xla"                        # "off" (or unknown): unfused


@register_op("conv2d_bn_train",
             inputs=("Input", "Filter", "Bias", "Scale", "BNBias",
                     "Mean", "Variance", "Residual"),
             outputs=("Output", "MeanOut", "VarianceOut", "SavedMean",
                      "SavedVariance"),
             optional=("Bias", "Residual"),
             attrs={"strides": [1, 1], "paddings": [0, 0], "act": "",
                    "groups": 1, "epsilon": 1e-5, "momentum": 0.9,
                    "data_format": "NCHW", "epilogue": ""})
def _conv2d_bn_train_op(ins, attrs):
    """conv2d + train-mode batch_norm + residual add + activation as
    ONE op — the target of transpiler.fuse_conv_bn_train.  Outputs
    mirror batch_norm's contract (MeanOut/VarianceOut wired back onto
    the running-stat vars; SavedMean = batch mean, SavedVariance =
    1/sqrt(var+eps)), so the rewrite preserves every BN output the rest
    of the graph may consume.  NCHW programs are normalized to NHWC
    internally (the layout transpiler rewrites the op to native NHWC
    on the TPU path)."""
    x, w = ins["Input"], ins["Filter"]
    bias = ins.get("Bias")
    scale, shift = ins["Scale"], ins["BNBias"]
    mean_in, var_in = ins["Mean"], ins["Variance"]
    residual = ins.get("Residual")
    eps, mom = attrs["epsilon"], attrs["momentum"]
    fmt = attrs.get("data_format", "NCHW")
    if fmt == "NCHW":
        x = jnp.transpose(x, (0, 2, 3, 1))
        if residual is not None:
            residual = jnp.transpose(residual, (0, 2, 3, 1))
    out, mean, var = conv2d_bn_act(
        x, w, scale, shift, bias, residual,
        strides=attrs.get("strides", [1, 1]),
        paddings=attrs.get("paddings", [0, 0]),
        act=attrs.get("act") or None, epsilon=eps,
        impl=_bn_impl_from_flag())
    if fmt == "NCHW":
        out = jnp.transpose(out, (0, 3, 1, 2))
    mean_out = mean_in * mom + lax.stop_gradient(mean) * (1 - mom)
    var_out = var_in * mom + lax.stop_gradient(var) * (1 - mom)
    saved_var = 1.0 / jnp.sqrt(var + eps)
    return {"Output": out, "MeanOut": mean_out, "VarianceOut": var_out,
            "SavedMean": mean, "SavedVariance": saved_var}

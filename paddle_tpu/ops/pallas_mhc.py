"""The two halves of a manifold-constrained hyper-connection
(ops/llm_ops.py mhc_pre, mhc_post) as Pallas TPU kernels that read and
write every stream-sized array once a pass, in its own dtype.

X [B, n, T, C] are the n residual streams, stream-major; Y, U and their
gradients are [B, T, C].  In XLA the mixes are broadcast products over
n x n x T x C that read their operands from HBM for every pair of
streams, with float32 copies and casts of whole stream arrays beside
them: 73.6 ms of the 267 ms step of `xing4_29b_train_s4k` for 20 ms of
bytes (PERF.md, PR 51).  Here a grid step takes a block of tT tokens
at all n streams and the whole width C (a token's n C entries are one
RMS norm and one row of the product with Phi, so a block is whole
tokens), and walks it by chunks of _ROWS tokens x up to _WIDTH
channels with float32 arithmetic in registers:

    pt_mhc_post_fwd   Out[i] = sum_j HRes[i, j] X[j] + HPost[i] Y
    pt_mhc_post_bwd   dX[j] = sum_i HRes[i, j] dOut[i]
                      dY = sum_i HPost[i] dOut[i]
                      dHRes[i, j, t] = sum_c dOut[i] X[j]
                      dHPost[i, t] = sum_c dOut[i] Y
    pt_mhc_pre_fwd    q = X (NormScale * Phi), sum_c X^2, the gates, the
                      Sinkhorn rounds and U = sum_j H_pre[j] X[j]
    pt_mhc_pre_bwd    dX of the three uses of X, dW = X^T (dp * inv),
                      dH_pre; the gates' and the rounds' gradient stays
                      jax.vjp of their XLA form on coefficient arrays

The coefficients of a token (n + n^2 of them, 2n + n^2 in mhc_pre)
weigh whole rows of a block, so the kernels take them token-major,
[B, T, K] float32 with the K coefficients on the lanes: a chunk's
column k is broadcast along the lanes once and used by all C / 128
lane tiles.  The ops hand them over tokens-last ([B, n, T],
[B, n, n, T]: the Sinkhorn rounds work on whole [n, n, T] slabs);
`coef_rows` / `coef_cols` turn one into the other, a transpose of a
coefficient-sized array in XLA.  mhc_pre's product leaves the MXU
token-major ([tT, K]); its gates need both forms (H_pre weighs rows;
HPost and HRes leave tokens-last), so the kernel forms the
pre-activations token-major and transposes that one [tT, 128] tile.

The product keeps `highest`'s precision: where X is bfloat16 it is
exact in bfloat16 and only the float32 weight is split, into three
bfloat16 parts laid side by side along the lanes (3 K of the 128
columns: ONE pass of the array where XLA runs six at K columns), which
lane rolls add up; where X is float32 the dot is `highest` itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas_gmm import _VMEM_BUDGET, _params

_F32 = jnp.float32
_BF16 = jnp.bfloat16
_LANES = 128
_ROWS = 16          # tokens a chunk: a packed bfloat16 sublane tile
_WIDTH = 4096       # channels a chunk, at most
_TOKEN_BLOCKS = (1024, 512, 256, 128, 64, 32, 16)
# stream-sized blocks a grid step holds, in units of one stream's
# [tT, C] (X's is n of them), by kernel
_BLOCK_UNITS = {
    "post_fwd": lambda n: 2 * n + 1,            # X, Out, Y
    "post_bwd": lambda n: 3 * n + 2,            # dOut, X, dX, Y, dY
    "pre_fwd": lambda n: n + 1,                 # X, U
    "pre_bwd": lambda n: 2 * n + 1,             # X, dX, dU
}


def _vmem(kernel, n, tt, c, itemsize):
    """Bytes of a grid step's blocks, double-buffered; mhc_pre's hold
    the product's [n, C, 128] weight beside them (in X's dtype; the
    backward also its float32 gradient, and a float32 [tT, C]
    scratch)."""
    weight = n * c * _LANES * {"pre_fwd": itemsize,
                               "pre_bwd": itemsize + 4}.get(kernel, 0)
    scratch = tt * c * 4 if kernel == "pre_bwd" else 0
    return 2 * (_BLOCK_UNITS[kernel](n) * tt * c * itemsize + weight) \
        + scratch


def token_block(kernel, n, t, c, itemsize):
    """tT, the tokens a grid step of `kernel` takes of streams
    [., n, t, c]: the largest of _TOKEN_BLOCKS that divides t and whose
    blocks _VMEM_BUDGET holds; mhc_pre's, which write coefficients
    tokens-last, whole lane tiles of tokens or all t.  None where the
    kernels cannot take the shapes (c not whole lane tiles, t no
    multiple of a chunk, the five lane groups of product_weight's
    2n + n^2 columns wider than a lane tile, n > 4): then the XLA
    composition runs."""
    if c % _LANES or t % _ROWS or 5 * (2 * n + n * n) > _LANES:
        return None
    blocks = [tt for tt in _TOKEN_BLOCKS if t % tt == 0]
    if kernel.startswith("pre"):
        blocks = [tt for tt in blocks if tt % _LANES == 0] or [t]
    return next((tt for tt in blocks
                 if _vmem(kernel, n, tt, c, itemsize) <= _VMEM_BUDGET), None)


def sinkhorn(a, iters, eps, row_axis=-1, col_axis=-2):
    """exp(a) with rows then columns divided by their sums (+ eps),
    `iters` times; a row's entries lie along row_axis."""
    m = jnp.exp(a)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=row_axis, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=col_axis, keepdims=True) + eps)
    return m


def coef_rows(*coefs):
    """Tokens-last coefficient arrays [B, ..., T] -> one token-major
    [B, T, K] float32, the arrays' entries side by side in order."""
    b, t = coefs[0].shape[0], coefs[0].shape[-1]
    return jnp.concatenate([a.astype(_F32).reshape(b, -1, t) for a in coefs],
                           axis=1).transpose(0, 2, 1)


def coef_cols(rows, *shapes):
    """coef_rows' inverse on the first lanes of rows [B, T, >= K]:
    arrays of `shapes` ([B, ..., T])."""
    out, at = [], 0
    for shape in shapes:
        k = math.prod(shape[1:-1])
        out.append(rows[:, :, at:at + k].transpose(0, 2, 1).reshape(shape))
        at += k
    return out


def _columns(coef, ks):
    """[rows, 1] a k, coefficient k of a chunk's tokens: a lane slice,
    broadcast along the lanes by the product that reads it."""
    return tuple(coef[:, k:k + 1] for k in ks)


def _zeros(rows=None):
    return jnp.zeros((_ROWS, _LANES), _F32)


def _row_sums(part):
    """part [rows, _LANES] summed over the lanes, on every lane."""
    return jnp.broadcast_to(jnp.sum(part, axis=1, keepdims=True), part.shape)


def _by_chunks(tt, c, first, tile, last=None):
    """Walks a block's [tt, c] by chunks of _ROWS tokens (the outer
    loop) x the widest run of whole lane tiles that divides c and
    _WIDTH holds (all of C 3,584: on the chip the four kernels read
    0.24 / 0.75 / 0.41 / 0.62 ms a call so, 0.91 / 3.22 / 1.26 / 1.30
    by chunks of 128 channels, PERF.md, PR 52): state = first(rows)
    once a chunk of tokens (its coefficients, read once for all its
    channels), state = tile(rows, lanes, state) a chunk, last(rows,
    state) after a row of chunks.  Two traced bodies whatever the
    block holds."""
    width = next(w for w in range(min(c, _WIDTH) // _LANES * _LANES, 0,
                                  -_LANES) if c % w == 0)

    def chunk(r, _):
        rows = pl.ds(pl.multiple_of(r * _ROWS, _ROWS), _ROWS)

        def lane_tile(l, state):
            return tile(rows, pl.ds(pl.multiple_of(l * width, width),
                                    width), state)

        state = lax.fori_loop(0, c // width, lane_tile, first(rows))
        if last is not None:
            last(rows, state)
        return 0

    lax.fori_loop(0, tt // _ROWS, chunk, 0)


def _fold(x):
    """[rows, _LANES]: x [rows, a chunk's channels] summed by lane tile."""
    out = x[:, :_LANES]
    for at in range(_LANES, x.shape[1], _LANES):
        out = out + x[:, at:at + _LANES]
    return out


def _post_fwd_kernel(coef_ref, x_ref, y_ref, o_ref, *, n):
    tt, c = y_ref.shape[1:]

    def first(rows):
        return _columns(coef_ref[0, rows, :], range(n * n + n))

    def tile(rows, lanes, h):
        x = [x_ref[0, j, rows, lanes].astype(_F32) for j in range(n)]
        y = y_ref[0, rows, lanes].astype(_F32)
        for i in range(n):
            mixed = h[i * n] * x[0]
            for j in range(1, n):
                mixed = mixed + h[i * n + j] * x[j]
            o_ref[0, i, rows, lanes] = (mixed + h[n * n + i] * y
                                        ).astype(o_ref.dtype)
        return h

    _by_chunks(tt, c, first, tile)


def _lane_sums(parts):
    """[rows, K-lane] from K partial sums [rows, _LANES]: each summed
    over its lanes and laid on lane k, the others zero."""
    lane = lax.broadcasted_iota(jnp.int32, parts[0].shape, 1)
    out = _zeros()
    for k, part in enumerate(parts):
        out = jnp.where(lane == k, _row_sums(part), out)
    return out


def _post_bwd_kernel(coef_ref, x_ref, y_ref, g_ref, dx_ref, dy_ref,
                     dcoef_ref, *, n):
    tt, c = y_ref.shape[1:]
    k_all = n * n + n

    def first(rows):
        return (_columns(coef_ref[0, rows, :], range(k_all)),
                (_zeros(),) * k_all)

    def tile(rows, lanes, state):
        h, acc = state
        g = [g_ref[0, i, rows, lanes].astype(_F32) for i in range(n)]
        x = [x_ref[0, j, rows, lanes].astype(_F32) for j in range(n)]
        y = y_ref[0, rows, lanes].astype(_F32)
        for j in range(n):
            dx = h[j] * g[0]
            for i in range(1, n):
                dx = dx + h[i * n + j] * g[i]
            dx_ref[0, j, rows, lanes] = dx.astype(dx_ref.dtype)
        dy = h[n * n] * g[0]
        for i in range(1, n):
            dy = dy + h[n * n + i] * g[i]
        dy_ref[0, rows, lanes] = dy.astype(dy_ref.dtype)
        acc = tuple(
            [acc[i * n + j] + _fold(g[i] * x[j])
             for i in range(n) for j in range(n)]
            + [acc[n * n + i] + _fold(g[i] * y) for i in range(n)])
        return h, acc

    def last(rows, state):
        dcoef_ref[0, rows, :] = _lane_sums(state[1])

    _by_chunks(tt, c, first, tile, last)


def _stream_specs(n, tt, c):
    """BlockSpecs over the grid (B, T / tT): of a [B, n, T, C] array,
    of a [B, T, C] array, and coefs(k) of a [B, T, k] array."""
    return (pl.BlockSpec((1, n, tt, c), lambda b, r: (b, 0, r, 0)),
            pl.BlockSpec((1, tt, c), lambda b, r: (b, r, 0)),
            lambda k: pl.BlockSpec((1, tt, k), lambda b, r: (b, r, 0)))


@functools.partial(jax.jit, static_argnames=("interpret",))
def mhc_post_fwd_pallas(x, y, coef, interpret=False):
    """Out [B, n, T, C] in x's dtype; coef = coef_rows(HRes, HPost)."""
    b, n, t, c = x.shape
    tt = token_block("post_fwd", n, t, c, x.dtype.itemsize)
    streams, one, coefs = _stream_specs(n, tt, c)
    return pl.pallas_call(
        functools.partial(_post_fwd_kernel, n=n),
        name="pt_mhc_post_fwd",
        grid=(b, t // tt),
        in_specs=[coefs(coef.shape[-1]), streams, one],
        out_specs=streams,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel"),
                  _vmem("post_fwd", n, tt, c, x.dtype.itemsize)),
    )(coef, x, y)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mhc_post_bwd_pallas(x, y, coef, g, interpret=False):
    """(dX in x's dtype, dY in y's, d coef [B, T, 128] float32 whose
    first lanes are coef's gradient in coef_rows' order)."""
    b, n, t, c = x.shape
    tt = token_block("post_bwd", n, t, c, x.dtype.itemsize)
    streams, one, coefs = _stream_specs(n, tt, c)
    return pl.pallas_call(
        functools.partial(_post_bwd_kernel, n=n),
        name="pt_mhc_post_bwd",
        grid=(b, t // tt),
        in_specs=[coefs(coef.shape[-1]), streams, one, streams],
        out_specs=[streams, one, coefs(_LANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(y.shape, y.dtype),
                   jax.ShapeDtypeStruct((b, t, _LANES), _F32)],
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel"),
                  _vmem("post_bwd", n, tt, c, x.dtype.itemsize)),
    )(coef, x, y, g.astype(x.dtype))


def _bf16_parts(v):
    """Three float32 arrays, each exact in bfloat16, that add up to
    float32 v exactly: its leading 8, middle 8 and last 8 significant
    bits, cut off by a mask on the words (a cast to bfloat16 and back
    is one a compiler may drop: XLA's does on a TPU, and the second
    and third part were 0)."""
    parts = []
    for _ in range(3):
        parts.append(lax.bitcast_convert_type(
            lax.bitcast_convert_type(v, jnp.uint32)
            & jnp.uint32(0xFFFF0000), _F32))
        v = v - parts[-1]
    return parts


def product_weight(norm_scale, phi, n, split):
    """The weight of mhc_pre's product as the kernels read it:
    NormScale * Phi, [n, C, 128] with the K = 2n + n^2 columns on the
    first lanes; with `split` (X bfloat16) bfloat16, five groups of K
    lanes: the three bfloat16 parts of every float32 entry, hi | mid |
    lo, which the forward's product reads, then hi | mid again, for
    the backward's (_pre_bwd_kernel)."""
    w = norm_scale.astype(_F32)[:, None] * phi.astype(_F32)
    k = w.shape[1]
    if split:
        parts = _bf16_parts(w)
        w = jnp.concatenate(parts + parts[:2], axis=1).astype(_BF16)
    w = jnp.pad(w, ((0, 0), (0, _LANES - w.shape[1])))
    return w.reshape(n, -1, _LANES), k


def gate_rows(alpha, bias, n):
    """[8, 128] float32: row 0 Alpha and row 1 Bias as the lanes of a
    token-major pre-activation take them (Alpha[0] on the n lanes of
    H_pre, Alpha[1] on H_post's, Alpha[2] on H_res's n^2)."""
    a = jnp.repeat(alpha.astype(_F32), jnp.array([n, n, n * n]),
                   total_repeat_length=2 * n + n * n)
    rows = jnp.stack([a, bias.astype(_F32)])
    return jnp.pad(rows, ((0, 6), (0, _LANES - rows.shape[1])))


def _product(x_ref, w_ref, n, k, split):
    """q [tT, 128] float32 whose first k lanes are X (NormScale * Phi)
    of the block's tokens."""
    q = None
    for j in range(n):
        part = jnp.dot(x_ref[0, j], w_ref[j], preferred_element_type=_F32,
                       precision=None if split else lax.Precision.HIGHEST)
        q = part if q is None else q + part
    if split:       # hi + mid + lo, each exact against a bfloat16 x
        q = q + pltpu.roll(q, _LANES - k, 1) + pltpu.roll(q, _LANES - 2 * k, 1)
    return q


def _squares(x_ref, n):
    """_by_chunks' tile that adds a chunk's x^2 of all n streams."""
    def tile(rows, lanes, acc):
        for j in range(n):
            x = x_ref[0, j, rows, lanes].astype(_F32)
            acc = acc + _fold(x * x)
        return acc
    return tile


def _pre_activation(ss, x_ref, w_ref, ab_ref, n, k, split, eps):
    """Token-major [tT, 128] tiles from ss, the sum of a token's
    squares on every lane: (z, the gates' pre-activations on the first
    k lanes and 0 past them; pn = q inv; inv; q)."""
    c = x_ref.shape[3]
    inv = lax.rsqrt(ss / (n * c) + eps)
    q = _product(x_ref, w_ref, n, k, split)
    pn = q * inv
    return ab_ref[0:1, :] * pn + ab_ref[1:2, :], pn, inv, q


def _tokens_last(zt, n, clamp, iters, eps):
    """(H_post [n, tT], H_res [n, n, tT]) of zt [>= 2n + n^2, tT], the
    pre-activations tokens-last."""
    raw = jnp.stack([zt[(2 + i) * n:(3 + i) * n] for i in range(n)])
    return (2 * jax.nn.sigmoid(zt[n:2 * n]),
            sinkhorn(jnp.clip(raw, *clamp), iters, eps, row_axis=1,
                     col_axis=0))


def _pre_fwd_kernel(x_ref, w_ref, ab_ref, u_ref, hpost_ref, hres_ref,
                    pre_ref, *, n, k, split, iters, eps, clamp):
    """pre_ref [tT, 128] float32 scratch: the sum of squares of a
    token's entries on every lane, then H_pre on the first n lanes."""
    tt, c = u_ref.shape[1:]

    def norm(rows, acc):
        pre_ref[rows, :] = _row_sums(acc)

    _by_chunks(tt, c, _zeros, _squares(x_ref, n), norm)
    z = _pre_activation(pre_ref[...], x_ref, w_ref, ab_ref, n, k, split,
                        eps)[0]
    pre_ref[...] = jax.nn.sigmoid(z)
    # [128, tT]: tokens last
    hpost_ref[0], hres_ref[0] = _tokens_last(z.T, n, clamp, iters, eps)

    def first(rows):
        return _columns(pre_ref[rows, :], range(n))

    def mix(rows, lanes, h):
        u = h[0] * x_ref[0, 0, rows, lanes].astype(_F32)
        for j in range(1, n):
            u = u + h[j] * x_ref[0, j, rows, lanes].astype(_F32)
        u_ref[0, rows, lanes] = u.astype(u_ref.dtype)
        return h

    _by_chunks(tt, c, first, mix)


def _pre_specs(n, tt, c, w):
    """in_specs of (X, the weight, the gates' rows) and the BlockSpecs
    of U, HPost and HRes over the grid (B, T / tT)."""
    streams, one, _ = _stream_specs(n, tt, c)
    return ([streams, pl.BlockSpec(w.shape, lambda b, r: (0, 0, 0)),
             pl.BlockSpec((8, _LANES), lambda b, r: (0, 0))],
            [one, pl.BlockSpec((1, n, tt), lambda b, r: (b, 0, r)),
             pl.BlockSpec((1, n, n, tt), lambda b, r: (b, 0, 0, r))])


@functools.partial(jax.jit, static_argnames=(
    "iters", "eps", "clamp", "interpret"))
def mhc_pre_fwd_pallas(x, norm_scale, phi, alpha, bias, iters, eps, clamp,
                       interpret=False):
    """(U [B, T, C] in x's dtype, HPost [B, n, T], HRes [B, n, n, T]
    float32); `iters` Sinkhorn rounds on the pre-activations clipped to
    clamp = (min, max)."""
    b, n, t, c = x.shape
    split = x.dtype == _BF16
    tt = token_block("pre_fwd", n, t, c, x.dtype.itemsize)
    w, k = product_weight(norm_scale, phi, n, split)
    ins, outs = _pre_specs(n, tt, c, w)
    return pl.pallas_call(
        functools.partial(_pre_fwd_kernel, n=n, k=k, split=split,
                          iters=iters, eps=eps, clamp=clamp),
        name="pt_mhc_pre_fwd",
        grid=(b, t // tt),
        in_specs=ins,
        out_specs=outs,
        out_shape=[jax.ShapeDtypeStruct((b, t, c), x.dtype),
                   jax.ShapeDtypeStruct((b, n, t), _F32),
                   jax.ShapeDtypeStruct((b, n, n, t), _F32)],
        scratch_shapes=[pltpu.VMEM((tt, _LANES), _F32)],
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel"),
                  _vmem("pre_fwd", n, tt, c, x.dtype.itemsize)),
    )(x, w, gate_rows(alpha, bias, n))


def _side_by_side(parts, k):
    """bfloat16 [tT, 128]: part i (float32, exact in bfloat16, live on
    the first k lanes and 0 past them) on the lanes [i k, (i + 1) k)."""
    out = parts[0]
    for i, part in enumerate(parts[1:], 1):
        out = out + pltpu.roll(part, i * k, 1)
    return out.astype(_BF16)


def _pre_bwd_kernel(x_ref, w_ref, ab_ref, du_ref, dpost_ref, dres_ref,
                    dx_ref, dw_ref, dab_ref, ss_ref, dh_ref, path_ref, *,
                    n, k, split, iters, eps, clamp):
    """Scratch, float32: ss_ref [tT, 128] a token's sum of squares on
    every lane; dh_ref [tT, 128] dH_pre on the first n lanes, then the
    rows' coefficients of dX (H_pre, and the norm's on lane n);
    path_ref [tT, C] the product's part of one stream's dX."""
    tt, c = du_ref.shape[1:]

    @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
    def _first_step():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        dab_ref[...] = jnp.zeros_like(dab_ref)

    squares = _squares(x_ref, n)

    def sums(rows, lanes, acc):
        du = du_ref[0, rows, lanes].astype(_F32)
        return (squares(rows, lanes, acc[0]),) + tuple(
            acc[1 + j] + _fold(du * x_ref[0, j, rows, lanes].astype(_F32))
            for j in range(n))

    def reduced(rows, acc):
        ss_ref[rows, :] = _row_sums(acc[0])
        dh_ref[rows, :] = _lane_sums(acc[1:])

    _by_chunks(tt, c, lambda rows: (_zeros(),) * (n + 1), sums, reduced)
    z, pn, inv, q = _pre_activation(ss_ref[...], x_ref, w_ref, ab_ref, n, k,
                                    split, eps)
    h = jax.nn.sigmoid(z)           # H_pre on the first n lanes
    _, vjp = jax.vjp(
        lambda zt: _tokens_last(zt, n, clamp, iters, eps), z.T)
    # dh_ref is 0 past lane n, the tokens-last gradient 0 before row n
    dz = dh_ref[...] * h * (1 - h) + vjp((dpost_ref[0], dres_ref[0]))[0].T
    dab_ref[0:1, :] += jnp.sum(dz * pn, axis=0, keepdims=True)
    dab_ref[1:2, :] += jnp.sum(dz, axis=0, keepdims=True)
    dpn = dz * ab_ref[0:1, :]       # 0 past lane k, where q is not
    dq = dpn * inv
    lane = lax.broadcasted_iota(jnp.int32, dq.shape, 1)
    dinv = jnp.sum(jnp.where(lane < k, dpn * q, 0.0), axis=1, keepdims=True)
    # d(sum of squares) * 2, the weight of X[j] in its own gradient
    dh_ref[...] = jnp.where(lane == n, -dinv * inv * inv * inv / (n * c), h)
    dq_w = dq_x = dq
    if split:
        # X is exact in bfloat16: dq's three parts side by side give dW
        # `highest`'s precision in one pass.  dX leaves in bfloat16
        # (2^-9): against the weight's five groups, dq's hi | hi | hi |
        # mid | mid is every product down to 2^-16 of the sum in one
        # pass; only lo x hi, of the six `highest` runs, is left out
        hi, mid, lo = _bf16_parts(dq)
        dq_w = _side_by_side([hi, mid, lo], k)
        dq_x = _side_by_side([hi, hi, hi, mid, mid], k)
    precision = None if split else lax.Precision.HIGHEST
    for j in range(n):
        dw_ref[j] += lax.dot_general(
            x_ref[0, j], dq_w, (((0,), (0,)), ((), ())),
            preferred_element_type=_F32, precision=precision)
        path_ref[...] = lax.dot_general(
            dq_x, w_ref[j], (((1,), (1,)), ((), ())),
            preferred_element_type=_F32, precision=precision)

        def first(rows):
            return _columns(dh_ref[rows, :], (j, n))

        def tile(rows, lanes, coefs):
            dx = coefs[0] * du_ref[0, rows, lanes].astype(_F32) \
                + path_ref[rows, lanes] \
                + coefs[1] * x_ref[0, j, rows, lanes].astype(_F32)
            dx_ref[0, j, rows, lanes] = dx.astype(dx_ref.dtype)
            return coefs

        _by_chunks(tt, c, first, tile)


@functools.partial(jax.jit, static_argnames=(
    "iters", "eps", "clamp", "interpret"))
def mhc_pre_bwd_pallas(x, norm_scale, phi, alpha, bias, du, dpost, dres,
                       iters, eps, clamp, interpret=False):
    """The gradients of (X, NormScale, Phi, Alpha, Bias) from those of
    (U, HPost, HRes), X's in x's dtype, the parameters' float32."""
    b, n, t, c = x.shape
    split = x.dtype == _BF16
    tt = token_block("pre_bwd", n, t, c, x.dtype.itemsize)
    w, k = product_weight(norm_scale, phi, n, split)
    ins, outs = _pre_specs(n, tt, c, w)
    streams = ins[0]
    dx, dw, dab = pl.pallas_call(
        functools.partial(_pre_bwd_kernel, n=n, k=k, split=split,
                          iters=iters, eps=eps, clamp=clamp),
        name="pt_mhc_pre_bwd",
        grid=(b, t // tt),
        in_specs=ins + outs,
        out_specs=[streams,
                   pl.BlockSpec((n, c, _LANES), lambda b, r: (0, 0, 0)),
                   pl.BlockSpec((8, _LANES), lambda b, r: (0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((n, c, _LANES), _F32),
                   jax.ShapeDtypeStruct((8, _LANES), _F32)],
        scratch_shapes=[pltpu.VMEM((tt, _LANES), _F32),
                        pltpu.VMEM((tt, _LANES), _F32),
                        pltpu.VMEM((tt, c), _F32)],
        interpret=interpret,
        **_params(interpret, ("arbitrary", "arbitrary"),
                  _vmem("pre_bwd", n, tt, c, x.dtype.itemsize)),
    )(x, w, gate_rows(alpha, bias, n), du.astype(x.dtype),
      dpost.astype(_F32), dres.astype(_F32))
    dw = dw.reshape(n * c, _LANES)
    dw = sum(dw[:, i * k:(i + 1) * k] for i in range(3 if split else 1))
    return (dx,
            jnp.sum(dw * phi.astype(_F32), axis=1).astype(norm_scale.dtype),
            (dw * norm_scale.astype(_F32)[:, None]).astype(phi.dtype),
            jnp.stack([jnp.sum(g) for g in jnp.split(dab[0, :k], [n, 2 * n])]
                      ).astype(alpha.dtype),
            dab[1, :k].astype(bias.dtype))

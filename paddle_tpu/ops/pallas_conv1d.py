"""The short depthwise causal convolution over time of the Mamba-2 and
KDA mixers (ops/ssd_ops.py causal_conv1d), forward and backward, as two
Pallas TPU kernels that move every operand once.

    z[t, c] = sum_k W[c, k] x[t - (K-1) + k, c] (+ Bias[c])     x = 0, t < 0
    y = silu(z) | z

x [B, T, C] token-major, channels on the lanes.  In XLA the K
row-shifted slices of the padded x are relayouts (a shift by 1..K-1
rows is no multiple of a sublane tile) and the op ran at an eighth of
the HBM's rate (PERF.md, PR 38).  Here a grid step takes a row tile of
one lane block and, through a second BlockSpec, the _HALO rows before
it (zeros at every batch start); in VMEM it walks the tile by chunks
of _CHUNK rows (one traced loop body: unrolled over a tile's 32 chunks
the 27 calls of a step added 8 s to a cell's set-up, PERF.md, PR 42),
casts a chunk and the rows before it to float32, shifts by rows with
sublane rolls and adds the K taps in the XLA op's order.

Grid (B, C / lane block, T / row tile).  The forward's steps are
independent.  The backward walks the row tiles from the last to the
first, because dx needs dz of the K - 1 rows AFTER a row:

    dz = dy silu'(z)                    z formed again from x
    dx[t] = sum_k W[:, k] dz[t + (K-1) - k]
    dW[c, k] = sum_t dz[t] x[t - (K-1) + k]         dBias = sum_t dz

so it carries the first rows of the later tile's dz in a VMEM scratch
(zeros at every batch end) and accumulates dW and dBias in float32 in
its output block, which stays in VMEM along the row axis and is
written once a lane block.  It reads x, W, Bias and dy only: no saved
output, so a recompute segment has nothing more to bind.

Float32 inside, y and dx in x's dtype, dW and dBias float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_F32 = jnp.float32
_LANES = 128
_HALO = 16          # rows: a whole sublane tile of bfloat16 and float32
_MAX_TAPS = 8
_ACC_ROWS = 16      # dW in rows [0, K), dBias in row _MAX_TAPS
_ROW_TILES = (2048, 1024, 512, 256, 128, 64, 32, 16)
_LANE_BLOCKS = (256, 128)
_CHUNK = 64         # rows a pass of the kernel body works on


def tiles(t, c, k):
    """(row tile, lane block) the kernels take for x [., t, c] and k
    taps, or None where they cannot tile it: then the XLA graph runs."""
    if c % _LANES or not 1 <= k <= _MAX_TAPS:
        return None
    tt = next((r for r in _ROW_TILES if t % r == 0), None)
    if tt is None:
        return None
    return tt, next(b for b in _LANE_BLOCKS if c % b == 0)


def _shifts(xx, k, back=False):
    """[xx[t - (K-1) + i] for i < K] by row t of xx (back: xx[t + (K-1)
    - i]): sublane rolls; the rows a roll wraps round are the caller's
    to drop."""
    n = xx.shape[0]
    return [xx if s == 0 else pltpu.roll(xx, n - s if back else s, 0)
            for s in range(k - 1, -1, -1)]


def _taps(shifted, w_ref):
    """sum_i W[i] shifted[i], the adds in the XLA op's order."""
    z = shifted[0] * w_ref[0:1, :]
    for i in range(1, len(shifted)):
        z = z + shifted[i] * w_ref[i:i + 1, :]
    return z


def _by_chunks(x_ref, halo, chunk, carry, rev=False):
    """Walks the tile by chunks of _CHUNK rows, the last first where
    `rev`: carry = chunk(xx, rows, carry) with xx the float32 rows of
    the chunk and the _HALO rows before it (`halo` before the tile's
    first) and `rows` the chunk's place in the tile.  One traced body
    for the chunks after the first, whatever the tile holds."""
    tt = x_ref.shape[1]
    ch = min(_CHUNK, tt)
    n = tt // ch

    def first(carry):
        xx = jnp.concatenate([halo, x_ref[0, 0:ch].astype(_F32)], 0)
        return chunk(xx, pl.ds(0, ch), carry)

    def later(j, carry):
        i = n - 1 - j if rev else j + 1
        at = pl.multiple_of(i * ch, ch)
        xx = x_ref[0, pl.ds(at - _HALO, ch + _HALO)].astype(_F32)
        return chunk(xx, pl.ds(at, ch), carry)

    if not rev:
        carry = first(carry)
    if n > 1:
        carry = lax.fori_loop(0, n - 1, later, carry)
    return first(carry) if rev else carry


def _fwd_kernel(*refs, k, act, has_bias):
    x_ref, halo_ref, w_ref = refs[:3]
    b_ref = refs[3] if has_bias else None
    y_ref = refs[-1]
    halo = halo_ref[0].astype(_F32)
    halo = jnp.where(pl.program_id(2) == 0, jnp.zeros_like(halo), halo)

    def chunk(xx, rows, carry):
        z = _taps(_shifts(xx, k), w_ref)[_HALO:]
        if has_bias:
            z = z + b_ref[...]
        if act == "silu":
            z = jax.nn.silu(z)
        y_ref[0, rows] = z.astype(y_ref.dtype)
        return carry

    _by_chunks(x_ref, halo, chunk, 0)


def _bwd_kernel(*refs, k, act, has_bias):
    x_ref, halo_ref, dy_ref, w_ref = refs[:4]
    b_ref = refs[4] if has_bias else None
    dx_ref, acc_ref, next_ref = refs[-3:]
    r = pl.program_id(2)            # 0 is the LAST row tile

    @pl.when(r == 0)
    def _batch_end():
        next_ref[...] = jnp.zeros_like(next_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    halo = halo_ref[0].astype(_F32)
    halo = jnp.where(r == pl.num_programs(2) - 1, jnp.zeros_like(halo),
                     halo)

    def chunk(xx, rows, after):
        """`after`: dz of the _HALO rows after the chunk."""
        dz = dy_ref[0, rows].astype(_F32)
        xs = _shifts(xx, k)
        if act == "silu":
            z = _taps(xs, w_ref)[_HALO:]
            if has_bias:
                z = z + b_ref[...]
            s = jax.nn.sigmoid(z)
            dz = dz * (s * (1.0 + z * (1.0 - s)))
        dx = _taps(_shifts(jnp.concatenate([dz, after], 0), k, back=True),
                   w_ref)
        dx_ref[0, rows] = dx[:dz.shape[0]].astype(dx_ref.dtype)
        for i in range(k):
            acc_ref[0, i:i + 1, :] += jnp.sum(dz * xs[i][_HALO:], axis=0,
                                              keepdims=True)
        if has_bias:
            acc_ref[0, _MAX_TAPS:_MAX_TAPS + 1, :] += jnp.sum(
                dz, axis=0, keepdims=True)
        return dz[:_HALO]

    next_ref[...] = _by_chunks(x_ref, halo, chunk, next_ref[...], rev=True)


def _call(kernel, name, x, w, bias, rev, interpret):
    """What the two pallas_calls share: (call, specs, operands) over
    the grid (B, C / lane block, T / row tile); `rev` walks the row
    tiles from the last to the first (then the row axis is sequential).
    call(in_specs, out_specs, out_shape) -> the kernel's function;
    operands: the filter with taps on the sublanes [K, C] and the bias
    [1, C], float32, with their specs under "w"."""
    b, t, c = x.shape
    k = w.shape[-1]
    tt, bl = tiles(t, c, k)
    nr, per = t // tt, tt // _HALO

    def row(r):
        return nr - 1 - r if rev else r

    operands = [w.astype(_F32).T]
    if bias is not None:
        operands.append(bias.astype(_F32).reshape(1, -1))
    specs = {
        "x": pl.BlockSpec((1, tt, bl), lambda i, c, r: (i, row(r), c)),
        # the _HALO rows before the tile; the first tile's are masked
        "halo": pl.BlockSpec(
            (1, _HALO, bl),
            lambda i, c, r: (i, jnp.maximum(row(r) * per - 1, 0), c)),
        "w": [pl.BlockSpec((o.shape[0], bl), lambda i, c, r: (0, c))
              for o in operands],
        "acc": pl.BlockSpec((1, _ACC_ROWS, bl), lambda i, c, r: (i, 0, c)),
    }
    more = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary" if rev else "parallel"),
            vmem_limit_bytes=64 << 20)}
    if rev:     # dz of the rows after the tile, carried along the rows
        more["scratch_shapes"] = [pltpu.VMEM((_HALO, bl), _F32)]
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, k=k, has_bias=bias is not None),
        name=name, grid=(b, c // bl, nr), interpret=interpret, **more)
    return call, specs, operands


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def conv1d_fwd_pallas(x, w, bias, act, interpret=False):
    """y [B, T, C] in x's dtype."""
    call, sp, wb = _call(functools.partial(_fwd_kernel, act=act),
                         "pt_conv1d_fwd", x, w, bias, False, interpret)
    return call(in_specs=[sp["x"], sp["halo"]] + sp["w"],
                out_specs=sp["x"],
                out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x, x, *wb)


@functools.partial(jax.jit, static_argnames=("act", "interpret"))
def conv1d_bwd_pallas(x, w, bias, dy, act, interpret=False):
    """(dx in x's dtype, dW [C, K] and dBias [C] (None without a bias)
    in their inputs' dtypes)."""
    call, sp, wb = _call(functools.partial(_bwd_kernel, act=act),
                         "pt_conv1d_bwd", x, w, bias, True, interpret)
    b, _, c = x.shape
    dx, acc = call(
        in_specs=[sp["x"], sp["halo"], sp["x"]] + sp["w"],
        out_specs=[sp["x"], sp["acc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, _ACC_ROWS, c), _F32)],
    )(x, x, dy.astype(x.dtype), *wb)
    acc = jnp.sum(acc, axis=0)
    dw = acc[:w.shape[-1]].T.astype(w.dtype)
    db = None if bias is None else acc[_MAX_TAPS].astype(bias.dtype)
    return dx, dw, db

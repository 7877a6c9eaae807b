"""The short depthwise causal convolution over time of the Mamba-2 and
KDA mixers and, with two gates, the whole mixer of an LFM2 layer
(ops/ssd_ops.py causal_conv1d), forward and backward, as two Pallas TPU
kernels that move every operand once.

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

`gated` (a static option of both kernels: the same bodies): the
operand is P [B, T, 3 C], one projection whose thirds along the lanes
are the gates Gb, Gc and the signal x~, in that order, and

    x = Gb * x~        y = Gc * act(z)         y [B, T, C]

so the input gate is applied before the taps read a row and its
predecessors, the output gate after them.  The forward reads the three
thirds in place, as lane blocks of P found by three index maps (no
split of P).  The backward forms x and act(z) again in VMEM and writes

    dGc = dy act(z)     dz = dy Gc act'(z)     dGb = dx x~     dx~ = dx Gb

into ONE gradient dP [B, T, 3 C], so that the projection's grad reads
one array as its forward wrote one (no concatenation of three).  An
output block lies at one place, so the gated backward's blocks hold a
row tile at P's whole width, grid (B, T / row tile), and the body
walks the lane blocks of a third with the same code; the row tile is
the largest whose blocks stay under _GATED_BWD_VMEM.

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
_ALL = slice(None)
# the gated backward's blocks at P's whole width, double-buffered: P,
# dP and dy, 7 C elements a row
_GATED_BWD_VMEM = 32 << 20


def tiles(t, c, k):
    """(row tile, lane block) the kernels take for x [., t, c] and k
    taps, or None where they cannot tile it: then the XLA graph runs."""
    if c % _LANES or not 1 <= k <= _MAX_TAPS:
        return None
    tt = next((r for r in _ROW_TILES if t % r == 0), None)
    if tt is None:
        return None
    return tt, next(b for b in _LANE_BLOCKS if c % b == 0)


def gated_bwd_row_tile(t, c, itemsize):
    """The gated backward's row tile: tiles()'s, halved until the
    blocks of P, dP and dy at their whole widths fit _GATED_BWD_VMEM
    twice over (512 rows at C 2,048 in bfloat16)."""
    tt = tiles(t, c, 1)[0]
    while tt > _HALO and 2 * 7 * c * itemsize * tt > _GATED_BWD_VMEM:
        tt //= 2
    return tt


def _shifts(xx, k, back=False):
    """[xx[t - (K-1) + i] for i < K] by row t of xx (back: xx[t + (K-1)
    - i]): sublane rolls; the rows a roll wraps round are the caller's
    to drop."""
    n = xx.shape[0]
    return [xx if s == 0 else pltpu.roll(xx, n - s if back else s, 0)
            for s in range(k - 1, -1, -1)]


def _taps(shifted, w):
    """sum_i W[i] shifted[i], the adds in the XLA op's order; w(i) the
    float32 [1, lanes] row of tap i."""
    z = shifted[0] * w(0)
    for i in range(1, len(shifted)):
        z = z + shifted[i] * w(i)
    return z


def _rows(ref, lanes=_ALL):
    """rows -> float32 ref[0, rows, lanes] of a [1, T, C] block."""
    return lambda rows: ref[0, rows, lanes].astype(_F32)


def _signal(x, gate=None):
    """rows -> the convolution's input: x, times the input gate where
    there is one."""
    if gate is None:
        return x
    return lambda rows: gate(rows) * x(rows)


def _by_chunks(x, tt, halo, chunk, carry, rev=False):
    """Walks a tile of tt rows by chunks of _CHUNK rows, the last first
    where `rev`: carry = chunk(xx, rows, carry) with xx the float32
    rows x(.) of the chunk and the _HALO rows before it (`halo` before
    the tile's first) and `rows` the chunk's place in the tile.  One
    traced body for the chunks after the first, whatever the tile
    holds."""
    ch = min(_CHUNK, tt)
    n = tt // ch

    def first(carry):
        xx = jnp.concatenate([halo, x(pl.ds(0, ch))], 0)
        return chunk(xx, pl.ds(0, ch), carry)

    def later(j, carry):
        i = n - 1 - j if rev else j + 1
        at = pl.multiple_of(i * ch, ch)
        return chunk(x(pl.ds(at - _HALO, ch + _HALO)), pl.ds(at, ch),
                     carry)

    if not rev:
        carry = first(carry)
    if n > 1:
        carry = lax.fori_loop(0, n - 1, later, carry)
    return first(carry) if rev else carry


def _pre_activation(xs, w, bias):
    z = _taps(xs, w)[_HALO:]
    return z if bias is None else z + bias()


def _fwd_kernel(*refs, k, act, has_bias, gated):
    refs = list(refs)
    y_ref = refs.pop()
    x_ref, halo_ref = refs[:2]
    gb_ref, gb_halo_ref, gc_ref = refs[2:5] if gated else (None,) * 3
    w_ref = refs[5 if gated else 2]
    b_ref = refs[-1] if has_bias else None
    x = _signal(_rows(x_ref), _rows(gb_ref) if gated else None)
    halo = _signal(_rows(halo_ref),
                   _rows(gb_halo_ref) if gated else None)(_ALL)
    halo = jnp.where(pl.program_id(2) == 0, jnp.zeros_like(halo), halo)
    bias = None if b_ref is None else (lambda: b_ref[...])

    def chunk(xx, rows, carry):
        z = _pre_activation(_shifts(xx, k), lambda i: w_ref[i:i + 1, :],
                            bias)
        if act == "silu":
            z = jax.nn.silu(z)
        if gated:
            z = z * _rows(gc_ref)(rows)
        y_ref[0, rows] = z.astype(y_ref.dtype)
        return carry

    _by_chunks(x, x_ref.shape[1], halo, chunk, 0)


def _bwd_kernel(*refs, k, act, has_bias, gated, bl):
    """Ungated: one lane block a grid step, grid (B, C / bl, T / tt).
    Gated: P's whole width a grid step, grid (B, T / tt); the lane
    blocks of a third are walked here."""
    x_ref, halo_ref, dy_ref, w_ref = refs[:4]
    b_ref = refs[4] if has_bias else None
    dx_ref, acc_ref, next_ref = refs[-3:]
    row_axis = 1 if gated else 2
    r = pl.program_id(row_axis)            # 0 is the LAST row tile
    tt, c = dy_ref.shape[1:]

    @pl.when(r == 0)
    def _batch_end():
        next_ref[...] = jnp.zeros_like(next_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    batch_start = r == pl.num_programs(row_axis) - 1

    def lane_block(at):
        """The backward of the channels [at, at + bl) of the tile."""
        own = pl.ds(at, bl) if gated else _ALL          # of y's C lanes
        gb, gc, xt = ((pl.ds(i * c + at, bl) for i in range(3))
                      if gated else (None, None, _ALL))    # of P's lanes
        x = _signal(_rows(x_ref, xt), _rows(x_ref, gb) if gated else None)
        halo = _signal(_rows(halo_ref, xt),
                       _rows(halo_ref, gb) if gated else None)(_ALL)
        halo = jnp.where(batch_start, jnp.zeros_like(halo), halo)

        def w(i):
            return w_ref[i:i + 1, own]

        bias = None if b_ref is None else (lambda: b_ref[:, own])

        def chunk(xx, rows, after):
            """`after`: dz of the _HALO rows after the chunk."""
            dz = _rows(dy_ref, own)(rows)
            xs = _shifts(xx, k)
            if act == "silu" or gated:
                z = _pre_activation(xs, w, bias)
            if gated:
                out = jax.nn.silu(z) if act == "silu" else z
                dx_ref[0, rows, gc] = (dz * out).astype(dx_ref.dtype)
                dz = dz * _rows(x_ref, gc)(rows)
            if act == "silu":
                s = jax.nn.sigmoid(z)
                dz = dz * (s * (1.0 + z * (1.0 - s)))
            dx = _taps(_shifts(jnp.concatenate([dz, after], 0), k,
                               back=True), w)[:dz.shape[0]]
            if gated:
                dx_ref[0, rows, gb] = (dx * _rows(x_ref, xt)(rows)
                                       ).astype(dx_ref.dtype)
                dx = dx * _rows(x_ref, gb)(rows)
            dx_ref[0, rows, xt] = dx.astype(dx_ref.dtype)
            for i in range(k):
                acc_ref[0, i:i + 1, own] += jnp.sum(
                    dz * xs[i][_HALO:], axis=0, keepdims=True)
            if has_bias:
                acc_ref[0, _MAX_TAPS:_MAX_TAPS + 1, own] += jnp.sum(
                    dz, axis=0, keepdims=True)
            return dz[:_HALO]

        next_ref[:, own] = _by_chunks(x, tt, halo, chunk,
                                      next_ref[:, own], rev=True)

    for at in range(0, c, bl):
        lane_block(at)


def _call(kernel, name, x, w, bias, rev, gated, interpret):
    """What the two pallas_calls share: (call, specs, operands) over
    the grid (B, C / lane block, T / row tile); `rev` walks the row
    tiles from the last to the first (then the row axis is sequential),
    and with `gated` its grid is (B, T / row tile) over blocks of x's
    whole width.  call(in_specs, out_specs, out_shape) -> the kernel's
    function; operands: the filter with taps on the sublanes [K, C] and
    the bias [1, C], float32, with their specs under "w"; "y" a block
    of an array of the convolution's C channels, "x" of x (the third
    x~ of a gated forward's, whose gates' blocks are "gb" and "gc")."""
    b, t, width = x.shape
    c, k = w.shape
    tt, bl = tiles(t, c, k)
    whole = gated and rev
    if whole:
        tt = gated_bwd_row_tile(t, c, x.dtype.itemsize)
    nr, per, nc = t // tt, tt // _HALO, c // bl

    def row(r):
        return nr - 1 - r if rev else r

    def before(r):
        # the _HALO rows before the tile; the first tile's are masked
        return jnp.maximum(row(r) * per - 1, 0)

    operands = [w.astype(_F32).T]
    if bias is not None:
        operands.append(bias.astype(_F32).reshape(1, -1))
    if whole:
        specs = {
            "x": pl.BlockSpec((1, tt, width), lambda i, r: (i, row(r), 0)),
            "halo": pl.BlockSpec((1, _HALO, width),
                                 lambda i, r: (i, before(r), 0)),
            "y": pl.BlockSpec((1, tt, c), lambda i, r: (i, row(r), 0)),
            "w": [pl.BlockSpec((o.shape[0], c), lambda i, r: (0, 0))
                  for o in operands],
            "acc": pl.BlockSpec((1, _ACC_ROWS, c), lambda i, r: (i, 0, 0)),
        }
        grid, sequential = (b, nr), ("parallel", "arbitrary")
    else:
        def third(n):
            # the lane blocks of P's n-th third; of x where not gated
            return (pl.BlockSpec((1, tt, bl),
                                 lambda i, c, r: (i, row(r), n * nc + c)),
                    pl.BlockSpec((1, _HALO, bl),
                                 lambda i, c, r: (i, before(r), n * nc + c)))

        x_spec, halo_spec = third(2 if gated else 0)
        specs = {
            "x": x_spec, "halo": halo_spec, "y": third(0)[0],
            "w": [pl.BlockSpec((o.shape[0], bl), lambda i, c, r: (0, c))
                  for o in operands],
            "acc": pl.BlockSpec((1, _ACC_ROWS, bl),
                                lambda i, c, r: (i, 0, c)),
        }
        if gated:
            specs["gb"], specs["gb_halo"] = third(0)
            specs["gc"] = third(1)[0]
        grid = (b, nc, nr)
        sequential = ("parallel", "parallel",
                      "arbitrary" if rev else "parallel")
    more = {} if interpret else {
        "compiler_params": pltpu.CompilerParams(
            dimension_semantics=sequential, vmem_limit_bytes=64 << 20)}
    if rev:     # dz of the rows after the tile, carried along the rows
        more["scratch_shapes"] = [
            pltpu.VMEM((_HALO, c if whole else bl), _F32)]
    call = functools.partial(
        pl.pallas_call,
        functools.partial(kernel, k=k, has_bias=bias is not None,
                          gated=gated),
        name=name, grid=grid, interpret=interpret, **more)
    return call, specs, operands


@functools.partial(jax.jit, static_argnames=("act", "gated", "interpret"))
def conv1d_fwd_pallas(x, w, bias, act, gated=False, interpret=False):
    """y [B, T, C] in x's dtype; x [B, T, C], or with `gated` the
    projection [B, T, 3 C] = [Gb | Gc | x~]."""
    call, sp, wb = _call(functools.partial(_fwd_kernel, act=act),
                         "pt_conv1d_fwd", x, w, bias, False, gated,
                         interpret)
    gates = [sp["gb"], sp["gb_halo"], sp["gc"]] if gated else []
    return call(
        in_specs=[sp["x"], sp["halo"]] + gates + sp["w"],
        out_specs=sp["y"],
        out_shape=jax.ShapeDtypeStruct(x.shape[:2] + w.shape[:1], x.dtype),
    )(*[x] * (2 + len(gates)), *wb)


@functools.partial(jax.jit, static_argnames=("act", "gated", "interpret"))
def conv1d_bwd_pallas(x, w, bias, dy, act, gated=False, interpret=False):
    """(dx in x's dtype and shape: with `gated` the whole projection's
    gradient [dGb | dGc | dx~]; dW [C, K] and dBias [C] (None without a
    bias) in their inputs' dtypes)."""
    call, sp, wb = _call(
        functools.partial(_bwd_kernel, act=act,
                          bl=tiles(x.shape[1], *w.shape)[1]),
        "pt_conv1d_bwd", x, w, bias, True, gated, interpret)
    b, c = x.shape[0], w.shape[0]
    dx, acc = call(
        in_specs=[sp["x"], sp["halo"], sp["y"]] + sp["w"],
        out_specs=[sp["x"], sp["acc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, _ACC_ROWS, c), _F32)],
    )(x, x, dy.astype(x.dtype), *wb)
    acc = jnp.sum(acc, axis=0)
    dw = acc[:w.shape[-1]].T.astype(w.dtype)
    db = None if bias is None else acc[_MAX_TAPS].astype(bias.dtype)
    return dx, dw, db

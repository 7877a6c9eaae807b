"""The rotary position embedding (ops/llm_ops.py rotary_embedding) as
one Pallas TPU kernel, pt_rotary, that turns X where it lies.

X is read as [B, T, H D]: tokens on the sublanes, a token's heads side
by side on the lanes, which is how a projection writes it and how the
token-major flash kernels read it.  The XLA form reshapes the last axis
to (2, rd/2) or (D/2, 2) to find an entry's partner: a 128-lane head
cut into two halves of 64 lanes, or into 64 pairs of 2, is a relayout
of every tile of the array, in float32, forward and again in the
generic backward (PERF.md, PR 54).  Here the partner is a LANE ROLL:

    out = x cos + partner(x) sin          float32, written in x's dtype

    pairing       partner of lane l, p = l mod D
    halves        l + rd/2 where p < D - rd/2, else l - rd/2
    interleaved   l + 1 where l is even, else l - 1

so two rolls of the block (up and down) and a select on the lane, or
ONE roll where the two land on the same lanes (halves of a whole
128-lane head: by 64).  A roll wraps round the chunk it is taken over,
and no lane the select takes has wrapped: a pair lies inside its head,
and a chunk holds whole heads.  The sign of the pair's first entry
(`a cos - b sin`, `b cos + a sin`) is in the sin table, and the leading
D - rd lanes of a head, which pass through, read cos 1 and sin 0 there.

The tables are float32 [T, L] with L = lcm(D, 128) lanes: the period
along the lanes of the pattern (head, entry) -> (cos, sin), in whole
lane tiles (128 at D 64 and 128, 384 at D 192).  The grid is (T / row
tile, B, H D / lane block) with the lane blocks innermost: a table
block's index does not change along them nor along the batch, so the
pipeline fetches it once a row tile.  In VMEM the body walks a block by
passes of _PASS entries, each unrolled into chunks of _ROWS rows (the
tables' rows read once a chunk) x the W lanes a roll is taken over: a
lane tile where no pair straddles one, else L.

The op's output is `m R(theta) x` pair by pair, so its backward is `m
R(-theta) g`: the SAME kernel over the tables with sin negated
(tables(back=True)).  Nothing is saved for it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas_gmm import _params

_F32 = jnp.float32
_LANES = 128
_ROWS = 64              # rows whose tables a pass of the body holds
_PASS = 1 << 16         # entries of X a pass of the body works on
_ROW_TILES = (512, 256, 128, 64, 32)
_LANE_BLOCK = 1024      # lanes of X a grid step takes, at most
_MAX_TABLE_LANES = 1024


def table_lanes(d):
    """L: the lanes after which the heads' (cos, sin) pattern repeats,
    in whole lane tiles."""
    return d * _LANES // math.gcd(d, _LANES)


def blocks(t, width, d):
    """(row tile, lane block) the kernel takes for X [., t, width] of
    heads of d entries, or None where it cannot tile it (width no
    multiple of 128, so that no chunk of whole lane tiles holds whole
    heads; t no multiple of a row tile): then the XLA form runs."""
    lanes = table_lanes(d)
    if width % d or width % _LANES or lanes > _MAX_TABLE_LANES:
        return None
    tr = next((r for r in _ROW_TILES if t % r == 0), None)
    if tr is None:
        return None
    bl = max(b for b in range(lanes, width + 1, lanes)
             if width % b == 0 and (b <= _LANE_BLOCK or b == lanes))
    return tr, bl


def partner_rule(d, rd, pairing):
    """(period, cut, shift, W): lane l's partner is l + shift where
    l mod period < cut, else l - shift, by rolls over W lanes: a lane
    tile where no pair straddles one (every interleaved pair, whose
    first entry is on an even lane; the halves of a head that divides
    128 lanes), else all of table_lanes(d)."""
    if pairing == "halves":
        return (d, d - rd // 2, rd // 2,
                _LANES if _LANES % d == 0 else table_lanes(d))
    return 2, 1, 1, _LANES


@functools.partial(jax.jit, static_argnames=(
    "t", "d", "rd", "pairing", "mscale", "back"))
def tables(t, d, rd, pairing, inv_freq, mscale, back=False):
    """(cos, sin) float32 [t, table_lanes(d)]: for each position and
    each lane of a run of whole heads, mscale cos(angle) and +/- mscale
    sin(angle), the minus on a pair's first entry (`back`: on its
    second, the rotation by the negative angle); 1 and 0 on the lanes
    that pass through.  The angles and their products with mscale are
    the XLA form's, entry for entry.  Jitted, so that a step's many
    rotary ops trace these ten operations once a signature (ouro's 48
    ops, traced five times each, spent 1.2 s of the step's trace
    here; PERF.md, PR 54)."""
    ang = jnp.arange(t, dtype=_F32)[:, None] * jnp.asarray(inv_freq)[None]
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    first, second = (sin, -sin) if back else (-sin, sin)
    if pairing == "halves":
        cos = jnp.concatenate([cos, cos], axis=1)
        sin = jnp.concatenate([first, second], axis=1)
    else:
        cos = jnp.repeat(cos, 2, axis=1)
        sin = jnp.stack([first, second], axis=-1).reshape(t, rd)
    keep = ((0, 0), (d - rd, 0))
    reps = (1, table_lanes(d) // d)
    return (jnp.tile(jnp.pad(cos, keep, constant_values=1.0), reps),
            jnp.tile(jnp.pad(sin, keep), reps))


def _kernel(cos_ref, sin_ref, x_ref, o_ref, *, period, cut, shift, width):
    tr, bl = x_ref.shape[1:]
    lanes = cos_ref.shape[1]
    one_roll = 2 * shift == width
    chunk = min(_ROWS, tr)
    if not one_roll:
        lane = lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
        up = lane % period < cut
    # a pass of the body: _PASS entries as chunks of _ROWS rows x W
    # lanes, unrolled.  The chunks are independent, so their loads,
    # rolls and stores overlap; ONE chunk a loop step left the units
    # idle most of a step (mellum2's q: 1.59 ms a call at 32 x 128,
    # 0.48 at 64 x 1,024, against 0.35 at the HBM's rate; PERF.md,
    # PR 54)
    rows_a_pass = next(r for r in (512, 256, 128, chunk) if tr % r == 0
                       and (r * bl <= _PASS or r == chunk))

    def a_pass(r, _):
        for below in range(0, rows_a_pass, chunk):
            rows = pl.ds(pl.multiple_of(r * rows_a_pass + below, chunk),
                         chunk)
            # the tables' rows, read once for all the chunk's lanes
            cos, sin = cos_ref[rows, :], sin_ref[rows, :]
            for at in range(0, bl, width):
                tab = slice(at % lanes, at % lanes + width)
                x = x_ref[0, rows, at:at + width].astype(_F32)
                # roll(x, s)[l] = x[l - s]
                partner = pltpu.roll(x, width - shift, 1)
                if not one_roll:
                    partner = jnp.where(up, partner,
                                        pltpu.roll(x, shift, 1))
                o_ref[0, rows, at:at + width] = (
                    x * cos[:, tab] + partner * sin[:, tab]
                ).astype(o_ref.dtype)
        return 0

    lax.fori_loop(0, tr // rows_a_pass, a_pass, 0)


@functools.partial(jax.jit, static_argnames=("d", "rule", "interpret"))
def rotary_pallas(x, cos, sin, d, rule, interpret=False):
    """x [B, T, H d] turned by the tables (cos, sin) of tables();
    rule = partner_rule(..).  Out in x's shape and dtype."""
    b, t, width = x.shape
    tr, bl = blocks(t, width, d)
    lanes = cos.shape[1]
    period, cut, shift, roll = rule
    table = pl.BlockSpec((tr, lanes), lambda r, i, c: (r, 0))
    block = pl.BlockSpec((1, tr, bl), lambda r, i, c: (i, r, c))
    return pl.pallas_call(
        functools.partial(_kernel, period=period, cut=cut, shift=shift,
                          width=roll),
        name="pt_rotary",
        grid=(t // tr, b, width // bl),
        in_specs=[table, table, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,
        **_params(interpret, ("parallel",) * 3,
                  2 * (2 * tr * bl * x.dtype.itemsize + 2 * tr * lanes * 4)),
    )(cos, sin, x)

"""The routed experts' combine: each token's sum over the pairs whose
expert this chip holds of gate * the pair's padded row.

The rows lie sorted by expert (ops/llm_ops.py `_group_layout`); a token
has rows in up to k groups, so the sum is by TOKEN.  XLA's form
(llm_ops._tokens_of_rows) gathers N rows k times, a row for every
token-expert pair, and throws away the pairs routed elsewhere: with 8
of 512 experts held, 63 of 64 gathered rows.  The kernel here,
pt_moe_combine, reads a row only for a pair that is held.

Mosaic copies whole sublane tiles, not single rows of a tiled [M, C]
array, so the copies follow what the layout gives for nothing: a
group's rows are in pair order, hence the rows of one BLOCK of tn
consecutive tokens in one group are a contiguous run.  A grid step
takes a block of tokens:

    plan (combine_plan, arithmetic on [N, k, G], made once a layer):
        for each block and group the first row of the run, rounded down
        to a copy's unit of 16 rows, and the units that cover it; for
        each token cnt, the number of its held pairs, and for its held
        pairs IN PAIR ORDER where their rows land in the step's buffer;
    copies: the units of every run, HBM -> buf, started a grid step
        ahead (two buffers), so they fly under the block before's sums;
    sums: for each token with cnt > 0, in float32 registers,
        0 + gate_c[t, 0] * row + gate_c[t, 1] * row + ..., one [1, C]
        row read a pair, written once a token; the block is cast and
        leaves through the output's BlockSpec.

A grid step reads its own block of the plan and the gates, a few KiB of
SMEM whatever N is.

A pair that is not held added an exact zero in XLA's form and adds
nothing here: the same float32 products added in the same order.  What
a unit holds beside the run (another block's rows, a group's unwritten
padding) is copied and never read.  The cost follows the count of held
pairs, read at run time as the grouped matmuls' grids read n_active;
the worst case, every pair held, is the same kernel with longer runs.

A bf16 row array is copied as it is and read as 32-bit words, two rows
a word (the even row the low half); a bf16 is the high half of its
float32, so a shift or a mask makes the float32: exact.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas_gmm import _VMEM_BUDGET, _params

_F32 = jnp.float32
# rows a copy: a packed bf16 tile of VMEM, two float32 tiles; one unit
# for both, so that a layer's forward and backward read one plan
_UNIT = 16


def _buffer_rows(tn, k, n_groups):
    """Rows of a step's buffer: a block's runs hold at most tn k rows
    together, and each run's units up to a unit less one row on either
    side of it."""
    return -(-tn * k // _UNIT) * _UNIT + 2 * _UNIT * n_groups


def _vmem_bytes(tn, k, c, n_groups):
    """What a call takes of VMEM, reckoned for float32 rows and output
    whatever they are (one plan a layer): two buffers of runs, the
    float32 sums and two buffers of the output block."""
    return 4 * c * (2 * _buffer_rows(tn, k, n_groups) + 3 * tn)


def _token_block(n, k, c, n_groups, rows):
    """tn, the tokens a grid step of a combine of n tokens with k pairs
    each from [rows, c] row arrays in n_groups groups: the largest
    divisor of n that is a multiple of 16 (a bf16 output block's
    sublanes) and whose buffers _VMEM_BUDGET holds, or all n where n
    has no such divisor and fits.  None where the kernel cannot take
    the call: c not whole lanes, rows not whole units, no block that
    fits.
    A pure function of the call's shapes: a larger block has fewer runs
    to round up to whole units and fewer grid steps (PERF.md, PR 43:
    64 to 512 tokens read within 7% of each other on the chip)."""
    if c % 128 or rows % _UNIT:
        return None

    def fits(t):
        return _vmem_bytes(t, k, c, n_groups) <= _VMEM_BUDGET

    blocks = [t for t in itertools.takewhile(fits, range(16, n, 16))
              if n % t == 0]
    return blocks[-1] if blocks else n if fits(n) else None


def _compact(x, order):
    """x [k, N] by pair -> by held pair: out[s, n] is x[j, n] of the
    s-th held pair j of token n (order[j, n] == s), zero past the last.
    Arithmetic on [k, N]: no sort, no scatter."""
    return jnp.stack([jnp.sum(jnp.where(order == s, x, 0), axis=0)
                      for s in range(x.shape[0])])


def _by_block(x, tn):
    """x [k, N] -> [N / tn, 1, k tn]: a block of tn tokens' scalars a
    row, token t's s-th at s tn + t; the form a grid step takes its
    block in (SMEM, the last two extents whole)."""
    k, n = x.shape
    return x.reshape(k, n // tn, tn).transpose(1, 0, 2).reshape(
        n // tn, 1, k * tn)


def combine_plan(dest, slot, n_groups, c, rows):
    """What pt_moe_combine reads beside the [rows, c] row arrays, from
    `_group_layout`'s dest [N, k] (the padded row of each pair) and
    slot [N, k] (the held expert's place in the stack, n_groups where
    not held); None where the kernel cannot take the call's shapes
    (_token_block).  A dict: order [k, N] a pair's place among its
    token's held pairs, -1 where not held, and by block of tn tokens
    (_by_block) cnt a token's held pairs, pos where the row of a
    token's s-th held pair lands in its block's buffer, runs
    [N / tn, 1, 2 G] the first row of each group's run of the block's
    rows, then the number of its copies.  Tokens lie along the last
    axis throughout: an array with k or G last would be padded to 128
    lanes."""
    n, k = dest.shape
    tn = _token_block(n, k, c, n_groups, rows)
    if tn is None:
        return None
    blocks = n // tn
    dest, slot = dest.T, slot.T
    mine = slot < n_groups
    held = jnp.cumsum(mine.astype(jnp.int32), axis=0)
    order = jnp.where(mine, held - 1, -1)

    def by_block(x):        # [G, k, N], a block's tokens on their own axis
        return x.reshape(n_groups, k, blocks, tn)

    of_group = by_block(
        slot == jnp.arange(n_groups, dtype=jnp.int32)[:, None, None])
    row = by_block(jnp.broadcast_to(dest, (n_groups, k, n)))
    end = jnp.max(jnp.where(of_group, row + 1, 0), axis=(1, 3))
    # no row reaches the int32 maximum: a group's first may lie past
    # any multiple of the pairs (every group has a tile of its own)
    first = jnp.min(jnp.where(of_group, row, jnp.iinfo(jnp.int32).max),
                    axis=(1, 3)) // _UNIT * _UNIT
    first = jnp.where(end > 0, first, 0)
    units = jnp.where(end > 0, -(-(end - first) // _UNIT), 0)
    # a run's units follow the runs of the groups before it
    shift = (jnp.cumsum(units, axis=0) - units) * _UNIT - first
    pos = dest + jnp.sum(jnp.where(
        of_group, shift[:, None, :, None], 0), axis=0).reshape(k, n)
    return {"order": order, "cnt": _by_block(held[-1:], tn),
            "pos": _by_block(_compact(pos, order), tn),
            "runs": jnp.concatenate([first, units]).T.astype(
                jnp.int32)[:, None]}


def _combine_kernel(runs_ref, cnt_ref, pos_ref, *rest, tn, n_groups,
                    packed, gated):
    """Grid step i starts the copies of block i's runs and sums block
    i - 1, whose copies step i - 1 started: one step more than blocks."""
    if gated:
        gate_ref, a_ref, o_ref, buf, acc_ref, sem, pending = rest
    else:
        (a_ref, o_ref, buf, acc_ref, sem, pending), gate_ref = rest, None
    i = pl.program_id(0)
    into = i % 2
    c = acc_ref.shape[1]

    def copy(src, dst, slot):
        return pltpu.make_async_copy(
            a_ref.at[pl.ds(pl.multiple_of(src, _UNIT), _UNIT)],
            buf.at[slot, pl.ds(pl.multiple_of(dst, _UNIT), _UNIT)],
            sem.at[slot])

    @pl.when(i + 1 < pl.num_programs(0))
    def _start():
        def group(g, at):       # group after group, a unit after a unit
            first = runs_ref[0, 0, g]
            units = runs_ref[0, 0, n_groups + g]

            def unit(u, _):
                copy(first + u * _UNIT, (at + u) * _UNIT, into).start()
                return 0

            lax.fori_loop(0, units, unit, 0)
            return at + units

        pending[into] = lax.fori_loop(0, n_groups, group, jnp.int32(0))

    @pl.when(i > 0)
    def _sum():
        this = 1 - into

        def landed(u, _):       # a wait a copy, whichever it was
            copy(0, 0, this).wait()
            return 0

        lax.fori_loop(0, pending[this], landed, 0)
        rows = buf.at[this]
        words = rows.bitcast(jnp.uint32) if packed else rows
        acc_ref[...] = jnp.zeros_like(acc_ref)

        def token(t, _):
            def pair(s, total):
                at = pos_ref[0, 0, s * tn + t]
                if packed:      # the odd row is the high half already
                    low = (16 - 16 * (at % 2)).astype(jnp.uint32)
                    term = lax.bitcast_convert_type(
                        (words[pl.ds(at // 2, 1), :] << low)
                        & jnp.uint32(0xFFFF0000), _F32)
                else:
                    term = words[pl.ds(at, 1), :]
                if gated:
                    term = term * gate_ref[0, 0, s * tn + t]
                return total + term

            @pl.when(cnt_ref[0, 0, t] > 0)
            def _held():
                acc_ref[pl.ds(t, 1), :] = lax.fori_loop(
                    0, cnt_ref[0, 0, t], pair, jnp.zeros((1, c), _F32))

            return 0

        lax.fori_loop(0, tn, token, 0)
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("out_dtype", "interpret"))
def moe_combine_pallas(a, plan, gate=None, out_dtype=None, interpret=False):
    """a [M, C] float32 or bfloat16 (M a multiple of 16, C of 128),
    plan: combine_plan's for a's shape, gate [N, k] float32 or None ->
    [N, C] in out_dtype (a's by default): out[n] = sum over n's held
    pairs, in pair order, of gate * float32(the pair's row of a)."""
    c = a.shape[1]
    blocks, _, tn = plan["cnt"].shape
    k = plan["pos"].shape[2] // tn
    n_groups = plan["runs"].shape[2] // 2
    out_dtype = jnp.dtype(out_dtype or a.dtype)
    gates = () if gate is None else (
        _by_block(_compact(gate.T, plan["order"]), tn),)

    def scalars(x, index):      # a block of it a grid step
        return pl.BlockSpec((1,) + x.shape[1:], lambda i: (index(i), 0, 0),
                            memory_space=pltpu.SMEM)

    def started(i):
        return jnp.minimum(i, blocks - 1)

    def summed(i):
        return jnp.maximum(i - 1, 0)

    return pl.pallas_call(
        functools.partial(_combine_kernel, tn=tn, n_groups=n_groups,
                          packed=a.dtype != _F32, gated=gate is not None),
        name="pt_moe_combine",
        grid=(blocks + 1,),
        in_specs=[scalars(plan["runs"], started)]
        + [scalars(x, summed) for x in (plan["cnt"], plan["pos"], *gates)]
        + [pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((tn, c), lambda i: (summed(i), 0)),
        scratch_shapes=[
            pltpu.VMEM((2, _buffer_rows(tn, k, n_groups), c), a.dtype),
            pltpu.VMEM((tn, c), _F32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32)],
        out_shape=jax.ShapeDtypeStruct((blocks * tn, c), out_dtype),
        interpret=interpret,
        **_params(interpret, ("arbitrary",), _vmem_bytes(tn, k, c, n_groups)),
    )(plan["runs"], plan["cnt"], plan["pos"], *gates, a)

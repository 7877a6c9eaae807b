"""Pallas TPU kernels: fused flash attention.

Capability anchor: the reference computes attention as separate
matmul/softmax/matmul ops that materialize the [Tq, Tk] score matrix in
HBM (e.g. nets.py scaled_dot_product_attention,
/root/reference/python/paddle/fluid/nets.py:503-area; transformer tests
build it from `layers.matmul` + `layers.softmax`).  On TPU the score
matrix is the HBM-bandwidth bottleneck, so here attention is a single
Pallas kernel: blockwise QK^T on the MXU with online-softmax
accumulation in VMEM scratch — the [Tq, Tk] matrix never leaves VMEM
(FlashAttention pattern).

Layout: two, and ONE set of kernel bodies (docs/FLASH_ATTENTION.md).
Head-major q/k/v are [B, H, T, D] (what a caller that builds its heads
itself has: latent attention, whose v, output and its gradient have a
head size Dv of their own, q.k 192, v 128; ring and Ulysses).
Token-major they are [B, T, H*D], as the q, k and v projections leave
their matmuls and as the output projection wants the result back: the
kernels address head h through the BlockSpec index maps (`_Tiles`), so
no [B, H, T, D] copy of q, k, v, out or a gradient is ever made (96
copies of 33.5 MB in a six-layer step at 64 x 512; PERF.md, PR 31).  A
block's last dim must be a multiple of 128 lanes: at D = 64 a block
holds two heads, at 128 one, and a head's tile is the 128 lanes with
the other head's zeroed (`_head_tile`), which costs no MXU pass on a
128 x 128 array.  `_flash_layout` picks from the operands' rank, head
size and head count; a token-major call the blocks cannot serve is
transposed inside the entry.  Grid is (B*H/hpb, Tq/block_q,
Tk/block_k) with the KV
dimension innermost so the (acc, m, l) scratch carries across KV steps;
hpb is the heads a step takes: head-major 1, token-major the heads
of a lane block, 128/D.

A causal call computes a triangle of its grid and a call with a
sliding window (`window=`: query i sees keys i - window < j <= i) a
band of it.  Which block pair runs is ONE predicate (`_Diagonal`); a
causal call's dead steps hold their blocks (no fetch), and a windowed
call's grids walk the band's steps alone (no step below the band),
under kernel names of their own (pt_flash_win_*).

The public `flash_attention` is differentiable via ONE custom_vjp
(`_flash_lse`, shared with `flash_attention_lse` and the IR op): forward
runs the Pallas kernel on TPU (plain XLA path elsewhere) and saves
(q, k, v, o, lse); backward runs ONE Pallas kernel that recomputes P
blockwise from lse and writes dq, dk and dv from it (kv blocks outer,
the head's dq resident in VMEM; past 74k rows, where that dq does not
fit, a dq sweep and a dk/dv sweep) — the [Tq, Tk] matrices stay in VMEM
in both directions.  The XLA impl is plain attention, differentiated by
jax.
"""

from __future__ import annotations

import collections
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.observability import device_trace as _obs_device
from paddle_tpu.observability import metrics as _obs_metrics

# Which implementation each kernel entry ended on, counted where the
# choice is final: after the platform default AND after the geometry /
# VMEM reroutes, which choose from something observable but say
# nothing.  Counted at trace time inside a jit, per call when eager.
# chip_smoke.py reads it to fail a phase that names the Pallas path
# and got another.
_M_KERNEL_IMPL = _obs_metrics.counter(
    "paddle_tpu_kernel_impl_total",
    "Pallas-backed kernel entries by the implementation they resolved "
    "to (pallas | interpret | xla), after every reroute",
    # about 35 kernel names by two to four impls each: a process that
    # runs them all (a test worker) passed the default 64, and the
    # overflow series has no `kernel` label for a reader to key on
    max_series=256)


def _count_impl(kernel, impl):
    _M_KERNEL_IMPL.inc(kernel=kernel, impl=impl)


def _kernel_scope():
    """The name-stack element every Pallas kernel entry opens around
    its work, so that the device-side kernel names stay put.

    Each `pl.pallas_call` in ops/ carries a fixed `name="pt_<kernel>"`.
    The TPU compiler names a Mosaic custom call after the innermost
    element of the jax name stack, which `name=` makes the kernel's,
    and the profiler's device line shows it.  But under `jax.vjp`
    (core/registry.py builds every `<op>_grad` so) jax wraps the FIRST
    scope entered inside a transform in the transform's name, and the
    instruction would read `jvp_pt_flash_fwd_` or
    `transpose_jvp_pt_flash_bwd_dq__`.  This scope takes that wrapping
    (`transpose(jvp(pt))/pt_flash_bwd_dq/pallas_call`), so the
    instruction is the kernel's name in the forward op, in the backward
    op and under `shard_map` alike.  It is opened at the entry, outside
    the custom_vjp call, not around each pallas_call: a wrapper there
    cost 0.8 s of a 5.5 s first trace of the sharded Transformer step
    (PERF.md, PR 24).  No kernel name ends in a digit or a dot
    (benchmarks/trace_reduce.py strips those to group calls)."""
    return jax.named_scope("pt")


_NEG_INF = -1e30
_MIN_LANES = 128  # TPU vector lane count; m/l scratch padded to this


# ---------------------------------------------------------------------------
# reference (XLA) implementation — also the backward path
# ---------------------------------------------------------------------------

def _plain_attention(q, k, v, causal, scale, with_lse=False, window=0):
    """q/k/v: [B, H, T, D]; k and v may have H / group heads, and are
    then repeated to H (what the kernels never do).  with_lse: also
    the log-sum-exp of each row of the scaled, masked scores, float32
    [B, H, Tq].  window (0: none; causal only): a query sees the
    `window` keys that end at its own, key j where i - window < j <= i
    (`_Diagonal`)."""
    if k.shape[1] != q.shape[1]:
        group = q.shape[1] // _kv_heads(q, k, None)
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    p = None
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        qpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        kpos = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        mask = qpos + (tk - tq) >= kpos
        if window:
            mask &= qpos + (tk - tq) - window < kpos
        mask = mask[None, None]
        s = jnp.where(mask, s, _NEG_INF)
        # fully-masked rows (tq > tk) output 0, matching the kernel
        p = jax.nn.softmax(s, axis=-1) * mask
    else:
        p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) \
        .astype(q.dtype)
    if with_lse:
        return out, jax.nn.logsumexp(s, axis=-1)
    return out


# ---------------------------------------------------------------------------
# the causal grid: which block pairs compute, and what a dead step holds
# ---------------------------------------------------------------------------

def _block_of(pos, block, n):
    """The block of `block` rows that holds row `pos`, held to a grid
    of n blocks (block 0 for a row before the first).  Python ints in,
    a Python int out (`_Diagonal.band_steps` sizes a grid with it)."""
    if isinstance(pos, int):
        return min(max(pos, 0) // block, n - 1)
    return jnp.minimum(jnp.maximum(pos, 0) // block, n - 1)


class _Diagonal(collections.namedtuple(
        "_Diagonal", "block_q block_k q_off window", defaults=(0,))):
    """Where a causal call's diagonal lies over its grid of block pairs,
    and with a window the band under it: the ONE statement of which
    pair (qi, ki) computes, read by the three kernel bodies (`run`,
    `inside`) and, solved for either index, by the BlockSpec index maps
    (`_second_held`) and the grids' sizes (`band_steps`), so that
    predicate, maps and grids cannot drift.  q_off = tk - tq: query row
    r sees keys 0 .. q_off + r, with a window (0: none) the last
    `window` of them, q_off + r - window + 1 .. q_off + r.  The indices
    may be Python ints, arrays (a test walks a whole grid at once) or a
    grid's traced program ids."""

    def run(self, qi, ki):
        """Block pair (qi, ki) holds a score on or below the diagonal:
        its first key is no later than its last query row's last key;
        and, with a window, one inside the band: its last key is no
        earlier than its first query row's first key."""
        runs = (ki * self.block_k) <= (
            self.q_off + qi * self.block_q + self.block_q - 1)
        if self.window:
            runs &= (ki * self.block_k + self.block_k - 1) >= (
                self.q_off + qi * self.block_q - self.window + 1)
        return runs

    def inside(self, qi, ki):
        """Every score of block pair (qi, ki) is allowed, so the pair
        builds no mask: its last key is no later than its first query
        row's last key and, with a window, its first key no earlier
        than its last query row's first."""
        whole = (ki * self.block_k + self.block_k - 1) <= (
            self.q_off + qi * self.block_q)
        if self.window:
            whole &= (ki * self.block_k) >= (
                self.q_off + qi * self.block_q + self.block_q
                - self.window)
        return whole

    def last_ki(self, qi, nk):
        """The last kv block of the nk that q block qi runs: `run`
        solved for ki, held to the grid (0 for a q block that runs
        none, tq > tk: it computes nothing, whatever it holds)."""
        last_key = self.q_off + qi * self.block_q + self.block_q - 1
        return _block_of(last_key, self.block_k, nk)

    def first_ki(self, qi, nk):
        """`last_ki`'s twin at the band's lower edge: the first kv
        block that q block qi runs (0 without a window)."""
        if not self.window:
            return 0
        first_key = self.q_off + qi * self.block_q - self.window + 1
        return _block_of(first_key, self.block_k, nk)

    def first_qi(self, ki, nq):
        """The first q block of the nq that runs kv block ki: `run`
        solved for qi, held to the grid."""
        first_row = ki * self.block_k - self.q_off
        return _block_of(first_row, self.block_q, nq)

    def last_qi(self, ki, nq):
        """`first_qi`'s twin at the band's lower edge: the last q
        block that runs kv block ki (nq - 1 without a window)."""
        if not self.window:
            return nq - 1
        last_row = ki * self.block_k + self.block_k - 1 \
            + self.window - 1 - self.q_off
        return _block_of(last_row, self.block_q, nq)

    def dead_steps(self, nk):
        """Whether a grid of nk kv blocks holds a pair that does not
        run: the first q block's row of it has the fewest that do."""
        return not self.run(0, nk - 1)

    def band(self, outer, n, walks):
        """(first, last) of the n inner blocks that outer block `outer`
        runs: kv blocks of a q block (walks "kv": the forward, the dq
        sweep) or q blocks of a kv block (walks "q": the dk/dv
        sweep)."""
        if walks == "kv":
            return self.first_ki(outer, n), self.last_ki(outer, n)
        return self.first_qi(outer, n), self.last_qi(outer, n)

    def band_steps(self, nq, nk, walks):
        """The inner axis of a windowed call's grid: the most inner
        blocks an outer block runs, at most (window + block_q + block_k
        - 2) // block_k + 1 of the nk kv blocks a q block, and not nk:
        no pair below the band is a grid step.  Step j of outer block o
        is the pair of inner block first(o) + j (`band_block`), which
        the body tests like any other."""
        n_outer, n = (nq, nk) if walks == "kv" else (nk, nq)
        return max(last - first + 1 for first, last in (
            self.band(outer, n, walks) for outer in range(n_outer)))

    def band_block(self, outer, j, n, walks):
        """(inner block, whether the grid has it) of step j of a
        windowed call's band grid; without a window the grid's inner
        axis is the blocks themselves."""
        if not self.window:
            return j, True
        first, _ = self.band(outer, n, walks)
        return first + j, first + j < n


# ---------------------------------------------------------------------------
# a grid step's per-head tiles
# ---------------------------------------------------------------------------

def _head_tile(ref, h, hpb, token_major, block=None):
    """Head-slot h's [rows, width] tile of a q/k/v/dO block.

    Head-major, the block is [hpb, rows, d] and the tile its h-th
    entry.  Token-major, the block is [1, rows, 128]: the hpb heads of
    one lane block side by side, and the tile is ALL 128 lanes with the
    other head's zeroed.  Nothing is sliced or shuffled across lanes: a
    contraction over the 128 lanes adds exact zeros to head h's 64
    products in float32, and a product that is 128 lanes wide holds
    head h's result in its lanes and exact zeros in the others, so the
    tiles of a block's heads ADD to the block.  On a 128 x 128 array a
    64-deep contraction and a 64-wide output cost a full pass already
    (PERF.md section 5).

    block: the lane block to cut the tile from in place of ref's, for
    the K and V of grouped heads (`_kv_blocks`)."""
    if not token_major:
        return ref[h]
    x = ref[0] if block is None else block
    if hpb == 1:
        return x
    lane = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    d = x.shape[1] // hpb
    mine = (lane >= h * d) & (lane < (h + 1) * d)
    return jnp.where(mine, x, jnp.zeros_like(x))


def _kv_slot(hpb, token_major, group, q_blocks):
    """Which head slot of ITS lane block holds the KV head that this
    grid step's query heads read, or None where a head's K and V lie
    where its Q does: equal head counts, and every layout with one head
    a step, where the BlockSpec index map alone picks the KV head
    (`_Tiles.spec`, kv=True).  Token-major at two heads a lane block
    (D = 64) and an even group, the block's query heads read the SAME
    KV head, (g % q_blocks) * hpb // group.  Read at the kernel's top:
    a grid index is not to be had inside a `pl.when` body."""
    if group == 1 or not token_major or hpb == 1:
        return None
    return ((pl.program_id(0) % q_blocks) * hpb // group) % hpb


def _kv_blocks(k_ref, v_ref, hpb, slot):
    """The step's K and V lane blocks with the KV head of `_kv_slot`
    in every head slot of the lanes: one lane rotation and a select a
    step, so that `_head_tile` cuts head slot h's K and V out of the
    lanes its Q lies in.  K and V are read where they are: no [.., H,
    D] copy of them exists in HBM.  (None, None) without a slot."""
    if slot is None:
        return None, None
    k, v = k_ref[0], v_ref[0]
    d = k.shape[1] // hpb
    here = lax.broadcasted_iota(jnp.int32, k.shape, 1) // d == slot

    def both(x):
        # Mosaic rotates 32-bit lanes only; the round trip is exact
        turned = pltpu.roll(x.astype(jnp.float32), d, 1).astype(x.dtype)
        return jnp.where(here, x, turned)

    return both(k), both(v)


# ---------------------------------------------------------------------------
# pallas forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                l_ref, *, scale, causal, block_q, block_k, kv_len,
                q_off, hpb, token_major=False, group=1, q_blocks=1,
                window=0, nk=None):
    """window, nk: a windowed call's grid walks a q block's BAND of the
    nk kv blocks and not all of them (`_Diagonal.band_steps`): step j
    is kv block first_ki(qi) + j."""
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    kv_slot = _kv_slot(hpb, token_major, group, q_blocks)
    diagonal = _Diagonal(block_q, block_k, q_off, window)
    ki, in_grid = diagonal.band_block(qi, step, nk, "kv")

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # KV blocks strictly above the diagonal of this Q block (and, with
    # a window, strictly below its band) are skipped
    run = diagonal.run(qi, ki) if causal else True
    if window:
        run &= in_grid
    # interior blocks (every position valid, fully below the causal
    # diagonal) skip mask construction entirely: the two [bq, bk]
    # iotas + compares + selects are VPU work on par with the exp
    # itself at head_dim 64, so specializing nearly halves VPU cost
    # on the dominant block population
    interior = (ki + 1) * block_k <= kv_len
    if causal:
        interior &= diagonal.inside(qi, ki)

    def _accumulate(masked):
        # the mask depends only on (qi, ki) geometry — one per step,
        # shared by the step's heads
        mask = None
        if masked:
            kpos = ki * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = kpos < kv_len          # padded keys contribute nothing
            if causal:
                qpos = q_off + qi * block_q + lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                mask = mask & (qpos >= kpos)
                if window:
                    mask = mask & (qpos - window < kpos)
        # the heads are independent dependency chains — the scheduler
        # interleaves their MXU and VPU work within the step
        kb, vb = _kv_blocks(k_ref, v_ref, hpb, kv_slot)
        for h in range(hpb):
            q = _head_tile(q_ref, h, hpb, token_major)    # [bq, d]
            k = _head_tile(k_ref, h, hpb, token_major, kb)  # [bk, d]
            v = _head_tile(v_ref, h, hpb, token_major, vb)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[h, :, 0]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_next[:, None])
            if masked:
                # explicit zero for masked entries: a fully-masked row
                # would otherwise see exp(-1e30 - (-1e30)) = 1 and
                # accumulate garbage
                p = jnp.where(mask, p, 0.0)
            alpha = jnp.exp(m_prev - m_next)
            l_next = l_ref[h, :, 0] * alpha + jnp.sum(p, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_next[:, None],
                                        m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_next[:, None],
                                        l_ref.shape[1:])

    @pl.when(run & interior)
    def _compute_fast():
        _accumulate(masked=False)

    @pl.when(run & ~interior)
    def _compute_edge():
        _accumulate(masked=True)

    @pl.when(step == steps - 1)
    def _finalize():
        for h in range(hpb):
            l = l_ref[h, :, 0]
            l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 out
            out = acc_ref[h] / l[:, None]
            if not token_major:
                o_ref[h, ...] = out.astype(o_ref.dtype)
            elif h == 0:
                block = out
            else:
                # each head's acc is zero off its lanes (_head_tile)
                block = block + out
            # log-sum-exp per row, consumed by the backward kernels; for
            # a fully-masked row m=-inf and l was clamped to 1 ->
            # lse=-inf, whose exp(s - lse) entries are all masked off in
            # backward.
            rows = m_ref[h, :, 0] + jnp.log(l)
            # lane-replicated ([bq, 128]): Mosaic requires the last two
            # block dims to be (8k, 128m) or full — a [1, bq] block is
            # rejected by the TPU lowering (caught on the first
            # real-chip bench run; interpret-mode tests never enforce
            # tiling)
            lse_ref[h, ...] = jnp.broadcast_to(rows[:, None],
                                               lse_ref.shape[1:])
        if token_major:
            o_ref[0] = block.astype(o_ref.dtype)


def _pad_axis(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _dims(q, k, v, heads):
    """(b, h, tq, tk, d, dv) of head-major [B, H, T, D] operands, or,
    with a head count, of token-major [B, T, H*D] ones.  h is the
    QUERY heads' count; K and V may have fewer (`_kv_heads`)."""
    if heads is None:
        b, h, tq, d = q.shape
        return b, h, tq, k.shape[2], d, v.shape[3]
    b, tq, width = q.shape
    d = width // heads
    return b, heads, tq, k.shape[1], d, v.shape[2] // (k.shape[2] // d)


def _kv_heads(q, k, heads):
    """The KV heads' count, read off K: its head axis, or token-major
    its width over the head size.  The query heads' count is a whole
    multiple of it (grouped-query attention: query head h reads KV
    head h // group)."""
    h = q.shape[1] if heads is None else heads
    hkv = k.shape[1] if heads is None else k.shape[2] // (q.shape[2] // h)
    if hkv < 1 or h % hkv:
        raise ValueError(
            "flash_attention: %d query heads are no whole multiple of "
            "the %d KV heads" % (h, hkv))
    return hkv


def _block_geometry(q, k, v, block_q, block_k, heads=None):
    """(dims, bq, bk, hpb) of the kernels' grid over these operands:
    their `_dims`, blocks clamped to the lengths, and the heads a grid
    step takes: on the token-major layout (`heads` given) the heads of
    one 128-lane block, head-major one (so that with grouped KV heads
    the index map alone picks the step's KV head)."""
    dims = _, _, tq, tk, d, _ = _dims(q, k, v, heads)
    bq = min(block_q, max(tq, 8))
    bk = min(block_k, max(tk, 8))
    hpb = 1 if heads is None else _MIN_LANES // d
    return dims, bq, bk, hpb


class _Tiles:
    """Where the kernels' per-head [rows, width] tiles of q, k, v, out
    and their gradients lie in HBM: the ONE thing, with `_head_tile`,
    that differs between the two layouts.

    Head-major [B, H, T, D]: the kernels see [B*H, T, D] (a free
    reshape) and grid step g takes the block (hpb, rows, D) at g, the
    tiles of heads g*hpb ... of the flattened B*H axis.  Token-major
    [B, T, H*D], as a projection leaves it: the kernels see it as it
    is, and step g takes the block (1, rows, 128) at batch g // n,
    lane block g % n of the n = H*D/128 a batch has, which holds the
    same hpb = 128/D heads g*hpb ... of the flattened axis.  The row
    statistics are [B*H, T, 128] on both.

    Grouped KV heads (`group` query heads read one KV head: K and V
    have H / group heads): the grid stays the QUERY heads', and a
    step's K and V block is its heads' KV head's, found by the index
    map (`spec(.., kv=True)`): head-major, one head a step, the row
    b * H/group + h // group of the flattened [B*H/group, T, D];
    token-major the lane block that holds KV head g*hpb // group.  So
    K and V are read where they lie and no copy of them at H heads is
    made.  dk and dv leave the kernels a query head (`shape`, the
    plain `spec`)."""

    def __init__(self, dims, hpb, token_major, group=1):
        self.b, self.h = dims[:2]
        self.hpb, self.token_major, self.group = hpb, token_major, group

    def operand(self, x, block_rows):
        """x as the kernels take it, rows padded to whole blocks."""
        if not self.token_major:
            x = x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
        return _pad_axis(x, 1, block_rows)

    def spec(self, rows, width, row_block, kv=False):
        """BlockSpec of the (rows, width) tiles; row_block(i, j) is the
        row block at the grid's two inner indices.  kv: of K or V."""
        h, hpb = self.h, self.hpb
        group = self.group if kv else 1
        if not self.token_major:
            if group == 1:
                return pl.BlockSpec(
                    (hpb, rows, width),
                    lambda g, i, j: (g, row_block(i, j), 0))
            return pl.BlockSpec(
                (1, rows, width),
                lambda g, i, j: (g // h * (h // group) + g % h // group,
                                 row_block(i, j), 0))
        n = h // hpb
        return pl.BlockSpec(
            (1, rows, hpb * width),
            lambda g, i, j: (g // n, row_block(i, j),
                             g % n * hpb // group // hpb))

    def shape(self, rows, width):
        if not self.token_major:
            return (self.b * self.h, rows, width)
        return (self.b, rows, self.h * width)

    def acc(self, rows, width, per_head=False):
        """float32 VMEM accumulator of a step's (rows, width) tiles: one
        a head slot, or, token-major, ONE [rows, 128] for the lane
        block, into which its heads' products add (`_head_tile`);
        per_head where each head needs its own all the same (the
        forward's, rescaled by the head's alpha)."""
        if not self.token_major:
            return pltpu.VMEM((self.hpb, rows, width), jnp.float32)
        return pltpu.VMEM((self.hpb if per_head else 1, rows, _MIN_LANES),
                          jnp.float32)

    def result(self, x, rows):
        """A kernel output without its row padding, in the layout the
        operands came in."""
        x = x[:, :rows]
        if not self.token_major:
            x = x.reshape(self.b, self.h, *x.shape[1:])
        return x


def _first(i, j):
    """Row-block pickers for `_Tiles.spec` and the row statistics'
    spec: which of a grid's two inner indices walks a tile's rows."""
    return i


def _second(i, j):
    return j


def _second_held(diagonal, nq, nk, walks):
    """`_second` for a causal call: the operand that walks the grid's
    INNER axis holds the block of the nearest step that runs over the
    steps that do not, so Pallas's pipeline sees an unchanged block
    index there and issues no copy: a step that computes nothing
    fetches nothing.  At a step that runs it is `_second` exactly.

    walks "kv": K and V on a (g, qi, ki) grid (the forward, the dq
    sweep), whose dead steps END a q block's sweep: the last kv block
    it runs stays.  walks "q": q, dO and the two row statistics on the
    (g, ki, qi) grid of the dk/dv sweep, whose dead steps START a kv
    block's sweep: the first q block it runs is there from step 0
    (and so fetched early, behind the last step of the sweep before).
    diagonal None (not causal): `_second` itself.

    With a window the inner axis is a BAND's steps and not the blocks
    (`_Diagonal.band_steps`): step j is the outer block's first inner
    block + j, and the steps past its last (a band shorter than the
    longest: at the sequence's ends, or where the edges fall inside
    fewer blocks) hold that last one."""
    if diagonal is None:
        return _second
    if diagonal.window:
        def held(outer, j):
            first, last = diagonal.band(
                outer, nk if walks == "kv" else nq, walks)
            return jnp.minimum(first + j, last)
        return held
    if walks == "kv":
        return lambda qi, ki: jnp.minimum(ki, diagonal.last_ki(qi, nk))
    return lambda ki, qi: jnp.maximum(qi, diagonal.first_qi(ki, nq))


def _lanes(n):
    """A minor dim occupies whole 128-lane tiles."""
    return -(-n // _MIN_LANES) * _MIN_LANES


# What Mosaic scopes a kernel to on a v5e unless it asks for more, and
# the most the one-sweep backward asks for of the core's 128 MiB.
_MOSAIC_SCOPED_VMEM = 16 << 20
_BWD_FUSED_VMEM_MAX = 96 << 20


def _fwd_vmem_bytes(hpb, bq, bk, d, dv, itemsize):
    """VMEM the forward needs, bytes, from above, reckoned as
    `_bwd_fused_vmem_bytes` is.  Two heads a step at 1,024-row blocks
    pass what Mosaic scopes a kernel to by default (their score tiles
    alone are 24 MiB), so the forward asks."""
    d, dv = _lanes(d), _lanes(dv)
    # q, k, v and out blocks, double-buffered
    tiles = 2 * itemsize * (bq * (d + dv) + bk * (d + dv))
    # the lse block, double-buffered; m, l and the float32 acc
    stats = 2 * 4 * bq * _MIN_LANES \
        + 2 * 4 * bq * _MIN_LANES + 4 * bq * dv
    # S, P and P's cast, as far as Mosaic keeps them whole
    temps = 3 * 4 * bq * bk
    return tiles + hpb * (stats + temps)


# jitted so that a step traces each kernel once per signature, not once
# per layer: jax keeps no cache of kernel-body traces (the partial it is
# handed is new every call), and of a six-layer Transformer step's first
# trace most was the same three kernel bodies traced 24 times.  XLA
# inlines the calls: the compiled step is the same module (same opcode
# counts and code size, compiled for a described v5e; PERF.md, PR 24).
@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "block_q", "block_k", "interpret", "heads",
    "window"))
def _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                      interpret=False, heads=None, window=0):
    """q/k: [B, H, T, D], v: [B, H, Tk, Dv] (Dv = D everywhere but in
    latent attention, whose q.k size is 192 and v size 128) ->
    ([B, H, Tq, Dv], lse [B*H, Tq_padded]).  With `heads`, the three
    and the output are token-major [B, T, H*D] (`_Tiles`; the entries
    send only what `_flash_layout` passed).  k and v may have fewer
    heads than q, H / group (`_kv_heads`).  window (causal only; 0:
    none): the grid's kv axis is the band's steps (`_Diagonal`) and
    the call is named pt_flash_win_fwd."""
    token_major = heads is not None
    dims, bq, bk, hpb = _block_geometry(q, k, v, block_q, block_k, heads)
    b, h, tq, tk, d, dv = dims
    group = h // _kv_heads(q, k, heads)
    tiles = _Tiles(dims, hpb, token_major, group)
    qp, kp, vp = tiles.operand(q, bq), tiles.operand(k, bk), \
        tiles.operand(v, bk)
    tq_p, tk_p = qp.shape[1], kp.shape[1]
    nq, nk = tq_p // bq, tk_p // bk
    diagonal = _Diagonal(bq, bk, tk - tq, window) if causal else None
    grid = (b * h // hpb, nq,
            diagonal.band_steps(nq, nk, "kv") if window else nk)
    kv_rows = _second_held(diagonal, nq, nk, walks="kv")

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        kv_len=tk, q_off=tk - tq if causal else 0, hpb=hpb,
        token_major=token_major, group=group, q_blocks=h // hpb,
        window=window, nk=nk)
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(
                _MOSAIC_SCOPED_VMEM,
                _fwd_vmem_bytes(hpb, bq, bk, d, dv, q.dtype.itemsize)))
    out, lse = pl.pallas_call(
        kernel,
        name="pt_flash_win_fwd" if window else "pt_flash_fwd",
        grid=grid,
        in_specs=[
            tiles.spec(bq, d, _first),
            tiles.spec(bk, d, kv_rows, kv=True),
            tiles.spec(bk, dv, kv_rows, kv=True),
        ],
        out_specs=[
            tiles.spec(bq, dv, _first),
            pl.BlockSpec((hpb, bq, _MIN_LANES),
                         lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(tiles.shape(tq_p, dv), q.dtype),
            jax.ShapeDtypeStruct((b * h, tq_p, _MIN_LANES), jnp.float32),
        ],
        scratch_shapes=[
            tiles.acc(bq, dv, per_head=True),
            pltpu.VMEM((hpb, bq, _MIN_LANES), jnp.float32),
            pltpu.VMEM((hpb, bq, _MIN_LANES), jnp.float32),
        ],
        interpret=interpret,
        **params,
    )(qp, kp, vp)
    # callers see the documented [B*H, Tq_padded] lse: strip the lanes
    return (tiles.result(out, tq), lse[:, :, 0])


# ---------------------------------------------------------------------------
# pallas backward kernels
# ---------------------------------------------------------------------------
# Recompute P blockwise from (q, k, lse); with delta = rowsum(dO * O):
#   dV = P^T dO
#   dS = P * (dO V^T - delta) * scale
#   dQ = dS K ;  dK = dS^T Q
# The [Tq, Tk] matrices never leave VMEM.  One sweep (_bwd_dkv_kernel
# with_dq) forms P and dS once a block pair for all three; the dq sweep
# beside a dk/dv sweep without dq, which form them once each, stay for
# the lengths whose dq does not fit VMEM (_flash_bwd).

def _bwd_interior(*, causal, block_q, block_k, kv_len, q_len, q_off,
                  qi, ki, window=0):
    """Traced predicate: this (qi, ki) block needs no mask — all kv
    and q positions valid, fully below the causal diagonal (and, with
    a window, fully inside the band)."""
    interior = ((ki + 1) * block_k <= kv_len) \
        & ((qi + 1) * block_q <= q_len)
    if causal:
        interior &= _Diagonal(block_q, block_k, q_off,
                              window).inside(qi, ki)
    return interior


def _bwd_p_ds_block(q, k, v, do, lse, delta, *, scale, causal,
                    block_q, block_k, kv_len, q_len, q_off, qi, ki,
                    masked=True, window=0):
    """Recompute the probability block P [bq, bk] (forward's mask plus
    a valid-q-row mask — padded q rows must contribute nothing to
    dk/dv) and the score gradient dS = P * (dO V^T - delta) * scale.
    With masked=False (interior blocks, see _bwd_interior) the mask
    iotas/compares/selects are skipped entirely."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    if masked:
        kpos = ki * block_k + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        qrow = qi * block_q + lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        mask = (kpos < kv_len) & (qrow < q_len)
        if causal:
            mask = mask & ((q_off + qrow) >= kpos)
            if window:
                mask = mask & ((q_off + qrow - window) < kpos)
        # masked entries (incl. fully-masked rows where lse=-1e30) -> 0
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)
    else:
        p = jnp.exp(s - lse[:, None])
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    ds = p * (dp - delta[:, None]) * scale
    return p, ds


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, acc_ref, *, scale, causal, block_q,
                   block_k, kv_len, q_len, q_off, hpb,
                   token_major=False, group=1, q_blocks=1, window=0,
                   nq=None, nk=None):
    qi = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    kv_slot = _kv_slot(hpb, token_major, group, q_blocks)
    diagonal = _Diagonal(block_q, block_k, q_off, window)
    ki, in_grid = diagonal.band_block(qi, step, nk, "kv")  # _fwd_kernel

    @pl.when(step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    run = diagonal.run(qi, ki) if causal else True
    if window:
        run &= in_grid
    geometry = dict(causal=causal, block_q=block_q, block_k=block_k,
                    kv_len=kv_len, q_len=q_len, q_off=q_off, qi=qi,
                    ki=ki, window=window)
    interior = _bwd_interior(**geometry)

    def _accumulate(masked):
        kb, vb = _kv_blocks(k_ref, v_ref, hpb, kv_slot)
        for h in range(hpb):
            q, k, v, do = (_head_tile(r, h, hpb, token_major, blk)
                           for r, blk in ((q_ref, None), (k_ref, kb),
                                          (v_ref, vb), (do_ref, None)))
            do = do.astype(jnp.float32)
            _, ds = _bwd_p_ds_block(
                q, k, v, do,
                lse_ref[h, :, 0], delta_ref[h, :, 0],
                scale=scale, masked=masked, **geometry)
            acc_ref[0 if token_major else h] += lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(run & interior)
    def _compute_fast():
        _accumulate(masked=False)

    @pl.when(run & ~interior)
    def _compute_edge():
        _accumulate(masked=True)

    @pl.when(step == steps - 1)
    def _finalize():
        for a in range(acc_ref.shape[0]):
            dq_ref[a, ...] = acc_ref[a].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    *refs, scale, causal, block_q, block_k, kv_len,
                    q_len, q_off, hpb, with_dq,
                    token_major=False, group=1, q_blocks=1, window=0,
                    nq=None, nk=None):
    """The dk/dv sweep: kv blocks outer, q blocks inner, dk_acc/dv_acc
    carried across the q sweep.  Head-major, every head slot has its
    accumulators; token-major, the heads of a lane block share one
    (`_head_tile`: their products are zero off their own lanes).

    Grouped KV heads (group > 1): K and V are read in place by the
    query head's KV head (`_Tiles.spec`, `_kv_blocks`), and dk and dv
    are written a QUERY head, each the head's own part; the entry sums
    a group's parts (`_sum_groups`).

    with_dq, it is the whole backward: P and dS, formed once a block
    pair, feed all three products.  dq_acc holds the head's whole dq
    [hpb, Tq_p, d] in float32 and carries across the OUTER kv axis, so
    each q block's rows add their kv blocks in ascending order (the sum
    `_bwd_dq_kernel` forms, bit for bit) and dq reaches HBM once a
    head.

    window: the inner axis walks a kv block's BAND of the nq q blocks
    (`_Diagonal.band_steps`), step j the q block first_qi(ki) + j.  A
    q block is then no longer met at every kv block, so with_dq the
    whole dq_acc is zeroed at a head's first step and written at its
    last, not a q block at the first and last kv block."""
    ki = pl.program_id(1)
    step = pl.program_id(2)
    steps = pl.num_programs(2)
    kv_slot = _kv_slot(hpb, token_major, group, q_blocks)
    diagonal = _Diagonal(block_q, block_k, q_off, window)
    qi, in_grid = diagonal.band_block(ki, step, nq, "q")
    if with_dq:
        dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc = refs
        last_ki = pl.num_programs(1) - 1

        def q_rows(i):
            return pl.ds(pl.multiple_of(i * block_q, block_q), block_q)

        # a band step past the grid runs nothing: any block's rows do
        rows = q_rows(jnp.minimum(qi, nq - 1) if window else qi)
    else:
        dk_ref, dv_ref, dk_acc, dv_acc = refs

    @pl.when(step == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    if with_dq and not window:
        @pl.when(ki == 0)
        def _init_dq():
            for a in range(dq_acc.shape[0]):
                dq_acc[a, rows, :] = jnp.zeros(
                    (block_q, dq_acc.shape[2]), dq_acc.dtype)
    elif with_dq:
        @pl.when((ki == 0) & (step == 0))
        def _init_dq_whole():
            def zero(i, carry):
                for a in range(dq_acc.shape[0]):
                    dq_acc[a, q_rows(i), :] = jnp.zeros(
                        (block_q, dq_acc.shape[2]), dq_acc.dtype)
                return carry
            lax.fori_loop(0, nq, zero, 0)

    # q blocks entirely above the diagonal (and, with a window, below
    # the band) contribute nothing
    run = diagonal.run(qi, ki) if causal else True
    if window:
        run &= in_grid
    geometry = dict(causal=causal, block_q=block_q, block_k=block_k,
                    kv_len=kv_len, q_len=q_len, q_off=q_off, qi=qi,
                    ki=ki, window=window)
    interior = _bwd_interior(**geometry)

    def _accumulate(masked):
        kb, vb = _kv_blocks(k_ref, v_ref, hpb, kv_slot)
        for h in range(hpb):
            q, k, v, do = (_head_tile(r, h, hpb, token_major, blk)
                           for r, blk in ((q_ref, None), (k_ref, kb),
                                          (v_ref, vb), (do_ref, None)))
            do = do.astype(jnp.float32)
            a = 0 if token_major else h
            p, ds = _bwd_p_ds_block(
                q, k, v, do,
                lse_ref[h, :, 0], delta_ref[h, :, 0],
                scale=scale, masked=masked, **geometry)
            dv_acc[a] += lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_acc[a] += lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if with_dq:
                dq_acc[a, rows, :] += lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    @pl.when(run & interior)
    def _compute_fast():
        _accumulate(masked=False)

    @pl.when(run & ~interior)
    def _compute_edge():
        _accumulate(masked=True)

    @pl.when(step == steps - 1)
    def _finalize():
        for a in range(dk_acc.shape[0]):
            dk_ref[a, ...] = dk_acc[a].astype(dk_ref.dtype)
            dv_ref[a, ...] = dv_acc[a].astype(dv_ref.dtype)

    if with_dq and not window:
        @pl.when(ki == last_ki)
        def _finalize_dq():
            for a in range(dq_acc.shape[0]):
                dq_ref[a, rows, :] = dq_acc[a, rows, :].astype(
                    dq_ref.dtype)
    elif with_dq:
        @pl.when((ki == last_ki) & (step == steps - 1))
        def _finalize_dq_whole():
            def write(i, carry):
                for a in range(dq_acc.shape[0]):
                    dq_ref[a, q_rows(i), :] = dq_acc[
                        a, q_rows(i), :].astype(dq_ref.dtype)
                return carry
            lax.fori_loop(0, nq, write, 0)


def _bwd_fused_vmem_bytes(hpb, tq_p, bq, bk, d, dv, itemsize):
    """VMEM the one-sweep backward needs, bytes, from above: what the
    chip's compiler asked for, compiled for a described v5e over the
    cells' shapes, head sizes 64 to 192 and both dtypes, was 0.35 to
    0.9 of this (PERF.md, PR 29).  The hpb heads of a lane block
    (token-major at head size 64) share its tiles and accumulators
    (the resident dq is [Tq_p, 128] for the pair: what one head's is,
    padded to whole lanes); the row statistics and the score tiles
    stay a head's."""
    d, dv = _lanes(d), _lanes(dv)
    # q, k, v and dO, double-buffered
    tiles = 2 * itemsize * (bq + bk) * (d + dv)
    # the two row statistics, double-buffered
    stats = 2 * 2 * 4 * bq * _MIN_LANES
    # dk/dv: the double-buffered output blocks and their accumulators
    dkv = (2 * itemsize + 4) * bk * (d + dv)
    # dq: the float32 accumulator and the output block
    dq = (2 * itemsize + 4) * tq_p * d
    # S/P, dP, dS and their casts, as far as Mosaic keeps them whole
    temps = 4 * 4 * bq * bk
    return tiles + dkv + dq + hpb * (stats + temps)


def _count_causal_fetch(causal, tq, tk, bq, bk, window=0):
    """paddle_tpu_kernel_impl_total{kernel="flash_attention_causal_fetch"}:
    once a causal entry to the kernels, forward or backward, `held`
    where its grid has a step above the diagonal (whose blocks the
    index maps hold, `_second_held`), `all_live` where it has none (one
    block a sequence).  An entry that is not causal adds no series.

    {kernel="flash_attention_window"} beside it, once an entry with a
    window: which grid the call got, `band` (the inner axis walks a
    band's steps, `_Diagonal.band_steps`) or `all_live` (one block a
    sequence: the band is the grid).  An entry without a window adds
    no series."""
    if causal:
        held = _Diagonal(bq, bk, tk - tq, window).dead_steps(-(-tk // bk))
        _count_impl("flash_attention_causal_fetch",
                    "held" if held else "all_live")
    if window:
        _count_impl("flash_attention_window",
                    "band" if -(-tq // bq) * -(-tk // bk) > 1
                    else "all_live")


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret,
               heads, window=0):
    """(out, lse) by `_flash_fwd_pallas`, counted here, outside the
    jit, as `_flash_bwd` counts."""
    (_, _, tq, tk, _, _), bq, bk, _ = _block_geometry(
        q, k, v, block_q, block_k, heads)
    _count_causal_fetch(causal, tq, tk, bq, bk, window)
    return _flash_fwd_pallas(q, k, v, causal, scale, block_q, block_k,
                             interpret=interpret, heads=heads,
                             window=window)


def _flash_bwd(q, k, v, o, lse, g, *, dlse=None, **call):
    """(dq, dk, dv) by `_flash_bwd_pallas`.  The shape alone picks the
    sweep: one kernel that writes all three where a head's dq fits the
    VMEM a kernel may ask for; past that two, dq streamed by q block.
    Counted here, outside the jit, so that a step of six layers reads
    six.  **call: the static arguments `_call_args` resolved."""
    heads = call.get("heads")
    (_, _, tq, tk, d, dv), bq, bk, hpb = _block_geometry(
        q, k, v, call["block_q"], call["block_k"], heads)
    vmem = _bwd_fused_vmem_bytes(
        hpb, -(-tq // bq) * bq, bq, bk, d, dv, q.dtype.itemsize)
    fused = vmem <= _BWD_FUSED_VMEM_MAX
    _count_impl("flash_attention_bwd", "fused" if fused else "two_sweep")
    _count_causal_fetch(call["causal"], tq, tk, bq, bk,
                        call.get("window", 0))
    return _flash_bwd_pallas(
        q, k, v, o, lse, g, dlse=dlse, **call,
        one_sweep_vmem=max(vmem, _MOSAIC_SCOPED_VMEM) if fused else None)


@functools.partial(jax.jit, static_argnames=(    # see _flash_fwd_pallas
    "causal", "scale", "block_q", "block_k", "interpret", "heads",
    "window", "one_sweep_vmem"))
def _flash_bwd_pallas(q, k, v, o, lse, g, causal, scale, block_q,
                      block_k, interpret=False, dlse=None, heads=None,
                      window=0, *, one_sweep_vmem):
    """q/k: [B, H, T, D], v, o and g = dO: [.., Dv] (with `heads`, all
    token-major [B, T, H*D], and so the gradients: `_Tiles`); lse:
    [B*H, Tq] or q-block padded, as the forward kernel returns it.
    one_sweep_vmem:
    the VMEM to ask for, bytes, for the one sweep (`_bwd_dkv_kernel`
    with_dq), or None for the dq and the dk/dv sweep; `_flash_bwd`
    picks.

    dlse ([B*H, Tq] or None): cotangent of the lse output when the
    caller consumes it (ring attention's cross-chunk merge).  Since
    d lse_r / d s_rc = p_rc, it folds into the delta term:
    dS = P*(dO V^T - delta) + P*dlse = P*(dO V^T - (delta - dlse)).

    window: `_flash_fwd_pallas`; the dq grid's kv axis and the dk/dv
    grid's q axis are the band's steps, and the calls are named
    pt_flash_win_bwd_dq and pt_flash_win_bwd_dkv.
    """
    token_major = heads is not None
    dims, bq, bk, hpb = _block_geometry(q, k, v, block_q, block_k, heads)
    b, h, tq, tk, d, dv = dims
    group = h // _kv_heads(q, k, heads)
    tiles = _Tiles(dims, hpb, token_major, group)
    qp, kp, vp, gp = tiles.operand(q, bq), tiles.operand(k, bk), \
        tiles.operand(v, bk), tiles.operand(g, bq)
    tq_p, tk_p = qp.shape[1], kp.shape[1]
    # rows past tq are masked in the kernels: what they hold is not read
    lse = _pad_axis(lse, 1, bq)
    # delta = rowsum(dO * O): cheap elementwise+reduce, done in XLA;
    # an lse cotangent subtracts from it (see docstring)
    delta_full = g.astype(jnp.float32) * o.astype(jnp.float32)
    if token_major:
        # a head's sum is over ITS lanes of a row.  As a reduce XLA
        # first copies both operands to a rows-minor layout (splitting
        # the 128-lane tiles is a relayout; six float32 copies of 67 MB
        # in the `_s512` step, compiled for a described v5e: PERF.md,
        # PR 31); as a product with the heads' 0/1 lane indicator it
        # is one fusion that reads dO and O once and writes [B, H, T].
        # The same float32 sum: a product of two bfloat16 has 16
        # significant bits, which the three bfloat16 passes of HIGH
        # hold exactly beside an indicator that is bfloat16 itself;
        # wider operands take the six of HIGHEST.
        heads_of = lax.broadcasted_iota(jnp.int32, (h * dv, h), 0) // dv \
            == lax.broadcasted_iota(jnp.int32, (h * dv, h), 1)
        delta_full = lax.dot_general(
            delta_full, heads_of.astype(jnp.float32),
            (((2,), (0,)), ((), ())),
            precision=(lax.Precision.HIGH if g.dtype == jnp.bfloat16
                       else lax.Precision.HIGHEST),
            preferred_element_type=jnp.float32).transpose(0, 2, 1)
    else:
        delta_full = delta_full.sum(-1)
    delta_full = delta_full.reshape(b * h, tq)
    if dlse is not None:
        # the lse output (and so its cotangent) is q-block padded;
        # only the first tq rows are real
        delta_full = delta_full - dlse.reshape(b * h, -1)[:, :tq] \
            .astype(jnp.float32)
    delta = _pad_axis(delta_full, 1, bq)
    # lane-replicate the per-row vectors: [B*H, Tq_p] ->
    # [B*H, Tq_p, 128] (2-D [1, bq] blocks violate Mosaic's
    # last-two-dims tiling rule; same layout the forward kernel
    # emits for lse)
    lse3 = jnp.broadcast_to(lse[:, :, None], (b * h, tq_p, _MIN_LANES))
    delta3 = jnp.broadcast_to(delta[:, :, None],
                              (b * h, tq_p, _MIN_LANES))
    q_off = tk - tq if causal else 0
    common = dict(scale=scale, causal=causal, block_q=bq, block_k=bk,
                  kv_len=tk, q_len=tq, q_off=q_off, hpb=hpb,
                  token_major=token_major, group=group,
                  q_blocks=h // hpb, window=window,
                  nq=tq_p // bq, nk=tk_p // bk)
    operands = (qp, kp, vp, gp, lse3, delta3)
    out_shape = [
        jax.ShapeDtypeStruct(tiles.shape(tq_p, d), q.dtype),
        jax.ShapeDtypeStruct(tiles.shape(tk_p, d), k.dtype),
        jax.ShapeDtypeStruct(tiles.shape(tk_p, dv), v.dtype),
    ]

    def specs(q_rows, k_rows):
        """in_specs of (q, k, v, dO, lse, delta) for a grid order:
        which of the two inner grid indices is the q block's."""
        stat = pl.BlockSpec((hpb, bq, _MIN_LANES),
                            lambda bh, i, j: (bh, q_rows(i, j), 0))
        return [tiles.spec(bq, d, q_rows),
                tiles.spec(bk, d, k_rows, kv=True),
                tiles.spec(bk, dv, k_rows, kv=True),
                tiles.spec(bq, dv, q_rows), stat, stat]

    def params(outer="parallel", vmem_limit_bytes=None):
        if interpret:
            return {}
        return {"compiler_params": pltpu.CompilerParams(
            dimension_semantics=("parallel", outer, "arbitrary"),
            vmem_limit_bytes=vmem_limit_bytes)}

    nq, nk = tq_p // bq, tk_p // bk
    diagonal = _Diagonal(bq, bk, q_off, window) if causal else None
    names = ("pt_flash_win_bwd_dq", "pt_flash_win_bwd_dkv") if window \
        else ("pt_flash_bwd_dq", "pt_flash_bwd_dkv")
    # kv blocks outer, q blocks inner: the dk/dv accumulators carry
    # across the q sweep
    kv_specs = specs(q_rows=_second_held(diagonal, nq, nk, walks="q"),
                     k_rows=_first)
    # dk and dv: a query head's, whatever the KV heads' count
    dkv_specs = [tiles.spec(bk, d, _first), tiles.spec(bk, dv, _first)]
    kv_grid = (b * h // hpb, nk,
               diagonal.band_steps(nq, nk, "q") if window else nq)
    kv_scratch = [tiles.acc(bk, d), tiles.acc(bk, dv)]
    if one_sweep_vmem is not None:
        dq, dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, with_dq=True, **common),
            name=names[1],
            grid=kv_grid,
            in_specs=kv_specs,
            # dq: the head's whole [Tq_p, d], resident over both sweeps
            out_specs=[tiles.spec(tq_p, d, lambda j, i: 0)] + dkv_specs,
            out_shape=out_shape,
            scratch_shapes=[tiles.acc(tq_p, d)] + kv_scratch,
            interpret=interpret,
            **params("arbitrary", one_sweep_vmem),
        )(*operands)
    else:
        q_specs = specs(q_rows=_first, k_rows=_second_held(
            diagonal, nq, nk, walks="kv"))
        dq = pl.pallas_call(
            functools.partial(_bwd_dq_kernel, **common),
            name=names[0],
            grid=(b * h // hpb, nq,
                  diagonal.band_steps(nq, nk, "kv") if window else nk),
            in_specs=q_specs,
            out_specs=q_specs[0],
            out_shape=out_shape[0],
            scratch_shapes=[tiles.acc(bq, d)],
            interpret=interpret,
            **params(),
        )(*operands)
        dk, dv_ = pl.pallas_call(
            functools.partial(_bwd_dkv_kernel, with_dq=False, **common),
            name=names[1],
            grid=kv_grid,
            in_specs=kv_specs,
            out_specs=dkv_specs,
            out_shape=out_shape[1:],
            scratch_shapes=kv_scratch,
            interpret=interpret,
            **params(),
        )(*operands)
    return (tiles.result(dq, tq),
            _sum_groups(tiles.result(dk, tk), group,
                        d if token_major else None),
            _sum_groups(tiles.result(dv_, tk), group,
                        dv if token_major else None))


def _sum_groups(x, group, width):
    """dk or dv a QUERY head, [B, H, T, D], or with the head size
    `width` token-major [B, T, H*D] -> a KV head: the float32 sum over
    the `group` query heads that read it, in x's dtype."""
    if group == 1:
        return x
    if width is None:
        b, h, t, d = x.shape
        parts = x.reshape(b, h // group, group, t, d)
        return parts.astype(jnp.float32).sum(2).astype(x.dtype)
    b, t, total = x.shape
    parts = x.reshape(b, t, total // (group * width), group, width)
    return parts.astype(jnp.float32).sum(3).astype(x.dtype).reshape(
        b, t, total // group)


# ---------------------------------------------------------------------------
# differentiable entries: ONE custom_vjp over the forward/backward kernels
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret,
               heads=None, window=0):
    """(out, lse): lse is the mergeable summary ring attention needs and
    the residual the IR grad op reads.  heads, window:
    `_flash_fwd_pallas`."""
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                      interpret, heads, window)


def _flash_lse_fwd(q, k, v, causal, scale, block_q, block_k,
                   interpret, heads, window):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k,
                          interpret, heads, window)
    return (out, lse), (q, k, v, out, lse)


def _flash_lse_bwd(causal, scale, block_q, block_k, interpret, heads,
                   window, res, g):
    q, k, v, o, lse = res
    do, dlse = g
    return _flash_bwd(q, k, v, o, lse, do, dlse=dlse, causal=causal,
                      scale=scale, block_q=block_q, block_k=block_k,
                      interpret=interpret, heads=heads, window=window)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


def flash_attention_lse(q, k, v, *, causal=False, scale=None,
                        block_q=None, block_k=None, impl="pallas",
                        window=None):
    """Like flash_attention but also returns the per-row log-sum-exp
    ([B*H, Tq_padded_to_block]): (out, lse) is a complete mergeable
    attention summary — two chunks combine as
      m = max(lse1, lse2); a_i = exp(lse_i - m)
      out = (out1*a1 + out2*a2) / (a1 + a2); lse = m + log(a1 + a2)
    which is what ring attention accumulates across KV rotations.
    Differentiable in q, k, v including through lse consumers.

    impl: "pallas" (the default — this entry has no XLA form) or
    "interpret", which a test asks for by name; nothing here picks
    interpret mode from the platform."""
    if impl not in ("pallas", "interpret"):
        raise ValueError(
            "flash_attention_lse impl must be 'pallas' or 'interpret', "
            "got %r" % (impl,))
    impl, kw = _call_args(q, k, causal, scale, block_q, block_k, impl,
                          window=window)
    _count_impl("flash_attention", impl)
    _count_impl("flash_attention_layout", "head_major")
    with _kernel_scope():
        return _flash_lse(q, k, v, **kw)


def _default_block(t, window=0):
    """Default tile edge for a sequence length of t.

    Pinned by the 2026-08-01 on-chip sweep (PERF.md section 6, PR 21:
    v5e, seq 32k d64): 1024x1024 ran fwd+bwd 1.5x faster than the old
    512x512 default (76.9 ms vs 116.8).  Short sequences keep 512 —
    the kernel clamps to T anyway and seq-512 shapes showed no win
    from smaller tiles.

    With a window the band decides where it is shorter than that: the
    edge is no longer than the window (a power of two, at least 128),
    since a q block runs about (window + block) / block kv blocks
    whatever the block and a longer one only adds masked scores.  At a
    window as long as the length's default the default stays, though no
    pair then lies inside the band (`_Diagonal.inside`) and twice the
    allowed pairs are computed: pinned by the 2026-10-04 on-chip sweep
    (PERF.md section 6, PR 53; tools/flash_window_price.py: v5e, 1 x
    16,384 tokens, 32 / 4 heads of 128 token-major, a window of 1,024;
    the Mosaic calls of one layer alone, forward + one-sweep backward,
    ms): 1024 x 1024 5.15 + 8.70 = 13.85 (2 steps a q block) against
    512 x 512 7.21 + 7.28 = 14.49 (3 steps, one of them inside the
    band, 1.5 times the allowed pairs), 512 x 1024 14.51, 1024 x 512
    17.74, 2048 x 1024 19.98, 2048 x 2048 38.44 and, by the whole
    programs' time, 256 x 256 27.0 against 17.2 and 16.6 (five steps of
    a quarter the work: the step's own cost and the statistics' stores
    decide, not the masked scores).  The forward alone would take 1024
    and the backward 512 (12.43 together): one rule for both keeps the
    op's and the custom_vjp's blocks the same.  The full causal layer
    there: 19.39 + 34.52 = 53.9."""
    if window:
        return min(_default_block(t),
                   max(128, 1 << (window.bit_length() - 1)))
    return 1024 if t >= 1024 else 512


def flash_attention(q, k, v, *, causal=False, scale=None, block_q=None,
                    block_k=None, impl=None, heads=None, window=None):
    """Fused attention. q/k: [B, H, T, D], v: [B, H, Tk, Dv]; returns
    [B, H, Tq, Dv].  Dv = D everywhere but in latent attention (q.k 192
    = 128 + 64 rotary, v 128): the three kernels take the two sizes.

    heads: the head count of TOKEN-MAJOR operands, q/k [B, T, H*D] and
    v [B, Tk, H*Dv] as the projections leave them; returns
    [B, Tq, H*Dv].  No head-major copy is made where the kernels can
    address the heads in place (`_flash_layout`); elsewhere the
    operands are transposed here, to the same answer.

    window (None or 0: none; needs causal): a sliding window, query i
    sees the `window` keys that end at its own, i - window < j <= i.
    The kernels' grids walk the band and no pair below it
    (`_Diagonal`), under names of their own (pt_flash_win_fwd,
    pt_flash_win_bwd_dq, pt_flash_win_bwd_dkv); a window that reaches
    every key is no window.

    impl: None (auto: pallas on TPU, XLA elsewhere), "pallas",
    "interpret" (pallas interpret mode, for CPU tests), or "xla".
    block_q/block_k default to a size picked by sequence length and
    window (_default_block).
    """
    return _flash_attention_fwd(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, impl=impl, heads=heads, window=window)[0]


def _call_args(q, k, causal=False, scale=None, block_q=None, block_k=None,
               impl=None, heads=None, window=None):
    """What a flash entry's unset (None, or an op attr's 0) arguments
    mean, resolved in ONE place (the saved-residual backward reads the
    forward's lse and must tile it the same way): scale 1/sqrt(d), impl
    `_auto_impl()`, blocks by sequence length and window, a window that
    reaches every key (window >= Tk) none.  heads: None for
    [B, H, T, D] operands, the head count of token-major [B, T, H*D]
    ones (rows are dim -2 of both).  Returns
    (impl, the static arguments `_flash_lse` and `_flash_bwd_pallas`
    share)."""
    impl = impl or _auto_impl()
    window = int(window or 0)
    if window < 0 or (window and not causal):
        raise ValueError(
            "flash_attention: window %d needs causal=True and a length "
            "of at least 1 (a query sees the `window` keys that end at "
            "its own)" % window)
    if window >= k.shape[-2]:
        window = 0
    return impl, dict(
        causal=bool(causal),
        scale=float(scale or 1.0 / math.sqrt(q.shape[-1] // (heads or 1))),
        block_q=block_q or _default_block(q.shape[-2], window),
        block_k=block_k or _default_block(k.shape[-2], window),
        interpret=impl == "interpret", heads=heads or None,
        window=window)


def _flash_layout(q, k, v, heads, impl):
    """Which way the kernels address the heads of these operands,
    chosen from what the entry sees and nowhere else: "token_major"
    where [B, T, H*D] operands can be tiled in place, that is a kernel
    impl, one head size D = Dv of 64 or 128, and whole 128-lane blocks
    (an even head count at 64) of Q and, with grouped KV heads, of K
    and V too, a lane block's query heads reading ONE KV head (an even
    group at 64: `_kv_blocks`); "head_major" for [B, H, T, D] operands
    and for every token-major call that fails the rule, which the
    entry transposes to [B, H, T, D] and back: the same answer at the
    cost of the copies.  Counted, a call, in
    paddle_tpu_kernel_impl_total{kernel="flash_attention_layout"}."""
    if heads:
        d = q.shape[-1] // heads
        hkv = _kv_heads(q, k, heads)
        hpb = _MIN_LANES // d if d in (64, 128) else 0
        if impl != "xla" and hpb and v.shape[-1] == hkv * d \
                and (heads * d) % _MIN_LANES == 0 \
                and (hkv * d) % _MIN_LANES == 0 \
                and (hkv == heads or (heads // hkv) % hpb == 0):
            return "token_major"
    return "head_major"


def _split_heads(x, heads):
    """[B, T, H*D] -> [B, H, T, D]."""
    b, t, width = x.shape
    return x.reshape(b, t, heads, width // heads).transpose(0, 2, 1, 3)


def _merge_heads(x):
    """[B, H, T, D] -> [B, T, H*D]."""
    b, h, t, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b, t, h * d)


def _to_kernel_layout(kw, impl, *operands):
    """What a flash entry does about the layout of its operands (q, k,
    v first): counts `_flash_layout`'s choice, and where token-major
    operands cannot be tiled in place returns them [B, H, T, D], with
    kw["heads"] unset.  Returns (transposed, operands)."""
    heads = kw["heads"]
    q, k, v = operands[:3]
    layout = _flash_layout(q, k, v, heads, impl)
    _count_impl("flash_attention_layout", layout)
    if heads is None or layout == "token_major":
        return False, operands
    kw["heads"] = None
    hkv = _kv_heads(q, k, heads)
    # k and v (operands 1 and 2) by their own head count
    return True, tuple(_split_heads(x, hkv if i in (1, 2) else heads)
                       for i, x in enumerate(operands))


def _flash_attention_fwd(q, k, v, **call):
    """flash_attention plus the residual its backward needs: (out, lse),
    lse the log-sum-exp of each row of the scaled, masked scores,
    float32 [B, H, Tq] (-1e30 on a fully masked row).  The same quantity
    on every impl: the kernel's own row statistic on pallas/interpret,
    `logsumexp` of the scores plain attention forms on xla.
    Differentiable in q, k, v.  The `flash_attention` IR op is this;
    `_flash_attention_bwd` is its grad op when (out, lse) were kept.
    **call: `_call_args`' keywords; with `heads`, q, k, v and out are
    token-major."""
    impl, kw = _call_args(q, k, **call)
    _count_impl("flash_attention", impl)
    if _kv_heads(q, k, kw["heads"]) != _dims(q, k, v, kw["heads"])[1]:
        # fewer KV heads than query heads: "grouped", read in place by
        # q_head // group (the kernels), or "repeated" to the query
        # heads' count in HBM (plain attention).  A call with equal
        # counts adds no series: it counts what it counted before
        _count_impl("flash_attention_kv_heads",
                    "repeated" if impl == "xla" else "grouped")
    transposed, (q, k, v) = _to_kernel_layout(kw, impl, q, k, v)
    # device-time attribution (ISSUE 10): at runtime with the `tracing`
    # flag on, an annotation carrying the active trace id; otherwise
    # the null context.  ONE call line either way: source locations
    # ride the Mosaic payload, so two call lines would make the
    # compiled module (and its cache key) depend on the flag
    with _obs_device.annotate("flash_attention"), _kernel_scope():
        if impl == "xla":
            out, lse = _plain_attention(q, k, v, kw["causal"],
                                        kw["scale"], with_lse=True,
                                        window=kw["window"])
        else:
            out, lse = _flash_lse(q, k, v, **kw)
    if transposed:
        out = _merge_heads(out)
    if impl == "xla":
        return out, lse
    b, h, tq = _dims(q, k, v, kw["heads"])[:3]
    # the kernel's [B*H, Tq_padded]: a slice (none at tq % block_q == 0)
    # and a free reshape.  The kernel writes the statistic lane-
    # replicated ([B*H, Tq, 128]) and `_flash_fwd_pallas` strips the
    # lanes; tied to out, that strip has to run here in the forward.
    # Left free, XLA's scheduler put it off until the backward read
    # lse and kept 128x the vector alive meanwhile: +0.4 GB a chip in
    # the dp2 x tp2 step (compiled for a described v5e; PERF.md, PR 25)
    return lax.optimization_barrier(
        (out, lse[:, :tq].reshape(b, h, tq)))


def _flash_attention_bwd(q, k, v, out, lse, g, **call):
    """(dq, dk, dv) from what `_flash_attention_fwd` returned and the
    cotangent g of out: the backward kernel and nothing else.  The
    forward kernel does not run again.  Kernel impls only: plain
    attention keeps no residual worth saving, jax differentiates it."""
    impl, kw = _call_args(q, k, **call)
    transposed, (q, k, v, out, g) = _to_kernel_layout(
        kw, impl, q, k, v, out, g)
    b, h, tq = _dims(q, k, v, kw["heads"])[:3]
    # lse has been ready since the forward, and the kernels read it
    # lane-replicated ([B*H, Tq, 128]): left free, XLA's scheduler makes
    # that broadcast right after the forward kernel and keeps 128x the
    # vector alive until here, +0.8 GB in the six-layer step at seq
    # 8192 (compiled for a described v5e; PERF.md, PR 25).  Tied to g,
    # it cannot be made before the backward reaches this layer.
    lse, g = lax.optimization_barrier((lse, g))
    # see _flash_attention_fwd: one call line, flag or no flag
    with _obs_device.annotate("flash_attention_grad"), _kernel_scope():
        grads = _flash_bwd(q, k, v, out, lse.reshape(b * h, tq), g, **kw)
    return tuple(map(_merge_heads, grads)) if transposed else grads


def _auto_impl():
    """What impl=None means: the Pallas kernel on a TPU, XLA elsewhere
    (interpret mode is only ever asked for by name)."""
    return "pallas" if _on_tpu() else "xla"


def _on_tpu():
    """True when JAX's default device is a TPU chip — the ONE place
    the kernel entries (here, ops/pallas_conv.py, ops/epilogue.py,
    parallel/ring_attention.py, parallel/ulysses.py) ask.  A backend
    that fails to initialize raises from here; it is not answered
    False."""
    return jax.devices()[0].platform == "tpu"


# ---------------------------------------------------------------------------
# flash decode: q_len=1 attention over a paged KV-cache (ISSUE 7)
# ---------------------------------------------------------------------------
# Decode-step attention for autoregressive serving: ONE query token per
# sequence attends over that sequence's whole cached prefix, with K/V
# streamed page-by-page from the ops/paged_kv.py pool through the
# per-sequence block table (vLLM PagedAttention shape).  The grid is
# (B, H/hpb, max_pages) — a split-K sweep over pages with the KV
# dimension innermost so the (acc, m, l) scratch carries across pages;
# each page's partial (out, lse) merges into the carry by EXACTLY the
# PR-2 mergeable-summary contract (m = max(m1, m2); a_i = exp(m_i - m);
# out = sum out_i*a_i / sum l_i*a_i) — the same formula ring attention
# uses across chunks, here applied page-by-page inside one kernel.
#
# Geometry notes (the Mosaic lessons from PR 1/2 applied):
#   * pages are [P, H, page_size, d] (head-major) so the per-step block
#     is (1, hpb, page_size, d) with legal trailing dims; a token-major
#     pool would put a size-1 head slice in the sublane position (the
#     rejected [1, bq] construct class).
#   * the single query row is sublane-replicated to 8 rows (16 for
#     bf16 — the (16, 128) bf16 tile rule) host-side; every row
#     computes the identical result and the caller takes row 0.  The
#     replication is ~B*H*16*d*4 bytes — noise next to the page
#     streaming this kernel exists to bound.
#   * the block table and sequence lengths ride in as SCALAR PREFETCH
#     (SMEM) so the K/V BlockSpec index maps can address physical pages
#     (blk[b, p]) before the body runs — the standard paged-attention
#     Pallas shape.
#   * head packing (the `head_pack` argument): at d <= 64 two heads
#     of the SAME sequence ride per grid step (block (1, 2, ...)),
#     needing H even — the pairing must not cross a batch boundary
#     because both heads share one block table entry.
#
# int8 KV (`kv_int8`): pages hold the PR-5 per-channel contract
# (q = clip(round(x/s*127))); the kernel dequantizes IN VMEM with the
# precomputed per-(head, dim) multiplier s/127, so what streams from
# HBM is int8 — the decode step's traffic is K/V-dominated, so this is
# the same structural cut int8-interlayer made for conv activations.
#
# Not differentiable (decode is inference); no custom_vjp.

_DECODE_VMEM_BUDGET = 12 * 2 ** 20  # conservative per-core VMEM cap
_SUBLANES_BY_DTYPE = {jnp.dtype(jnp.float32): 8,
                      jnp.dtype(jnp.bfloat16): 16,
                      jnp.dtype(jnp.int8): 32}


def _decode_qrows(dtype, q_len=1):
    """Sublane rows of the query block: the min sublane tile of the
    q/output dtype (f32 8, bf16 16) rounded up to hold q_len rows —
    q_len = 1 is the decode step (row 0 replicated), q_len = k+1 is
    the speculative verify step (ISSUE 11c: the last k+1 positions of
    each sequence ride as distinct rows, per-row causal masks)."""
    t = _SUBLANES_BY_DTYPE.get(jnp.dtype(dtype), 8)
    return -(-int(q_len) // t) * t


def _decode_hpb(head_pack, n_heads, d):
    """Heads per grid step: 2 when packing is on, profitable (d <= 64,
    the half-idle-MXU regime) and legal (H even — both packed heads
    share one block-table entry, so the pair must not straddle a
    sequence boundary)."""
    return 2 if (head_pack and d <= 64 and n_heads % 2 == 0) else 1


def _decode_geom_ok(q, k_pages, hpb, vmem_budget_bytes=None,
                    q_len=1):
    """True when the Pallas path is legal + fits VMEM; False routes to
    the gather+reference fallback, which flash_decode records in
    paddle_tpu_kernel_impl_total{kernel="flash_decode",impl="xla"}."""
    d = q.shape[-1]
    ps = k_pages.shape[2]
    store = jnp.dtype(k_pages.dtype)
    if ps % _SUBLANES_BY_DTYPE.get(store, 8) != 0:
        return False
    qrows = _decode_qrows(jnp.float32 if store == jnp.int8
                          else q.dtype, q_len)
    budget = vmem_budget_bytes or _DECODE_VMEM_BUDGET
    # double-buffered K+V page blocks + q/o/acc + the two row-stat
    # scratches
    page_bytes = 2 * 2 * hpb * ps * d * store.itemsize
    row_bytes = hpb * qrows * (3 * d + 2 * _MIN_LANES) * 4
    return page_bytes + row_bytes <= budget


def _decode_kernel(blk_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, page_size, hpb,
                   qrows, int8kv, q_len=1):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_p = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    kv_len = len_ref[b]
    # pages at or past the sequence length contribute nothing — skip
    # them outright (their block-table entries point at valid page 0,
    # so the prefetch window stays in bounds either way)
    live = (p * page_size) < kv_len

    @pl.when(live)
    def _step():
        kpos = p * page_size + lax.broadcasted_iota(
            jnp.int32, (qrows, page_size), 1)
        if q_len == 1:
            # the decode step: every sublane row replicates the ONE
            # query, one shared mask (the validated PR-7 lowering —
            # this branch is byte-identical to it)
            mask = kpos < kv_len
        else:
            # speculative verify (ISSUE 11c): row r is the query at
            # position kv_len - q_len + r, causal WITHIN the window —
            # row r sees keys < kv_len - q_len + 1 + r.  Padding rows
            # (r >= q_len) clamp to kv_len; the caller discards them.
            row = lax.broadcasted_iota(
                jnp.int32, (qrows, page_size), 0)
            limit = jnp.minimum(kv_len,
                                kv_len - q_len + 1 + row)
            mask = kpos < limit
        for h in range(hpb):
            q = q_ref[0, h]                      # [qrows, d]
            k = k_ref[0, h]                      # [page_size, d]
            v = v_ref[0, h]
            if int8kv:
                # int8 pages convert in VMEM; the per-channel dequant
                # scales were algebraically relocated OFF the page by
                # the wrapper (sum_d q_d*(k_td*s_d) == sum_d
                # (q_d*s_d)*k_td, so the K scale pre-multiplied q
                # host-side; the per-output-channel V scale applies to
                # the final acc/l outside the kernel).  What streams
                # from HBM is the raw int8 page — and the kernel body
                # carries zero scale-multiply VPU work per page.
                k = k.astype(jnp.float32)
                v = v.astype(jnp.float32)
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) \
                * scale
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[h, :, 0]
            m_next = jnp.maximum(m_prev, jnp.max(s, axis=-1))
            p_ = jnp.exp(s - m_next[:, None])
            # explicit zero for masked entries (a fully-masked row
            # would otherwise see exp(-1e30 - (-1e30)) = 1)
            p_ = jnp.where(mask, p_, 0.0)
            alpha = jnp.exp(m_prev - m_next)
            l_next = l_ref[h, :, 0] * alpha + jnp.sum(p_, axis=-1)
            acc_ref[h] = acc_ref[h] * alpha[:, None] + lax.dot_general(
                p_ if int8kv else p_.astype(v.dtype), v,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_next[:, None],
                                        m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_next[:, None],
                                        l_ref.shape[1:])

    @pl.when(p == n_p - 1)
    def _finalize():
        for h in range(hpb):
            l = l_ref[h, :, 0]
            l = jnp.where(l == 0.0, 1.0, l)  # zero-length seq -> 0 out
            o_ref[0, h] = (acc_ref[h] / l[:, None]).astype(o_ref.dtype)


def _flash_decode_pallas(q, k_pages, v_pages, block_tables, seq_lens,
                         scale, hpb, interpret=False, q_len=1):
    """q: [B, H, d] (q_len 1) or [B, R, H, d] (q_len R — the verify
    step; K-scale pre-applied in int8 mode either way); pools
    [P, H, ps, d]; block_tables [B, MP] int32; seq_lens [B] int32
    (INCLUDING the R window tokens) -> out [B, H, d] / [B, R, H, d]
    (f32 in int8 mode — the V scale applies outside)."""
    ps = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    qrows = _decode_qrows(q.dtype, q_len)
    int8kv = jnp.dtype(k_pages.dtype) == jnp.int8
    if q_len == 1:
        b, h, d = q.shape
        q8 = jnp.broadcast_to(q[:, :, None, :], (b, h, qrows, d))
    else:
        b, _, h, d = q.shape
        # rows 0..R-1 are the R real queries; padding rows repeat the
        # last one (masked identically to it, discarded by the caller)
        qr = jnp.transpose(q, (0, 2, 1, 3))          # [B, H, R, d]
        pad = jnp.broadcast_to(qr[:, :, -1:, :],
                               (b, h, qrows - q_len, d))
        q8 = jnp.concatenate([qr, pad], axis=2) if qrows > q_len \
            else qr
    kernel = functools.partial(_decode_kernel, scale=scale,
                               page_size=ps, hpb=hpb, qrows=qrows,
                               int8kv=int8kv, q_len=q_len)
    in_specs = [
        pl.BlockSpec((1, hpb, qrows, d),
                     lambda bi, hi, pi, blk, ln: (bi, hi, 0, 0)),
        pl.BlockSpec((1, hpb, ps, d),
                     lambda bi, hi, pi, blk, ln: (blk[bi, pi], hi, 0,
                                                  0)),
        pl.BlockSpec((1, hpb, ps, d),
                     lambda bi, hi, pi, blk, ln: (blk[bi, pi], hi, 0,
                                                  0)),
    ]
    args = [q8, k_pages, v_pages]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h // hpb, max_pages),
        in_specs=in_specs,
        out_specs=pl.BlockSpec(
            (1, hpb, qrows, d),
            lambda bi, hi, pi, blk, ln: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hpb, qrows, d), jnp.float32),
            pltpu.VMEM((hpb, qrows, _MIN_LANES), jnp.float32),
            pltpu.VMEM((hpb, qrows, _MIN_LANES), jnp.float32),
        ])
    params = {}
    if not interpret:
        params["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        name="pt_flash_decode",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, h, qrows, d),
            jnp.float32 if int8kv else q.dtype),
        interpret=interpret,
        **params,
    )(jnp.asarray(block_tables, jnp.int32),
      jnp.asarray(seq_lens, jnp.int32), *args)
    if q_len == 1:
        return out[:, :, 0, :]
    return jnp.transpose(out[:, :, :q_len, :], (0, 2, 1, 3))


def flash_decode_reference(q, k_pages, v_pages, block_tables, seq_lens,
                           scale=None, kv_scales=None):
    """Gather + reference attention replay: the flash_decode fallback
    path (VMEM budget / geometry gate / off-TPU impl) AND the parity
    oracle.  It gathers the pages dense through the block table and
    replays the kernel's page-ordered online-softmax merge with the
    SAME op order, shapes and rounding points (q sublane-replicated,
    per-page dot/max/exp/fma in f32, post-exp masking), so
    flash_decode output is array_equal to this path in every mode —
    the bit-parity contract PR 4 established for fused-vs-unfused.
    Mathematically it equals plain softmax(QK^T)V over the first
    seq_len cached tokens (allclose; asserted in tests).

    Runs as ONE jitted computation on purpose: the interpret/pallas
    kernel executes its whole grid inside one XLA computation, where
    the compiler contracts ``acc*alpha + dot(...)`` into an FMA; an
    eager op-by-op replay rounds the multiply and add separately and
    drifts 1 ulp per page (measured) — jitting the replay restores
    the identical fusion, and the production fallback runs under the
    caller's jit anyway.  The int8-KV dequant multiplies stay EAGER
    and outside the jitted region in BOTH paths (pre-scaled q, V scale
    on the final output) for the same reason — inside, the compiler
    folds them into the dots differently per path."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    bt = jnp.asarray(block_tables, jnp.int32)
    sl = jnp.asarray(seq_lens, jnp.int32)
    q_len = 1 if q.ndim == 3 else int(q.shape[1])
    if jnp.dtype(k_pages.dtype) == jnp.int8:
        q_eff, vdq = _int8_pre(q, kv_scales)
        if q_len == 1:
            raw = _decode_reference_jit(q_eff, k_pages, v_pages, bt,
                                        sl, jnp.float32(scale))
        else:
            raw = _decode_reference_multi_jit(
                q_eff, k_pages, v_pages, bt, sl, jnp.float32(scale),
                q_len)
        return _int8_post(raw, vdq, q.dtype)
    if q_len == 1:
        return _decode_reference_jit(q, k_pages, v_pages, bt, sl,
                                     jnp.float32(scale))
    return _decode_reference_multi_jit(q, k_pages, v_pages, bt, sl,
                                       jnp.float32(scale), q_len)


def _int8_pre(q, kv_scales):
    """Eager int8-KV dequant prologue shared by kernel + reference:
    the per-channel K scale rides the contraction dim, so
    sum_d q_d*(k_td*s_d) == sum_d (q_d*s_d)*k_td — pre-scale q once
    ([B, H, d] or [B, R, H, d]) instead of dequantizing every page
    ([ps, d] per step)."""
    if kv_scales is None:
        raise ValueError("int8 k_pages/v_pages need kv_scales "
                         "(per-channel [H, d] — paged_kv.kv_scales())")
    kdq = kv_scales[0].astype(jnp.float32) / 127.0
    vdq = kv_scales[1].astype(jnp.float32) / 127.0
    kdq = kdq[None, :, :] if q.ndim == 3 else kdq[None, None, :, :]
    return q.astype(jnp.float32) * kdq, vdq


def _int8_post(raw, vdq, out_dtype):
    """Eager int8-KV epilogue: the V scale is per OUTPUT channel, so
    it moves out of the page accumulation onto the final
    [B, H, d] / [B, R, H, d]."""
    vdq = vdq[None, :, :] if raw.ndim == 3 else vdq[None, None, :, :]
    return (raw * vdq).astype(out_dtype)


def _decode_reference_impl(q, k_pages, v_pages, block_tables, seq_lens,
                           scale):
    b, h, d = q.shape
    ps = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    qrows = _decode_qrows(q.dtype)
    int8kv = jnp.dtype(k_pages.dtype) == jnp.int8
    q8 = jnp.broadcast_to(q[:, :, None, :], (b, h, qrows, d))
    # gather [B, MP, H, ps, d] (the dense copy the kernel avoids)
    kg = jnp.take(k_pages, jnp.asarray(block_tables, jnp.int32),
                  axis=0)
    vg = jnp.take(v_pages, jnp.asarray(block_tables, jnp.int32),
                  axis=0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    m = jnp.full((b, h, qrows), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, qrows), jnp.float32)
    acc = jnp.zeros((b, h, qrows, d), jnp.float32)

    # ONE lax.scan over pages, not an unrolled python loop: the body
    # compiles once however wide the block table is (a 32k-token
    # sequence is a 512-wide table — unrolled, XLA's compile time
    # exploded on exactly that width, found by the chunked-join SLO
    # leg).  The per-page op order is unchanged, so kernel parity
    # holds bit-for-bit.
    def page_step(carry, inputs):
        m, l, acc = carry
        p, k, v = inputs                            # [B, H, ps, d]
        if int8kv:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        kpos = p * ps + lax.broadcasted_iota(
            jnp.int32, (qrows, ps), 1)
        mask = kpos[None, None] < lens[:, None, None, None]
        s = jnp.einsum("bhqd,bhkd->bhqk", q8, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, axis=-1))
        p_ = jnp.exp(s - m_next[..., None])
        p_ = jnp.where(mask, p_, 0.0)
        alpha = jnp.exp(m - m_next)
        l = l * alpha + jnp.sum(p_, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_ if int8kv else p_.astype(v.dtype),
            v, preferred_element_type=jnp.float32)
        return (m_next, l, acc), None

    (m, l, acc), _ = lax.scan(
        page_step, (m, l, acc),
        (jnp.arange(max_pages, dtype=jnp.int32),
         jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)
    return out[:, :, 0, :]


_decode_reference_jit = jax.jit(_decode_reference_impl)


def _decode_reference_multi_impl(q, k_pages, v_pages, block_tables,
                                 seq_lens, scale, q_len):
    """q-len-R twin of _decode_reference_impl (the verify-step oracle,
    ISSUE 11c): q [B, R, H, d], per-row causal masks mirroring the
    kernel's minimum(kv_len, kv_len - R + 1 + row) rule with the SAME
    op order / shapes / rounding points, so flash_decode at q_len > 1
    is array_equal to this in every mode."""
    b, rr, h, d = q.shape
    ps = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    qrows = _decode_qrows(q.dtype, q_len)
    int8kv = jnp.dtype(k_pages.dtype) == jnp.int8
    qr = jnp.transpose(q, (0, 2, 1, 3))              # [B, H, R, d]
    if qrows > rr:
        pad = jnp.broadcast_to(qr[:, :, -1:, :],
                               (b, h, qrows - rr, d))
        q8 = jnp.concatenate([qr, pad], axis=2)
    else:
        q8 = qr
    kg = jnp.take(k_pages, jnp.asarray(block_tables, jnp.int32),
                  axis=0)
    vg = jnp.take(v_pages, jnp.asarray(block_tables, jnp.int32),
                  axis=0)
    lens = jnp.asarray(seq_lens, jnp.int32)
    m = jnp.full((b, h, qrows), _NEG_INF, jnp.float32)
    l = jnp.zeros((b, h, qrows), jnp.float32)
    acc = jnp.zeros((b, h, qrows, d), jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (qrows, ps), 0)
    limit = jnp.minimum(
        lens[:, None, None, None],
        lens[:, None, None, None] - q_len + 1 + row[None, None])

    # same compile-scaling rule as the q-len-1 replay: ONE lax.scan
    # over pages, body compiled once however wide the table is
    def page_step(carry, inputs):
        m, l, acc = carry
        p, k, v = inputs                            # [B, H, ps, d]
        if int8kv:
            k = k.astype(jnp.float32)
            v = v.astype(jnp.float32)
        kpos = p * ps + lax.broadcasted_iota(
            jnp.int32, (qrows, ps), 1)
        mask = kpos[None, None] < limit
        s = jnp.einsum("bhqd,bhkd->bhqk", q8, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, _NEG_INF)
        m_next = jnp.maximum(m, jnp.max(s, axis=-1))
        p_ = jnp.exp(s - m_next[..., None])
        p_ = jnp.where(mask, p_, 0.0)
        alpha = jnp.exp(m - m_next)
        l = l * alpha + jnp.sum(p_, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p_ if int8kv else p_.astype(v.dtype),
            v, preferred_element_type=jnp.float32)
        return (m_next, l, acc), None

    (m, l, acc), _ = lax.scan(
        page_step, (m, l, acc),
        (jnp.arange(max_pages, dtype=jnp.int32),
         jnp.moveaxis(kg, 1, 0), jnp.moveaxis(vg, 1, 0)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l[..., None]).astype(q.dtype)
    return jnp.transpose(out[:, :, :rr, :], (0, 2, 1, 3))


_decode_reference_multi_jit = jax.jit(_decode_reference_multi_impl,
                                      static_argnums=(6,))


def flash_decode(q, k_pages, v_pages, block_tables, seq_lens, *,
                 scale=None, impl=None, head_pack=None,
                 kv_scales=None, vmem_budget_bytes=None, window=None):
    """Paged-KV decode-step attention.  q: [B, H, d] (ONE query token
    per sequence) or [B, R, H, d] (the SPECULATIVE VERIFY step, ISSUE
    11c: the R = k+1 newest tokens of each sequence as distinct query
    rows, row r causally seeing keys < seq_len - R + 1 + r);
    k_pages/v_pages: [num_pages, H, page_size, d] pool
    (ops/paged_kv.PagedKVCache layout; int8 pools need kv_scales =
    (k_scale, v_scale) per-channel [H, d]); block_tables: [B,
    max_pages] int32; seq_lens: [B] int32 — the FULL cached length,
    including the R window tokens in verify mode.  Returns [B, H, d]
    or [B, R, H, d].

    impl: None (auto: pallas on TPU, reference replay elsewhere),
    "pallas", "interpret", or "xla" (the gather+reference path).
    head_pack: two heads a grid step; needs d <= 64 and an even H
    (`_decode_hpb`; off where they do not hold).  Every mode is
    bit-identical (array_equal) to flash_decode_reference — the
    parity contract tests pin across page boundaries, ragged
    lengths, d in {64, 128}, f32/bf16/int8-KV,
    head-packed and not, q_len 1 and k+1.  Verify row r is ALSO
    bit-identical to a q-len-1 call at seq_len - R + 1 + r (masked
    pages are exact no-ops in the online-softmax merge) — the
    numerical half of the lossless-speculation contract.

    window: NOT built.  The page sweep reads every cached page of a
    sequence; a sliding window needs a first-page bound in the sweep
    and a cache allocator that frees the pages behind it (ROADMAP
    2.1)."""
    if window:
        raise NotImplementedError(
            "flash_decode: a sliding window (%r) is not built: the page "
            "sweep reads a sequence's whole cache" % (window,))
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    scale = float(scale)
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    q_len = 1 if q.ndim == 3 else int(q.shape[1])
    int8kv = jnp.dtype(k_pages.dtype) == jnp.int8
    if int8kv and kv_scales is None:
        raise ValueError("int8 k_pages/v_pages need kv_scales "
                         "(per-channel [H, d] — paged_kv.kv_scales())")
    hpb = _decode_hpb(head_pack, q.shape[-2], q.shape[-1])
    if impl in ("pallas", "interpret") and not _decode_geom_ok(
            q, k_pages, hpb, vmem_budget_bytes, q_len):
        impl = "xla"   # documented fallback: gather + reference replay
    _count_impl("flash_decode", impl)
    # see flash_attention
    with _obs_device.annotate("flash_decode"), _kernel_scope():
        return _flash_decode_entry(q, k_pages, v_pages, block_tables,
                                   seq_lens, scale, impl, hpb, int8kv,
                                   kv_scales, q_len)


def _flash_decode_entry(q, k_pages, v_pages, block_tables, seq_lens,
                        scale, impl, hpb, int8kv, kv_scales, q_len=1):
    if impl in ("pallas", "interpret"):
        if int8kv:
            q_eff, vdq = _int8_pre(q, kv_scales)
            raw = _flash_decode_pallas(
                q_eff, k_pages, v_pages, block_tables, seq_lens,
                scale, hpb, interpret=impl == "interpret",
                q_len=q_len)
            return _int8_post(raw, vdq, q.dtype)
        return _flash_decode_pallas(
            q, k_pages, v_pages, block_tables, seq_lens, scale, hpb,
            interpret=impl == "interpret", q_len=q_len)
    return flash_decode_reference(q, k_pages, v_pages, block_tables,
                                  seq_lens, scale=scale,
                                  kv_scales=kv_scales)


# ---------------------------------------------------------------------------
# IR op registration
# ---------------------------------------------------------------------------

from paddle_tpu.core.registry import register_op  # noqa: E402


def _gspmd_flash_shard_map(attrs, call, operands, kinds):
    """GSPMD front-end hook (parallel/gspmd.py tag_attention_ops):
    when the typed `gspmd` flag is on and the op carries
    gspmd_batch_axis / gspmd_head_axis attrs, run the kernel under
    shard_map on the current mesh — Mosaic kernels can't ride XLA's
    automatic partitioner, and attention is independent per
    (batch, head) row so the dp x tp split is exact.

    `call(*operands, heads=...)` computes one shard.  `kinds`: a
    letter an operand, then "->", then a letter an output: "x" for
    q/k/v/out and their gradients, "l" for LSE.  Head-major (no
    `heads` attr) x is [B, H, T, D] and rides P(batch_axis, head_axis,
    None, None); token-major it is [B, T, H*D] and rides
    P(batch_axis, None, head_axis), which is the sharding a
    column-parallel projection leaves its output in, and a shard is
    called with its LOCAL head count (where that does not fill lane
    blocks, `_flash_layout` sends the shard head-major).  LSE is
    [B, H, Tq], P(batch_axis, head_axis, None), on both.  Flag off or
    an untagged op returns None and the caller runs the plain
    single-program path.  A TAGGED op that fails a gate (no mesh, axis
    missing or size 1, batch, head or KV head count not divisible) also
    runs
    plain, and says so in
    paddle_tpu_kernel_impl_total{kernel="flash_attention_gspmd"}:
    impl="plain" against impl="shard_map"."""
    from paddle_tpu.flags import get_flag

    heads = attrs.get("heads") or None
    plain = functools.partial(call, heads=heads)
    if not get_flag("gspmd"):
        return plain(*operands)
    ba = attrs.get("gspmd_batch_axis") or None
    ha = attrs.get("gspmd_head_axis") or None
    if not (ba or ha):
        return plain(*operands)
    from paddle_tpu.parallel import env as penv

    mesh = penv.get_mesh()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if mesh is not None else {}
    bsz, hsz = operands[0].shape[0], heads or operands[0].shape[1]
    # the head axis splits K and V by KV head: the fewer of the two
    # counts has to divide
    hsz = math.gcd(hsz, _kv_heads(operands[0], operands[1], heads))
    if ba and (sizes.get(ba, 1) <= 1 or bsz % sizes.get(ba, 1) != 0):
        ba = None
    if ha and (sizes.get(ha, 1) <= 1 or hsz % sizes.get(ha, 1) != 0):
        ha = None
    if not (ba or ha):
        _count_impl("flash_attention_gspmd", "plain")
        return plain(*operands)
    from jax.sharding import PartitionSpec as P

    _count_impl("flash_attention_gspmd", "shard_map")
    spec = {"l": P(ba, ha, None),
            "x": P(ba, None, ha) if heads else P(ba, ha, None, None)}
    ins, outs = kinds.split("->")
    return jax.shard_map(
        functools.partial(
            call, heads=heads and heads // sizes.get(ha, 1)),
        mesh=mesh, in_specs=tuple(spec[c] for c in ins),
        out_specs=tuple(spec[c] for c in outs),
        check_vma=False)(*operands)


_FLASH_OP_ATTRS = {"causal": False, "scale": 0.0, "block_q": 0,
                   "block_k": 0, "heads": 0, "window": 0,
                   "gspmd_batch_axis": "", "gspmd_head_axis": ""}


def _flash_op_call(attrs):
    # an attr left at its 0 default means unset, as None does (_call_args)
    return {k: attrs.get(k) for k in
            ("causal", "scale", "block_q", "block_k", "window")}


@register_op("flash_attention", inputs=("Q", "K", "V"),
             outputs=("Out", "LSE"), attrs=_FLASH_OP_ATTRS)
def _flash_attention_op(ins, attrs):
    """Q, K, V and Out are [B, H, T, D], or, where the `heads` attr is
    set, token-major [B, T, H*D] as the projections leave them
    (`_flash_layout`).  Out comes with the residual the grad op reads
    instead of running the forward kernel again: LSE, the per-row
    log-sum-exp, float32 [B, H, Tq] on every impl and both layouts
    (_flash_attention_fwd).  An op desc that binds no LSE (a program
    from before the slot) runs the same."""
    out, lse = _gspmd_flash_shard_map(
        attrs,
        functools.partial(_flash_attention_fwd, **_flash_op_call(attrs)),
        (ins["Q"], ins["K"], ins["V"]), "xxx->xl")
    return {"Out": out, "LSE": lse}


def _flash_grad_reads_saved(ins, attrs=None):
    """Whether flash_attention_grad runs on the forward's Out and LSE:
    both bound and the impl a kernel (OpDef.reads_saved)."""
    return "Out" in ins and "LSE" in ins and _auto_impl() != "xla"


@register_op("flash_attention_grad",
             inputs=("Q", "K", "V", "Out", "LSE", "Out@GRAD"),
             outputs=("Q@GRAD", "K@GRAD", "V@GRAD"),
             optional=("Out", "LSE"), attrs=_FLASH_OP_ATTRS,
             differentiable=False, reads_saved=_flash_grad_reads_saved)
def _flash_attention_grad_op(ins, attrs):
    """Hand-written: XLA does not CSE a duplicated Mosaic custom call,
    so the generic `jax.vjp` grad op ran the forward kernel a second
    time in every layer (12 pt_flash_fwd in a six-layer step; PERF.md,
    PR 24).  The choice follows from what the op can see
    (`_flash_grad_reads_saved`):

      * Out and LSE bound (append_backward binds them; a recompute
        segment's backward binds them on the op it replays,
        ops/misc.py recompute_segment_grad) and the impl resolves to a
        kernel: the backward kernel on the saved residuals, under the
        same shard_map gate as the forward;
      * either slot unbound (a program serialized before the slots, a
        hand-built op) or the XLA impl: `jax.vjp` over the forward
        op's compute, as the generic grad op did.

    paddle_tpu_kernel_impl_total{kernel="flash_attention_grad"} says
    which: impl="saved" | "recompute".  A segment that keeps its
    replay of the op never calls this and counts neither."""
    q, k, v, g = (ins[s] for s in ("Q", "K", "V", "Out@GRAD"))
    if not _flash_grad_reads_saved(ins):
        _count_impl("flash_attention_grad", "recompute")
        _, vjp = jax.vjp(
            lambda q, k, v: _flash_attention_op(
                {"Q": q, "K": k, "V": v}, attrs)["Out"], q, k, v)
        dq, dk, dv = vjp(g)
    else:
        _count_impl("flash_attention_grad", "saved")
        dq, dk, dv = _gspmd_flash_shard_map(
            attrs,
            functools.partial(_flash_attention_bwd,
                              **_flash_op_call(attrs)),
            (q, k, v, ins["Out"], ins["LSE"], g), "xxxxlx->xxx")
    return {"Q@GRAD": dq, "K@GRAD": dk, "V@GRAD": dv}


@register_op("flash_decode",
             inputs=("Q", "KPages", "VPages", "BlockTables", "SeqLens",
                     "KScale", "VScale"),
             outputs=("Out",), optional=("KScale", "VScale"),
             attrs={"scale": 0.0, "window": 0})
def _flash_decode_op(ins, attrs):
    """IR surface of the paged decode-step attention (module section
    above); KScale/VScale are the int8-KV per-channel dequant scales."""
    kv_scales = None
    if "KScale" in ins:
        kv_scales = (ins["KScale"], ins["VScale"])
    return {"Out": flash_decode(ins["Q"], ins["KPages"], ins["VPages"],
                                ins["BlockTables"], ins["SeqLens"],
                                scale=attrs.get("scale") or None,
                                kv_scales=kv_scales,
                                window=attrs.get("window") or None)}

"""Grouped matrix multiplication over the experts one chip holds.

The expert feed-forward of a sparse mixture routes every token to k of E
experts; a chip that holds G of them computes, for the rows routed to
its own experts, `rows_g @ W[g]` for each held expert g.  The rows are
laid out sorted by expert with every group padded to whole tiles of
`tm` rows (ops/llm_ops.py `_group_layout`), so a row tile belongs to
exactly ONE expert and the kernels are plain tiled matmuls whose weight
block is picked per tile from a scalar-prefetched table:

    gmm    out[t]  = lhs[t] @ rhs[tile_group[t]]         pt_gmm_fwd
    gmm^T  out[t]  = lhs[t] @ rhs[tile_group[t]]^T       pt_gmm_bwd_dx
    tgmm   out[g]  = sum over tiles t of g: lhs[t]^T @ g[t]
                                                         pt_gmm_bwd_dw

SwiGLU stands between an expert layer's products, element-wise by row,
and the kernels form it on their own blocks in VMEM, so that no row
array exists only to carry it from one kernel to the next.  A call's
operands say which form it is (_BLOCKS):

    gmm_swiglu       lhs = (hg, hu): the product's left operand is
                     silu(hg) * hu, formed in float32 and rounded to
                     the operands' dtype on the way in; tgmm_swiglu the
                     same for tgmm                       (down, d W_down)
    gmm_swiglu_grad  gated = (hg, hu): the float32 accumulator is d act
                     and the store step writes (d hg, d hu), SwiGLU's
                     gradient, in its place              (d act)

The row arrays are sized for the worst case (every token-expert pair
routed here); the number of tiles that hold rows, `n_active`, is a
run-time scalar, and it is the bound of the grid's row-tile axis: a
call's grid ends at the last tile that holds rows, and no grid step is
spent on a tile past it.  Such a tile's output rows are never written;
what they hold is not defined, and the caller never reads them.  That
holds for the caller's own row arrays too: moe_experts makes them with
`row_buffer`, unwritten, and writes the live prefix [0, n_active * tm)
in chunks (llm_ops._over_live_rows); nothing by padded row, kernel or
array code, runs past n_active.  No capacity, no dropped token, no
[N, E, C] one-hot.

The blocks along the two weight axes are chosen by what fits VMEM, not
by what divides (`_tiles`): an axis admits every multiple of 128 that
divides it AND its whole extent, and of the shapes whose buffers fit
the budget a call runs the one with the fewest grid steps a row tile.
An expert width of 1,408 = 11 x 128 is taken whole, and at four cells'
shapes so is the other axis: one grid step a row tile a call, 12 a live
row tile of a layer's twelve calls where 128-wide blocks along 1,408
made it 418.  (At [4096, 1280] the three weight gradients take
halves.)  With the contraction whole the weight block's index is the
same for an expert's consecutive row tiles and the pipeline does not
fetch it again.  The rule reads the call's kernel, shapes and dtype and
nothing else; a call whose blocks need more than Mosaic's default scope
asks for it (`vmem_limit_bytes`).

Technique after the megablox grouped matmul of jax's Pallas TPU
examples; tile-aligned groups make the row masks and the group-metadata
pass of that kernel unnecessary.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# The most _tiles plans a call's blocks to take of the core's 128 MiB
# of VMEM, and what a call asks for beyond its blocks (Mosaic's own
# scratch).  The budget is the chip's answer (PERF.md, PR 40): at both
# cells' shapes every step fewer a row tile was faster, the whole
# matrix a block (16-33 MiB) fastest, in each of the six calls of a
# layer.
_VMEM_BUDGET = 40 << 20
_VMEM_HEADROOM = 4 << 20


def _blocks(dim):
    """The blocks an axis admits: every multiple of 128 that divides it,
    and its whole extent (a block equal to the array's extent is legal
    whatever its size).  Nothing pads a width to a friendlier one, and
    no block hangs over an edge."""
    return [t for t in range(128, dim, 128) if dim % t == 0] + [dim]


# kernel -> the blocks a grid step holds beside its one [tk, tn] block
# (a gmm's weight block in, a tgmm's output): row blocks [tm, tk] in,
# row blocks [tm, tn] in, whether the accumulator is a group's [tk, tn]
# (tgmm) or the rows' [tm, tn], and row blocks [tm, tn] out.  Two row
# blocks in are SwiGLU's pair (hg, hu): a call's operands say which
# form it is (_form), and _vmem_bytes, _tiles and the kernel bodies
# read here what it holds.
_BLOCKS = {"gmm": (1, 0, False, 1), "gmm_swiglu": (2, 0, False, 1),
           "gmm_swiglu_grad": (1, 2, False, 2),
           "tgmm": (1, 1, True, 0), "tgmm_swiglu": (2, 1, True, 0)}


def _form(lhs, rows_in, by_group):
    """The kernel of a call, from its operands: the table's row that
    holds len(lhs) row blocks [tm, tk] and len(rows_in) row blocks
    [tm, tn].  Operands that are no row of the table are an error."""
    kernel, = [kernel for kernel, blocks in _BLOCKS.items()
               if blocks[:3] == (len(lhs), len(rows_in), by_group)]
    return kernel


def _vmem_bytes(kernel, tm, tk, tn, itemsize):
    """What a call's blocks take of VMEM: two buffers of each input
    block and of each output block (the pipeline fetches the next while
    the kernel works on this one), the float32 accumulator, and the
    two blocks of a SwiGLU pair again in float32, where the kernel
    forms SwiGLU or its gradient."""
    lhs, rows_in, by_group, rows_out = _BLOCKS[kernel]
    blocks = lhs * tm * tk + tk * tn + (rows_in + rows_out) * tm * tn
    pair = tm * tk * (lhs == 2) + tm * tn * (rows_in == 2)
    return 2 * itemsize * blocks \
        + 4 * (tk * tn if by_group else tm * tn) + 2 * 4 * pair


def _tiles(kernel, k, n, tm, itemsize):
    """(tn, tk): the block along the output width n and along the
    contraction (the gmm kernels, either orientation of rhs) or the
    other output axis (the tgmm kernels) k.  Of the shapes _blocks
    admits and _VMEM_BUDGET holds, the one with the fewest grid steps a
    row tile, (n / tn) x (k / tk).  Among equals gmm takes the deeper
    tk (a whole contraction leaves the weight block's index alone from
    one row tile of an expert to the next, and the pipeline does not
    fetch it again), tgmm the wider tn (the faster of the two on the
    chip at like steps).  A pure function of the call's kernel, shapes
    and dtype: an expert width of 1,408 = 11 x 128 with 11 prime is
    taken whole, where "the largest divisor up to 1,024" made every
    block along it 128 wide; [3584, 1024] is one block where it was 7
    or 8."""
    fits = [(tn, tk) for tn in _blocks(n) for tk in _blocks(k)
            if _vmem_bytes(kernel, tm, tk, tn, itemsize) <= _VMEM_BUDGET]
    if not fits:        # not even the narrowest fits: take it, and ask
        return _blocks(n)[0], _blocks(k)[0]
    by_group = _BLOCKS[kernel][2]

    def cost(block):
        tn, tk = block
        steps = (n // tn) * (k // tk)
        return (steps, -tn, -tk) if by_group else (steps, -tk, -tn)

    return min(fits, key=cost)


def _vmem_limit(vmem_bytes):
    """What a call whose blocks take vmem_bytes passes as
    vmem_limit_bytes: Mosaic's default scope unless it needs more."""
    from paddle_tpu.ops.pallas_kernels import _MOSAIC_SCOPED_VMEM

    return max(_MOSAIC_SCOPED_VMEM, vmem_bytes + _VMEM_HEADROOM)


def _pair_or_one(lhs):
    """A call's lhs as a tuple: one array, or SwiGLU's pair."""
    return tuple(lhs) if isinstance(lhs, (tuple, list)) else (lhs,)


def _silu_and_grad(h):
    sig = jax.nn.sigmoid(h)
    return h * sig, sig * (1 + h * (1 - sig))


def _swiglu(hg, hu):
    """silu(hg) * hu in float32, rounded to hg's dtype: what the down
    projection multiplies."""
    act = _silu_and_grad(hg.astype(jnp.float32))[0] * hu.astype(jnp.float32)
    return act.astype(hg.dtype)


def _swiglu_grad(hg, hu, g_act):
    """(d hg, d hu) in hg's dtype from float32 d act."""
    silu, dsilu = _silu_and_grad(hg.astype(jnp.float32))
    return ((g_act * hu.astype(jnp.float32) * dsilu).astype(hg.dtype),
            (g_act * silu).astype(hg.dtype))


def _gmm_kernel(tg_ref, na_ref, *refs, kernel, transpose_rhs):
    n_lhs, n_gated = _BLOCKS[kernel][:2]
    lhs, w_ref, refs = refs[:n_lhs], refs[n_lhs], refs[n_lhs + 1:]
    gated, (*outs, acc_ref) = refs[:n_gated], refs[n_gated:]
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = _swiglu(lhs[0][...], lhs[1][...]) if n_lhs == 2 else lhs[0][...]
    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        x, w_ref[0], dims, preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        if n_gated == 2:
            values = _swiglu_grad(gated[0][...], gated[1][...], acc_ref[...])
        else:
            values = acc_ref[...],
        for o_ref, value in zip(outs, values):
            o_ref[...] = value.astype(o_ref.dtype)


def _tgmm_kernel(tg_ref, na_ref, *refs, kernel, n_tiles):
    n_lhs = _BLOCKS[kernel][0]
    lhs, (g_ref, o_ref, acc_ref) = refs[:n_lhs], refs[n_lhs:]
    i = pl.program_id(2)
    here = tg_ref[i]
    first = (i == 0) | (tg_ref[jnp.maximum(i - 1, 0)] != here)
    last = (i == na_ref[0] - 1) | (tg_ref[jnp.minimum(i + 1, n_tiles - 1)]
                                   != here)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = _swiglu(lhs[0][...], lhs[1][...]) if n_lhs == 2 else lhs[0][...]
    acc_ref[...] += lax.dot_general(
        x, g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _params(interpret, semantics, vmem_bytes):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=_vmem_limit(vmem_bytes))}


@functools.partial(jax.jit, static_argnames=(
    "tm", "transpose_rhs", "interpret"))
def gmm_pallas(lhs, rhs, tile_group, n_active, tm, transpose_rhs=False,
               interpret=False, gated=()):
    """lhs [M, K] (M a multiple of tm), rhs [G, K, N] (or [G, N, K] with
    transpose_rhs), tile_group [M/tm] int32, n_active [1] int32 ->
    [M, N] in lhs's dtype; rows of tiles past n_active undefined.  The
    grid visits the n_active[0] >= 1 tiles that hold rows and no other.
    lhs a pair (hg, hu), both [M, K]: the left operand is their SwiGLU
    (gmm_swiglu).  gated a pair (hg, hu), both [M, N]: the product is
    d act and the call returns (d hg, d hu) (gmm_swiglu_grad)."""
    lhs = _pair_or_one(lhs)
    kernel = _form(lhs, gated, False)
    n_outs = _BLOCKS[kernel][3]
    m, k = lhs[0].shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    dtype = lhs[0].dtype
    tn, tk = _tiles(kernel, k, n, tm, dtype.itemsize)
    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, tn, tk), lambda j, i, kk, tg, na: (tg[i], j, kk))
    else:
        w_spec = pl.BlockSpec(
            (1, tk, tn), lambda j, i, kk, tg, na: (tg[i], kk, j))
    in_rows = pl.BlockSpec((tm, tk), lambda j, i, kk, tg, na: (i, kk))
    out_rows = pl.BlockSpec((tm, tn), lambda j, i, kk, tg, na: (i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, n_active[0], k // tk),
        in_specs=[in_rows] * len(lhs) + [w_spec] + [out_rows] * len(gated),
        out_specs=[out_rows] * n_outs,
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    outs = pl.pallas_call(
        functools.partial(_gmm_kernel, kernel=kernel,
                          transpose_rhs=transpose_rhs),
        name="pt_gmm_bwd_dx" if transpose_rhs else "pt_gmm_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((m, n), dtype)] * n_outs,
        interpret=interpret,
        **_params(interpret, ("parallel", "arbitrary", "arbitrary"),
                  _vmem_bytes(kernel, tm, tk, tn, dtype.itemsize)),
    )(tile_group, n_active, *lhs, rhs, *gated)
    return outs[0] if n_outs == 1 else tuple(outs)


@functools.partial(jax.jit, static_argnames=("tm", "n_groups", "interpret"))
def tgmm_pallas(lhs, grad, tile_group, n_active, tm, n_groups,
                interpret=False):
    """lhs [M, K], grad [M, N] -> [G, K, N] in lhs's dtype: for each
    group the sum over its tiles of lhs[t]^T @ grad[t]; lhs a pair
    (hg, hu) stands for their SwiGLU (tgmm_swiglu).  Every group has at
    least one tile (the layout's guarantee), so every output block is
    written.  The grid's last axis ends at n_active[0]."""
    lhs = _pair_or_one(lhs)
    kernel = _form(lhs, (grad,), True)
    m, k = lhs[0].shape
    n = grad.shape[1]
    itemsize = lhs[0].dtype.itemsize
    tn, tk = _tiles(kernel, k, n, tm, itemsize)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k // tk, n // tn, n_active[0]),
        in_specs=[pl.BlockSpec((tm, tk), lambda kk, j, i, tg, na: (i, kk))]
        * len(lhs)
        + [pl.BlockSpec((tm, tn), lambda kk, j, i, tg, na: (i, j))],
        out_specs=pl.BlockSpec((1, tk, tn), lambda kk, j, i, tg, na:
                               (tg[i], kk, j)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, kernel=kernel, n_tiles=m // tm),
        name="pt_gmm_bwd_dw",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), lhs[0].dtype),
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary"),
                  _vmem_bytes(kernel, tm, tk, tn, itemsize)),
    )(tile_group, n_active, *lhs, grad)


# ---------------------------------------------------------------------------
# the same products in plain XLA on the same layout: what the kernels
# are tested against, and the implementation off the chip
# ---------------------------------------------------------------------------

def gmm_xla(lhs, rhs, tile_group, n_active, tm, transpose_rhs=False,
            gated=()):
    lhs = _pair_or_one(lhs)
    x = _swiglu(*lhs) if len(lhs) == 2 else lhs[0]
    m, k = x.shape
    t = m // tm
    out = jnp.einsum("tmk,tnk->tmn" if transpose_rhs else "tmk,tkn->tmn",
                     x.reshape(t, tm, k), rhs[tile_group],
                     preferred_element_type=jnp.float32)
    live = (jnp.arange(t) < n_active[0])[:, None, None]
    out = jnp.where(live, out, 0.0).reshape(m, -1)
    return _swiglu_grad(*gated, out) if gated else out.astype(x.dtype)


def tgmm_xla(lhs, grad, tile_group, n_active, tm, n_groups):
    lhs = _pair_or_one(lhs)
    x = _swiglu(*lhs) if len(lhs) == 2 else lhs[0]
    m, k = x.shape
    t = m // tm
    per_tile = jnp.einsum("tmk,tmn->tkn", x.reshape(t, tm, k),
                          grad.reshape(t, tm, -1),
                          preferred_element_type=jnp.float32)
    live = jnp.arange(t) < n_active[0]
    own = (tile_group[:, None] == jnp.arange(n_groups)[None]) \
        & live[:, None]
    return jnp.einsum("tg,tkn->gkn", own.astype(jnp.float32),
                      jnp.where(live[:, None, None], per_tile, 0.0)
                      ).astype(x.dtype)


def row_buffer(shape, dtype, impl):
    """A row array nothing has written yet, for a caller that writes
    the rows that came itself: what a grouped matmul's output is past
    n_active, all of it.  A kernel that writes nothing, so no fill is
    paid for rows nobody reads (in interpret mode they come back NaN,
    as a grid's unvisited rows do); in plain XLA an empty array."""
    if impl == "xla":
        return jnp.empty(shape, dtype)
    return pl.pallas_call(
        lambda out_ref: None, name="pt_row_buffer",
        out_shape=jax.ShapeDtypeStruct(shape, dtype),
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        interpret=impl == "interpret")()


def _count_tiles(kernel, k, n, tm, itemsize):
    """paddle_tpu_kernel_impl_total{kernel="moe_gmm_tile",
    impl="<tn>x<tk>"}, once a grouped-matmul call that runs a kernel:
    a step's counters say which block shapes it ran.  (How many row
    tiles its grid ran is a run-time number: moe_experts' Load.)"""
    from paddle_tpu.ops import pallas_kernels as pk

    pk._count_impl("moe_gmm_tile",
                   "%dx%d" % _tiles(kernel, k, n, tm, itemsize))


def gmm(lhs, rhs, tile_group, n_active, tm, impl, transpose_rhs=False,
        gated=()):
    if impl == "xla":
        return gmm_xla(lhs, rhs, tile_group, n_active, tm, transpose_rhs,
                       gated)
    pair = _pair_or_one(lhs)
    _count_tiles(_form(pair, gated, False), pair[0].shape[1],
                 rhs.shape[1 if transpose_rhs else 2], tm,
                 pair[0].dtype.itemsize)
    return gmm_pallas(lhs, rhs, tile_group, n_active, tm,
                      transpose_rhs=transpose_rhs,
                      interpret=impl == "interpret", gated=gated)


def tgmm(lhs, grad, tile_group, n_active, tm, n_groups, impl):
    if impl == "xla":
        return tgmm_xla(lhs, grad, tile_group, n_active, tm, n_groups)
    pair = _pair_or_one(lhs)
    _count_tiles(_form(pair, (grad,), True), pair[0].shape[1],
                 grad.shape[1], tm, pair[0].dtype.itemsize)
    return tgmm_pallas(lhs, grad, tile_group, n_active, tm, n_groups,
                       interpret=impl == "interpret")

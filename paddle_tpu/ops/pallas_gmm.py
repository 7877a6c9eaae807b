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


def _tile(dim, target):
    """Largest multiple of 128 that divides `dim` and is <= target, else
    the whole dimension (a block equal to the array's extent is legal
    whatever its size).  A width with no larger divisor gets narrow
    blocks: 1,408 = 11 x 128 with 11 prime yields 128, where 1,024
    yields 1,024 (target 1024) and 512 (target 512); nothing pads a
    width to a friendlier one."""
    best = None
    for t in range(128, min(dim, target) + 1, 128):
        if dim % t == 0:
            best = t
    return best or dim


def _tiles(k, n):
    """(tn, tk): the block along the output width n and along the
    contraction (gmm) or the other output axis (tgmm) k."""
    return _tile(n, 1024), _tile(k, 512)


def _gmm_kernel(tg_ref, na_ref, x_ref, w_ref, o_ref, acc_ref, *,
                transpose_rhs):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    dims = (((1,), (1,)), ((), ())) if transpose_rhs \
        else (((1,), (0,)), ((), ()))
    acc_ref[...] += lax.dot_general(
        x_ref[...], w_ref[0], dims, preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _tgmm_kernel(tg_ref, na_ref, x_ref, g_ref, o_ref, acc_ref, *,
                 n_tiles):
    i = pl.program_id(2)
    here = tg_ref[i]
    first = (i == 0) | (tg_ref[jnp.maximum(i - 1, 0)] != here)
    last = (i == na_ref[0] - 1) | (tg_ref[jnp.minimum(i + 1, n_tiles - 1)]
                                   != here)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += lax.dot_general(
        x_ref[...], g_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(last)
    def _store():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _params(interpret, semantics):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


@functools.partial(jax.jit, static_argnames=(
    "tm", "transpose_rhs", "interpret"))
def gmm_pallas(lhs, rhs, tile_group, n_active, tm, transpose_rhs=False,
               interpret=False):
    """lhs [M, K] (M a multiple of tm), rhs [G, K, N] (or [G, N, K] with
    transpose_rhs), tile_group [M/tm] int32, n_active [1] int32 ->
    [M, N] in lhs's dtype; rows of tiles past n_active undefined.  The
    grid visits the n_active[0] >= 1 tiles that hold rows and no other."""
    m, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tn, tk = _tiles(k, n)
    if transpose_rhs:
        w_spec = pl.BlockSpec(
            (1, tn, tk), lambda j, i, kk, tg, na: (tg[i], j, kk))
    else:
        w_spec = pl.BlockSpec(
            (1, tk, tn), lambda j, i, kk, tg, na: (tg[i], kk, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // tn, n_active[0], k // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda j, i, kk, tg, na: (i, kk)),
            w_spec],
        out_specs=pl.BlockSpec((tm, tn), lambda j, i, kk, tg, na: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, transpose_rhs=transpose_rhs),
        name="pt_gmm_bwd_dx" if transpose_rhs else "pt_gmm_fwd",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        interpret=interpret,
        **_params(interpret, ("parallel", "arbitrary", "arbitrary")),
    )(tile_group, n_active, lhs, rhs)


@functools.partial(jax.jit, static_argnames=(
    "tm", "n_groups", "interpret"))
def tgmm_pallas(lhs, grad, tile_group, n_active, tm, n_groups,
                interpret=False):
    """lhs [M, K], grad [M, N] -> [G, K, N] in lhs's dtype: for each
    group the sum over its tiles of lhs[t]^T @ grad[t].  Every group
    has at least one tile (the layout's guarantee), so every output
    block is written.  The grid's last axis ends at n_active[0]."""
    m, k = lhs.shape
    n = grad.shape[1]
    tn, tk = _tiles(k, n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(k // tk, n // tn, n_active[0]),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda kk, j, i, tg, na: (i, kk)),
            pl.BlockSpec((tm, tn), lambda kk, j, i, tg, na: (i, j))],
        out_specs=pl.BlockSpec((1, tk, tn), lambda kk, j, i, tg, na:
                               (tg[i], kk, j)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, n_tiles=m // tm),
        name="pt_gmm_bwd_dw",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_groups, k, n), lhs.dtype),
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary")),
    )(tile_group, n_active, lhs, grad)


# ---------------------------------------------------------------------------
# the same three products in plain XLA on the same layout: what the
# kernels are tested against, and the implementation off the chip
# ---------------------------------------------------------------------------

def gmm_xla(lhs, rhs, tile_group, n_active, tm, transpose_rhs=False):
    m, k = lhs.shape
    t = m // tm
    w = rhs[tile_group]                       # [T, K, N] or [T, N, K]
    spec = "tmk,tnk->tmn" if transpose_rhs else "tmk,tkn->tmn"
    out = jnp.einsum(spec, lhs.reshape(t, tm, k), w,
                     preferred_element_type=jnp.float32)
    live = (jnp.arange(t) < n_active[0])[:, None, None]
    return jnp.where(live, out, 0.0).astype(lhs.dtype).reshape(m, -1)


def tgmm_xla(lhs, grad, tile_group, n_active, tm, n_groups):
    m, k = lhs.shape
    t = m // tm
    per_tile = jnp.einsum("tmk,tmn->tkn", lhs.reshape(t, tm, k),
                          grad.reshape(t, tm, -1),
                          preferred_element_type=jnp.float32)
    live = jnp.arange(t) < n_active[0]
    own = (tile_group[:, None] == jnp.arange(n_groups)[None]) \
        & live[:, None]
    return jnp.einsum("tg,tkn->gkn", own.astype(jnp.float32),
                      jnp.where(live[:, None, None], per_tile, 0.0)
                      ).astype(lhs.dtype)


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


def _count_tiles(k, n):
    """paddle_tpu_kernel_impl_total{kernel="moe_gmm_tile",
    impl="<tn>x<tk>"}, once a grouped-matmul call that runs a kernel:
    a step's counters say which block shapes it ran.  (How many row
    tiles its grid ran is a run-time number: moe_experts' Load.)"""
    from paddle_tpu.ops import pallas_kernels as pk

    pk._count_impl("moe_gmm_tile", "%dx%d" % _tiles(k, n))


def gmm(lhs, rhs, tile_group, n_active, tm, impl, transpose_rhs=False):
    if impl == "xla":
        return gmm_xla(lhs, rhs, tile_group, n_active, tm, transpose_rhs)
    _count_tiles(lhs.shape[1], rhs.shape[1 if transpose_rhs else 2])
    return gmm_pallas(lhs, rhs, tile_group, n_active, tm,
                      transpose_rhs=transpose_rhs,
                      interpret=impl == "interpret")


def tgmm(lhs, grad, tile_group, n_active, tm, n_groups, impl):
    if impl == "xla":
        return tgmm_xla(lhs, grad, tile_group, n_active, tm, n_groups)
    _count_tiles(lhs.shape[1], grad.shape[1])
    return tgmm_pallas(lhs, grad, tile_group, n_active, tm, n_groups,
                       interpret=impl == "interpret")

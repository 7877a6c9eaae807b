"""Ops of the 2024-26 decoder block: RMSNorm, rotary embedding with YaRN
frequencies, SwiGLU, the top-k router (sigmoid scores with a selection
bias, or softmax scores), the grouped expert feed-forward over the
experts this chip holds, and the two halves of a manifold-constrained
hyper-connection (n residual streams mixed by a Sinkhorn-normalised
matrix).

Equations: docs/XING4_BLOCK.md and docs/DSV2_BLOCK.md.  Attention,
routing and experts follow the DeepSeek-V2 and -V3 reports
(arXiv:2405.04434, 2.1-2.2; arXiv:2412.19437, 2.1.1-2.1.2), the
residual path mHC (arXiv:2512.24880) over Hyper-Connections
(arXiv:2409.19606).  models/xing4.py and models/deepseek_v2.py build
their blocks from these through layers/llm.py.

Precision under AMP (contrib/mixed_precision): statistics, router
scores, mixing coefficients and the Sinkhorn iterations are computed in
float32 whatever the activations' dtype; only what is written back to
the residual streams or handed to a matmul follows the input's dtype.

Every compute runs under a jax.named_scope (pt_mla, pt_moe_route,
pt_moe_experts, pt_mhc, pt_rms_norm, pt_swiglu) so that a reader of the
compiled step's HLO metadata can tell the XLA fusions apart.
"""

from __future__ import annotations

import contextlib
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.core.registry import REQUIRED, register_op
from paddle_tpu.ops import pallas_mhc, pallas_rotary
from paddle_tpu.ops.pallas_mhc import sinkhorn  # noqa: F401 (its users' name)

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST


# ---------------------------------------------------------------------------
# RMSNorm, SwiGLU
# ---------------------------------------------------------------------------

def _rms(xf, eps):
    return xf * lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                          + eps)


@register_op("rms_norm", inputs=("X", "Scale"), outputs=("Y",),
             attrs={"epsilon": 1e-6, "unit_offset": False})
def rms_norm(ins, attrs):
    """Y = X / sqrt(mean(X^2, last axis) + epsilon) * Scale, the
    statistic in float32, Y in X's dtype.  unit_offset: times
    (1 + Scale), the scale held as its distance from one."""
    x = ins["X"]
    with jax.named_scope("pt_rms_norm"):
        y = _rms(x.astype(_F32), attrs["epsilon"])
        scale = ins["Scale"].astype(_F32)
        if attrs.get("unit_offset"):
            scale = 1.0 + scale
        return {"Y": (y * scale).astype(x.dtype)}


@register_op("swiglu", inputs=("Gate", "Up"), outputs=("Out",))
def swiglu(ins, attrs):
    """Out = silu(Gate) * Up, in float32, written in Gate's dtype."""
    g = ins["Gate"]
    with jax.named_scope("pt_swiglu"):
        out = jax.nn.silu(g.astype(_F32)) * ins["Up"].astype(_F32)
        return {"Out": out.astype(g.dtype)}


# ---------------------------------------------------------------------------
# rotary embedding, YaRN frequencies
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def yarn_inv_freq(dim, theta, factor, original_max, beta_fast, beta_slow):
    """Inverse frequencies [dim/2] of a rotary embedding scaled by YaRN
    (Peng et al. 2023, as the deepseek_v3 modelling code computes them):
    dimensions that turn more than beta_fast times over the original
    context keep theta^(-2i/dim), those that turn fewer than beta_slow
    times are divided by `factor`, and a linear ramp joins the two
    correction dimensions.  factor 1 is the plain embedding."""
    pos = theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if factor == 1:
        return (1.0 / pos).astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(original_max / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp                      # 1: extrapolate (unscaled)
    inv = (1.0 / (factor * pos)) * (1.0 - keep) + (1.0 / pos) * keep
    return inv.astype(np.float32)


def _rotary_xla(x, rd, pairing, inv, mscale):
    """The op as an XLA graph, X [B, T, H, D]: the partner found by a
    reshape of the last axis and a flip."""
    t, d = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=_F32)[:, None] * jnp.asarray(inv)[None]
    if pairing == "halves":
        cos = (jnp.cos(ang) * mscale)[None, :, None, None, :]
        sin = (jnp.sin(ang) * mscale)[None, :, None, None, :]
        rot = x[..., d - rd:].astype(_F32).reshape(
            x.shape[:-1] + (2, rd // 2))
        # (a, b) -> (a cos - b sin, b cos + a sin), a the first half
        turned = jnp.flip(rot, -2) * jnp.asarray([[-1.0], [1.0]], _F32)
        out = (rot * cos + turned * sin).reshape(
            x.shape[:-1] + (rd,)).astype(x.dtype)
        if rd < d:
            out = jnp.concatenate([x[..., :d - rd], out], axis=-1)
        return out
    # the pairs that pass through turn by the angle 0.  One product
    # over all of D: a slice and a concatenate would each cost a
    # copy of X forward and a padded copy of its gradient backward
    keep = (d - rd) // 2
    cos = jnp.pad(jnp.cos(ang) * mscale, ((0, 0), (keep, 0)),
                  constant_values=1.0)[None, :, None, :, None]
    sin = jnp.pad(jnp.sin(ang) * mscale, ((0, 0), (keep, 0))
                  )[None, :, None, :, None]
    pairs = x.astype(_F32).reshape(x.shape[:-1] + (d // 2, 2))
    # (a, b) -> (a cos - b sin, a sin + b cos)
    turned = jnp.flip(pairs, -1) * jnp.asarray([-1.0, 1.0], _F32)
    out = pairs * cos + turned * sin
    return out.reshape(x.shape).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _rotary_kernel(x, geom, interpret, back=False):
    """pt_rotary over X [B, T, H D]; geom = (D, pairing, mscale,
    yarn_inv_freq's arguments).  `back`: by the negative angle, which
    is the op's transpose, so the kernel differentiates itself."""
    d, pairing, mscale, freq = geom
    rd = freq[0]
    cos, sin = pallas_rotary.tables(x.shape[1], d, rd, pairing,
                                    yarn_inv_freq(*freq), mscale, back=back)
    with _kernel_call("rotary_embedding_grad" if back
                      else "rotary_embedding"):
        return pallas_rotary.rotary_pallas(
            x, cos, sin, d=d,
            rule=pallas_rotary.partner_rule(d, rd, pairing),
            interpret=interpret)


def _rotary_kernel_fwd(x, geom, interpret, back):
    return _rotary_kernel(x, geom, interpret, back), None


def _rotary_kernel_bwd(geom, interpret, back, _, g):
    # traced under the forward's name stack: the op's scope is on it
    return (_rotary_kernel(g, geom, interpret, not back),)


_rotary_kernel.defvjp(_rotary_kernel_fwd, _rotary_kernel_bwd)


@register_op("rotary_embedding", inputs=("X",), outputs=("Out",),
             attrs={"rotary_dim": 0, "theta": 10000.0, "factor": 1.0,
                    "original_max_position": 4096, "beta_fast": 32.0,
                    "beta_slow": 1.0, "mscale": 1.0,
                    "pairing": "interleaved", "n_head": 0, "impl": ""})
def rotary_embedding(ins, attrs):
    """X [B, T, H, D], or with `n_head` = H the projection as it comes,
    [B, T, H D]: rotates the LAST rotary_dim entries of every head's D
    (0: all of D) by the angle position * inv_freq[i], positions 0..T-1
    along axis 1; the leading D - rotary_dim entries pass through.
    `pairing` says which two entries turn together: "interleaved" (the
    default, deepseek_v3's) the neighbours (x[2i], x[2i+1]), "halves"
    (the Llama lineage's rotate_half) the entries (x[i], x[i + rd/2])
    of the rotated part.  The frequencies are a constant built from the
    attributes (yarn_inv_freq); cos and sin are multiplied by `mscale`.
    Float32 inside, Out in X's shape and dtype.

    impl: "" (the kernel pt_rotary on a TPU where it can tile X, H D
    whole lane tiles and T a multiple of 32: one pass over X where it
    lies, its gradient the same kernel at the negative angle,
    ops/pallas_rotary.py; the XLA graph elsewhere), "pallas",
    "interpret", "xla"; paddle_tpu_kernel_impl_total{kernel="rotary"}
    says which ran."""
    from paddle_tpu.ops import pallas_kernels as pk

    x = ins["X"]
    heads = attrs.get("n_head") or 0
    if heads and (x.ndim != 3 or x.shape[-1] % heads):
        raise ValueError("rotary_embedding: n_head %d takes X [B, T, H D], "
                         "not %s" % (heads, x.shape,))
    if not heads and x.ndim != 4:
        raise ValueError("rotary_embedding: X %s is not [B, T, H, D] (a "
                         "flat [B, T, H D] names its n_head)" % (x.shape,))
    d = x.shape[-1] // heads if heads else x.shape[-1]
    rd = attrs["rotary_dim"] or d
    if rd % 2 or (d - rd) % 2:
        raise ValueError("rotary_embedding: rotary_dim %d of %d must "
                         "leave whole pairs on both sides" % (rd, d))
    pairing = attrs["pairing"]
    if pairing not in ("interleaved", "halves"):
        raise ValueError("rotary_embedding: pairing %r is neither "
                         "'interleaved' nor 'halves'" % pairing)
    freq = (rd, float(attrs["theta"]), float(attrs["factor"]),
            int(attrs["original_max_position"]), float(attrs["beta_fast"]),
            float(attrs["beta_slow"]))
    b, t = x.shape[:2]
    width = math.prod(x.shape[2:])
    impl = attrs.get("impl") or pk._auto_impl()
    if impl != "xla" and pallas_rotary.blocks(t, width, d) is None:
        impl = "xla"
    pk._count_impl("rotary", impl)
    with jax.named_scope("pt_mla"):
        if impl == "xla":
            out = _rotary_xla(x.reshape(b, t, -1, d), rd, pairing,
                              yarn_inv_freq(*freq), attrs["mscale"])
        else:
            out = _rotary_kernel(
                x.reshape(b, t, width),
                (d, pairing, float(attrs["mscale"]), freq),
                impl == "interpret")
        return {"Out": out.reshape(x.shape)}


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

@register_op("moe_route", inputs=("X", "W", "Bias"),
             outputs=("TopkIdx", "TopkWeight", "Scores"),
             attrs={"k": REQUIRED, "routed_scaling_factor": 1.0,
                    "norm_topk_prob": True, "scoring_func": "sigmoid",
                    "n_group": 1, "topk_group": 1, "norm_topk_eps": 0.0},
             optional=("Bias",))
def moe_route(ins, attrs):
    """Scores over ALL experts, float32, by `scoring_func`: "sigmoid"
    (DeepSeek-V3 2.1.2) s = sigmoid(X W), "softmax" (DeepSeek-V2 2.2)
    s = softmax(X W) over the experts.  The k experts with the largest
    s + Bias are selected (the bias selects, it does not weigh; unbound
    it is zero, and softmax routing does not read it), their gates are
    routed_scaling_factor * s_e / (sum of the selected s +
    norm_topk_eps) (norm_topk_prob; the epsilon 0 unless the model's
    code adds one, as lfm2_moe's does) or routed_scaling_factor * s_e.
    X [.., C], W
    [C, E], Bias [E] -> TopkIdx int32, TopkWeight float32, both
    [.., k], and Scores = s, float32 [.., E]: what a balance loss
    reads, with a gradient to every expert's score.  No capacity:
    nothing is dropped here.

    Group-limited selection (DeepSeek-V3 2.1.2, `noaux_tc`) with
    n_group > 1: the E experts are n_group groups of E / n_group
    consecutive ids, a group's score is the sum of its two largest
    s + Bias, the topk_group best groups are kept, and the k experts
    are the largest s + Bias among the kept groups' (k <= topk_group E
    / n_group).  n_group 1 is the selection over all experts and the
    program of before the option."""
    x, w = ins["X"], ins["W"]
    scoring = attrs["scoring_func"]
    if scoring not in ("sigmoid", "softmax"):
        raise ValueError("moe_route: scoring_func %r is neither "
                         "'sigmoid' nor 'softmax'" % (scoring,))
    from paddle_tpu.ops import pallas_kernels as pk

    n_group, topk_group = attrs["n_group"], attrs["topk_group"]
    per_group = w.shape[-1] // max(n_group, 1)
    # whole groups, of two experts or more where there are several (a
    # group's score is the sum of its two best), enough kept for k
    if not (n_group >= 1 and w.shape[-1] % n_group == 0
            and (n_group == 1 or per_group >= 2)
            and 1 <= topk_group <= n_group
            and attrs["k"] <= topk_group * per_group):
        raise ValueError(
            "moe_route: %d experts in n_group %d with topk_group %d "
            "cannot give k = %d" % (w.shape[-1], n_group, topk_group,
                                    attrs["k"]))
    pk._count_impl("moe_route_scoring", scoring)
    if n_group > 1:
        pk._count_impl("moe_route_groups",
                       "%dof%d" % (topk_group, n_group))
    with jax.named_scope("pt_moe_route"):
        z = jnp.matmul(x.astype(_F32), w.astype(_F32), precision=_HIGHEST)
        if scoring == "softmax":
            s = ranked = jax.nn.softmax(z, axis=-1)
        else:
            s = ranked = jax.nn.sigmoid(z)
            if ins.get("Bias") is not None:
                ranked = s + ins["Bias"].astype(_F32)
        if n_group > 1:
            with jax.named_scope("pt_moe_route_groups"):
                grouped = ranked.reshape(ranked.shape[:-1]
                                         + (n_group, per_group))
                _, best = lax.top_k(
                    jnp.sum(lax.top_k(grouped, 2)[0], axis=-1), topk_group)
                kept = jnp.any(best[..., None] == jnp.arange(n_group),
                               axis=-2)
                ranked = jnp.where(kept[..., None], grouped,
                                   -jnp.inf).reshape(ranked.shape)
        _, idx = lax.top_k(ranked, attrs["k"])
        sel = jnp.take_along_axis(s, idx, axis=-1)
        if attrs["norm_topk_prob"]:
            total = jnp.sum(sel, axis=-1, keepdims=True)
            if attrs["norm_topk_eps"]:
                total = total + attrs["norm_topk_eps"]
            sel = sel / total
        return {"TopkIdx": idx.astype(jnp.int32),
                "TopkWeight": sel * attrs["routed_scaling_factor"],
                "Scores": s}


# ---------------------------------------------------------------------------
# the experts this chip holds
# ---------------------------------------------------------------------------

def _group_layout(idx, held, tm):
    """Where each token-expert pair goes when the pairs routed to held
    experts are sorted by expert and every group is padded to whole
    tiles of tm rows (at least one tile a group).

    idx [N, k] int32 expert ids, held: tuple of the expert ids held.
    Returns a dict: dest [N, k] the padded row of each pair (row 0
    where not `mine`), mine [N, k] bool, slot [N, k] the held expert's
    place in `held` (G where not `mine`), row_pair [M] the pair (n * k +
    j) that feeds each padded row (undefined where not row_live),
    row_live [M] bool, tile_group [M / tm] int32, n_active [1] int32,
    sizes [G] int32 the pairs routed to each held expert, with
    M = (ceil(N k / tm) + G) * tm rows: the worst case, every pair
    routed here.  The groups are laid out in stack order from row 0,
    so the rows that hold anything are a PREFIX of every row array,
    [0, n_active * tm): the one bound of the kernels' grids and of the
    op's own row work (_over_live_rows).  Only these index vectors are
    made for all M rows."""
    n, k = idx.shape
    g = len(held)
    p = n * k
    n_tiles = -(-p // tm) + g
    local = jnp.full(idx.shape, g, jnp.int32)
    for j, e in enumerate(held):
        local = jnp.where(idx == e, j, local)
    key = local.reshape(p)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    groups = jnp.arange(g, dtype=jnp.int32)
    sizes = jnp.sum((key[:, None] == groups[None]).astype(jnp.int32), 0)
    tiles = jnp.maximum(-(-sizes // tm), 1)
    start = jnp.cumsum(sizes) - sizes              # first sorted position
    tile_end = jnp.cumsum(tiles)
    pstart = (tile_end - tiles) * tm               # first padded row
    n_active = tile_end[-1:]
    tile = jnp.arange(n_tiles, dtype=jnp.int32)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right"),
        g - 1).astype(jnp.int32)
    # rows -> pairs, a tile at a time: a tile's rows share its group,
    # so what a row needs of its group is looked up once a tile
    off = (tile * tm - pstart[tile_group])[:, None] \
        + jnp.arange(tm, dtype=jnp.int32)[None]            # [tiles, tm]
    row_live = (off < sizes[tile_group][:, None]) \
        & (tile < n_active[0])[:, None]
    row_pair = order[jnp.clip(start[tile_group][:, None] + off, 0, p - 1)]
    # pairs -> rows: the rank of a pair inside its group is its sorted
    # position less the group's first
    rank = jnp.argsort(order).astype(jnp.int32)    # inverse permutation
    safe = jnp.minimum(key, g - 1)
    dest = jnp.where(key < g, pstart[safe] + rank - start[safe], 0)
    return {"dest": dest.reshape(n, k), "mine": local < g, "slot": local,
            "row_pair": row_pair.reshape(-1),
            "row_live": row_live.reshape(-1), "tile_group": tile_group,
            "n_active": n_active.astype(jnp.int32), "sizes": sizes}


# row tiles a step of _over_live_rows: 2,048 rows at 256 a tile.  Chosen
# once on the chip (PERF.md, PR 37: 4, 8 and 16 tiles read within 2% of
# each other at every live count; 8 and 16 a little ahead of 4)
_CHUNK_TILES = 8


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _over_live_rows(fn, outs, k, tm, impl, lay, *operands):
    """The op's own work by padded row, over the rows that came.

    outs: (width or None, dtype) of each row array to make, [M, width]
    or [M]; fn(rows, k, lay, *operands) -> a chunk of each, where
    rows(a) is the chunk's slice of a row array `a`.  Runs fn over
    chunks of _CHUNK_TILES row tiles of the live prefix
    [0, n_active * tm) and over nothing past it: the trip count is read
    from n_active at run time, as the kernels' grids are, and each
    chunk is written into its place in a carried buffer.  The worst
    case is the same loop, longer.  A last chunk that would pass M
    starts earlier instead: fn is a map over rows, so a row written
    twice gets the same value.  The buffers are made unwritten
    (pallas_gmm.row_buffer) and the rows past the last chunk stay so:
    not defined, never read, like the rows a kernel's grid did not
    reach.  A jit with fn static: a step traces and lowers each of the
    three loops once for its expert layers of one shape, forward and
    replay alike (without, first_call_s +2.3 s in `dsv2`: PERF.md)."""
    from paddle_tpu.ops.pallas_gmm import row_buffer

    m = lay["row_pair"].shape[0]
    size = min(_CHUNK_TILES * tm, m)
    trips = -(-(lay["n_active"][0] * tm) // size)

    def step(i, bufs):
        r0 = jnp.minimum(i * size, m - size)
        chunks = fn(lambda a: lax.dynamic_slice_in_dim(a, r0, size),
                    k, lay, *operands)
        return tuple(lax.dynamic_update_slice_in_dim(b, c, r0, 0)
                     for b, c in zip(bufs, chunks))

    return lax.fori_loop(0, trips, step, tuple(
        row_buffer((m,) if w is None else (m, w), dt, impl)
        for w, dt in outs))


def _token_rows(rows, k, lay, x):
    """x [N, ..] by token -> the chunk's rows, each its token's entry;
    zero where a row holds no pair."""
    picked = jnp.take(x, rows(lay["row_pair"]) // k, axis=0)
    return jnp.where(rows(lay["row_live"])[:, None], picked, 0),


def _cotangent_rows(rows, k, lay, gate, gf, ys):
    """d ys and each row's d gate: a row's token's cotangent times the
    row's gate is d ys, and against the row's expert output it is the
    pair's d gate."""
    g_tok, = _token_rows(rows, k, lay, gf)
    row_gate = jnp.where(rows(lay["row_live"]), jnp.take(
        gate.reshape(-1), rows(lay["row_pair"])), 0.0)
    return ((g_tok * row_gate[:, None]).astype(ys.dtype),
            jnp.sum(rows(ys).astype(_F32) * g_tok, axis=-1))


def _sum_rows(rows, k, lay, a, b):
    return rows(a).astype(_F32) + rows(b).astype(_F32),


@jax.jit
def _tokens_of_rows(lay, a, gate=None):
    """a [M, C] by row -> [N, C] float32: each token's sum over its k
    pairs, in order, of `gate` [N, k] times the pair's row; a pair
    whose expert is not held adds zero.  By token, not by row: a token
    has rows in up to k groups, and the alternative is a scatter-add.
    One gather of N rows a j: a [N, k, C] array would be laid out
    again with k padded to a sublane tile."""
    mine, dest = lay["mine"], lay["dest"]
    out = None
    for j in range(mine.shape[1]):
        picked = jnp.where(
            mine[:, j, None],
            jnp.take(a, dest[:, j], axis=0).astype(_F32), 0.0)
        if gate is not None:
            picked = picked * gate[:, j, None]
        out = picked if out is None else out + picked
    return out


def _combine(lay, a, gate, dtype, impl):
    """a [M, C] by row -> [N, C] in `dtype`: each token's sum over its
    held pairs, in pair order, of `gate` [N, k] times the pair's row.
    One Pallas kernel that reads the held pairs' rows
    (ops/pallas_moe_combine.py) where moe_experts made it a plan,
    _tokens_of_rows' gathers of every pair where not."""
    plan = lay.get("combine")
    if plan is None:
        return _tokens_of_rows(lay, a, gate).astype(dtype)
    from paddle_tpu.ops.pallas_moe_combine import moe_combine_pallas

    return moe_combine_pallas(a, plan, gate, out_dtype=dtype,
                              interpret=impl == "interpret")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _routed_experts(x, gate, wg, wu, wd, lay, k, tm, impl):
    return _routed_fwd(x, gate, wg, wu, wd, lay, k, tm, impl)[0]


def _routed_fwd(x, gate, wg, wu, wd, lay, k, tm, impl):
    """x [N, C], gate [N, k] float32 -> sum over a token's held experts
    of gate * SwiGLU_e(x), [N, C] in x's dtype.  Gathers only: a row
    gather lays the tokens out by expert, three grouped matmuls run
    over the tiles that hold rows, the third forming SwiGLU of the
    first two's outputs on its way in, and each token gathers its k
    rows back.  Everything indexed by padded row, the kernels and the
    gathers by row (_over_live_rows), ends at the last row tile that
    holds rows; what a row array holds past it is not defined, and
    nothing reads it.  Only the combine is by token (_combine)."""
    from paddle_tpu.ops.pallas_gmm import gmm

    tg, na = lay["tile_group"], lay["n_active"]
    dt = x.dtype
    xs, = _over_live_rows(_token_rows, ((x.shape[1], dt),), k, tm, impl,
                          lay, x)
    hg = gmm(xs, wg, tg, na, tm, impl)
    hu = gmm(xs, wu, tg, na, tm, impl)
    ys = gmm((hg, hu), wd, tg, na, tm, impl)
    out = _combine(lay, ys, gate, dt, impl)
    return out, (x, gate, wg, wu, wd, lay, xs, hg, hu, ys)


def _routed_bwd(k, tm, impl, res, g_out):
    from paddle_tpu.ops.pallas_gmm import gmm, tgmm

    x, gate, wg, wu, wd, lay, xs, hg, hu, ys = res
    tg, na = lay["tile_group"], lay["n_active"]
    n_groups = wg.shape[0]
    dt = x.dtype
    g_ys, row_dot = _over_live_rows(
        _cotangent_rows, ((x.shape[1], dt), (None, _F32)), k, tm, impl,
        lay, gate, g_out.astype(_F32), ys)
    d_gate = jnp.where(lay["mine"], jnp.take(row_dot, lay["dest"]), 0.0)
    d_wd = tgmm((hg, hu), g_ys, tg, na, tm, n_groups, impl)
    # d act stays the kernel's float32 accumulator: SwiGLU's gradient
    # is what it writes
    g_hg, g_hu = gmm(g_ys, wd, tg, na, tm, impl, transpose_rhs=True,
                     gated=(hg, hu))
    d_wg = tgmm(xs, g_hg, tg, na, tm, n_groups, impl)
    d_wu = tgmm(xs, g_hu, tg, na, tm, n_groups, impl)
    # d x: each token's rows of the float32 sum of the two products
    g_xs, = _over_live_rows(
        _sum_rows, ((x.shape[1], _F32),), k, tm, impl, lay,
        gmm(g_hg, wg, tg, na, tm, impl, transpose_rhs=True),
        gmm(g_hu, wu, tg, na, tm, impl, transpose_rhs=True))
    d_x = _combine(lay, g_xs, None, dt, impl)
    return d_x, d_gate.astype(gate.dtype), d_wg, d_wu, d_wd, None


_routed_experts.defvjp(_routed_fwd, _routed_bwd)


@register_op("moe_experts",
             inputs=("X", "TopkIdx", "TopkWeight", "WGate", "WUp", "WDown"),
             outputs=("Out", "Load"),
             attrs={"held": REQUIRED, "block_m": 0, "impl": ""})
def moe_experts(ins, attrs):
    """The routed experts' part of a sparse feed-forward that THIS chip
    computes: Out = sum over the token's selected experts that are in
    `held` of TopkWeight * W_down[e](silu(x W_gate[e]) * (x W_up[e])).

    held: the expert ids whose weights the stacks WGate/WUp [G, C, W]
    and WDown [G, W, C] hold, in stack order; the router ran over all
    experts.  A selected expert that is not held adds nothing, and no
    token is dropped: the token-expert pairs routed to held experts are
    sorted by expert and go through three grouped matmuls
    (ops/pallas_gmm.py) over row arrays of a static worst-case size
    with run-time group sizes.  All work by padded row ends at the last
    row tile that holds rows, n_active: a kernel's grid (SwiGLU and
    its gradient are formed inside the kernels, on their blocks), and
    the two gathers by row and d x's sum of two products, which run in
    loops over the live rows with a trip count read from n_active
    (_over_live_rows).  What a row array holds past that is not
    defined and not read.  There is no capacity: the worst case, every
    pair routed to one held expert, runs the same code with longer
    loops.  The combine alone is by token: with a Pallas impl one
    kernel that reads the rows of the pairs that are held and sums a
    token's in pair order (ops/pallas_moe_combine.py; it wants C a
    multiple of 128 and block_m of 16), else XLA's gathers of every
    pair's row; the same float32 products added in the same order.
    impl: "" (pallas on a TPU, xla elsewhere), "pallas", "interpret",
    "xla", the same form for each; block_m: rows a tile (0: 256).

    Load, float32 [G + 2]: what this execution was given, from the
    arrays that bound the kernels' grids (`_group_layout`'s sizes and
    n_active), not a recount: the pairs routed to each held expert in
    stack order, their sum, and the row tiles that hold rows.  No
    gradient flows to it; layers.moe_experts keeps it a row a step
    (layers.step_stat)."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops.pallas_moe_combine import combine_plan

    x = ins["X"]
    c = x.shape[-1]
    idx = ins["TopkIdx"].reshape(-1, ins["TopkIdx"].shape[-1])
    impl = attrs["impl"] or pk._auto_impl()
    tm = int(attrs["block_m"] or 256)
    held = tuple(int(e) for e in attrs["held"])
    pk._count_impl("moe_gmm", impl)
    pk._count_impl("moe_swiglu", "xla" if impl == "xla" else "in_kernel")
    with jax.named_scope("pt_moe_experts"):
        lay = _group_layout(idx, held, tm)
        # the combine's kernel where it can take the call's shapes,
        # XLA's gathers where not: one plan a layer, read by the
        # forward's combine, its replay and d x
        plan = None if impl == "xla" else combine_plan(
            lay["dest"], lay["slot"], len(held), c,
            lay["row_pair"].shape[0])
        pk._count_impl("moe_combine", impl if plan else "xla")
        if plan:
            lay["combine"] = plan
        gate = ins["TopkWeight"].reshape(idx.shape).astype(_F32)
        dt = x.dtype
        # see pallas_kernels._flash_attention_fwd: one call line
        with pk._obs_device.annotate("moe_experts"), pk._kernel_scope():
            out = _routed_experts(
                x.reshape(-1, c), gate, ins["WGate"].astype(dt),
                ins["WUp"].astype(dt), ins["WDown"].astype(dt), lay,
                idx.shape[-1], tm, impl)
        load = jnp.concatenate(
            [lay["sizes"], jnp.sum(lay["sizes"])[None], lay["n_active"]])
        return {"Out": out.reshape(x.shape), "Load": load.astype(_F32)}


# ---------------------------------------------------------------------------
# manifold-constrained hyper-connections: n residual streams
# ---------------------------------------------------------------------------

def _mhc_impl(x, kernels, impl=None):
    """The impl a hyper-connection half resolves to, counted as
    paddle_tpu_kernel_impl_total{kernel="mhc"}: `impl`, else pallas on
    a TPU and xla elsewhere; xla too where pallas_mhc.token_block finds
    no block for one of the op's `kernels` at X's shape and dtype."""
    from paddle_tpu.ops import pallas_kernels as pk

    impl = impl or pk._auto_impl()
    (_, n, t, c), size = x.shape, x.dtype.itemsize
    if impl != "xla" and not all(
            pallas_mhc.token_block(k, n, t, c, size) for k in kernels):
        impl = "xla"
    pk._count_impl("mhc", impl)
    return impl


@contextlib.contextmanager
def _kernel_call(name):
    """The scopes of a kernel entry; see
    pallas_kernels._flash_attention_fwd: one call line."""
    from paddle_tpu.ops import pallas_kernels as pk

    with pk._obs_device.annotate(name), pk._kernel_scope():
        yield


def _mhc_pre_xla(x, norm_scale, phi, alpha, bias, attrs):
    """(U, HPost, HRes): the XLA composition."""
    b, n, t, c = x.shape
    xf = x.astype(_F32)
    inv = lax.rsqrt(jnp.mean(jnp.square(xf), axis=(1, 3))
                    + attrs["eps"])                            # [B, T]
    # x~ Phi = rsqrt(..) * (X (NormScale * Phi)): X is read once
    w = (norm_scale.astype(_F32)[:, None]
         * phi.astype(_F32)).reshape(n, c, -1)
    p = jnp.einsum("bntc,ncw->bwt", xf, w, precision=_HIGHEST) \
        * inv[:, None, :]                                      # [B, W, T]
    alpha, bias = alpha.astype(_F32), bias.astype(_F32)
    h_pre = jax.nn.sigmoid(alpha[0] * p[:, :n] + bias[:n, None])
    h_post = 2 * jax.nn.sigmoid(alpha[1] * p[:, n:2 * n]
                                + bias[n:2 * n, None])
    raw = alpha[2] * p[:, 2 * n:].reshape(b, n, n, t) \
        + bias[2 * n:].reshape(n, n)[..., None]
    h_res = sinkhorn(jnp.clip(raw, attrs["clamp_min"], attrs["clamp_max"]),
                     attrs["sinkhorn_iters"], attrs["eps"],
                     row_axis=2, col_axis=1)
    u = jnp.sum(h_pre[..., None] * xf, axis=1)
    return u.astype(x.dtype), h_post, h_res


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _mhc_pre_kernel(x, norm_scale, phi, alpha, bias, attrs, interpret):
    """pt_mhc_pre_fwd; attrs: the op's, as sorted items (hashable)."""
    a = dict(attrs)
    with _kernel_call("mhc_pre"):
        return pallas_mhc.mhc_pre_fwd_pallas(
            x, norm_scale, phi, alpha, bias, iters=a["sinkhorn_iters"],
            eps=a["eps"], clamp=(a["clamp_min"], a["clamp_max"]),
            interpret=interpret)


def _mhc_pre_kernel_fwd(x, norm_scale, phi, alpha, bias, attrs, interpret):
    return (_mhc_pre_kernel(x, norm_scale, phi, alpha, bias, attrs,
                            interpret),
            (x, norm_scale, phi, alpha, bias))


def _mhc_pre_kernel_bwd(attrs, interpret, res, cts):
    x = res[0]
    a = dict(attrs)
    if not pallas_mhc.token_block("pre_bwd", *x.shape[1:], x.dtype.itemsize):
        # its blocks are the largest of the four kernels'
        _, vjp = jax.vjp(lambda *r: _mhc_pre_xla(*r, a), *res)
        return vjp(cts)
    # traced under the forward's name stack: the op's scope is on it
    with _kernel_call("mhc_pre_grad"):
        return pallas_mhc.mhc_pre_bwd_pallas(
            *res, *cts, iters=a["sinkhorn_iters"], eps=a["eps"],
            clamp=(a["clamp_min"], a["clamp_max"]), interpret=interpret)


_mhc_pre_kernel.defvjp(_mhc_pre_kernel_fwd, _mhc_pre_kernel_bwd)


def _mhc_pre(x, norm_scale, phi, alpha, bias, attrs, impl=None):
    impl = _mhc_impl(x, ("pre_fwd",), impl)
    with jax.named_scope("pt_mhc"):
        if impl == "xla":
            return _mhc_pre_xla(x, norm_scale, phi, alpha, bias, attrs)
        return _mhc_pre_kernel(x, norm_scale, phi, alpha, bias,
                               tuple(sorted(attrs.items())),
                               impl == "interpret")


@register_op("mhc_pre",
             inputs=("X", "NormScale", "Phi", "Alpha", "Bias"),
             outputs=("U", "HPost", "HRes"),
             attrs={"sinkhorn_iters": 20, "eps": 1e-6,
                    "clamp_min": -30.0, "clamp_max": 30.0})
def mhc_pre(ins, attrs):
    """The read half of a hyper-connection round a sublayer.  X
    [B, n, T, C], the n residual streams, stream-major: the last two
    axes are whole (sublane, lane) tiles, and a mix along n is
    elementwise over n slabs.  With x~ = RMSNorm_eps(vec(X)) *
    NormScale (vec over a token's n C entries, stream-major, [nC]) and
    p = x~ Phi, Phi [nC, 2n + n^2] = [phi_pre | phi_post | phi_res],
    Alpha [3], Bias [2n + n^2] in the same order:

        H_pre  = sigmoid(Alpha[0] p[:n] + Bias[:n])
        H_post = 2 sigmoid(Alpha[1] p[n:2n] + Bias[n:2n])
        H_res  = sinkhorn(clip(Alpha[2] mat(p[2n:]) + mat(Bias[2n:])))
        U      = H_pre X            the sublayer's input, [B, T, C]

    all in float32; U is written in X's dtype; HPost [B, n, T] and HRes
    [B, n, n, T] (HRes[b, i, j, t] weighs stream j in new stream i)
    stay float32 for mhc_post, tokens on the lane axis: the 20
    Sinkhorn rounds then work on [n, n, T] slabs instead of T padded
    [n, n] tiles.

    On a TPU, where ops/pallas_mhc.py can tile X (C whole lane tiles, T
    a multiple of 16, n <= 4), the forward is ONE kernel,
    pt_mhc_pre_fwd, that reads X once for the norm, the product, the
    gates, the rounds and U, and its gradient one, pt_mhc_pre_bwd;
    elsewhere the XLA composition.
    paddle_tpu_kernel_impl_total{kernel="mhc"} says which."""
    u, h_post, h_res = _mhc_pre(
        ins["X"], ins["NormScale"], ins["Phi"], ins["Alpha"], ins["Bias"],
        {k: attrs[k] for k in ("sinkhorn_iters", "eps", "clamp_min",
                               "clamp_max")})
    return {"U": u, "HPost": h_post, "HRes": h_res}


def _mhc_post_xla(x, y, h_post, h_res):
    xf = x.astype(_F32)
    # n^2 multiply-adds a stream element on the VPU, written as one
    # broadcast product summed over j: no slice of X, whose
    # transpose would pad a gradient back to X's size n times
    mixed = jnp.sum(h_res.astype(_F32)[..., None] * xf[:, None], axis=2)
    out = mixed + h_post.astype(_F32)[..., None] * y.astype(_F32)[:, None]
    return out.astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _mhc_post_kernel(x, y, h_post, h_res, interpret):
    """pt_mhc_post_fwd, differentiated by pt_mhc_post_bwd."""
    with _kernel_call("mhc_post"):
        return pallas_mhc.mhc_post_fwd_pallas(
            x, y, pallas_mhc.coef_rows(h_res, h_post), interpret=interpret)


def _mhc_post_kernel_fwd(x, y, h_post, h_res, interpret):
    return (_mhc_post_kernel(x, y, h_post, h_res, interpret),
            (x, y, h_post, h_res))


def _mhc_post_kernel_bwd(interpret, res, g):
    x, y, h_post, h_res = res
    # traced under the forward's name stack: the op's scope is on it
    with _kernel_call("mhc_post_grad"):
        dx, dy, dcoef = pallas_mhc.mhc_post_bwd_pallas(
            x, y, pallas_mhc.coef_rows(h_res, h_post), g,
            interpret=interpret)
    d_res, d_post = pallas_mhc.coef_cols(dcoef, h_res.shape, h_post.shape)
    return dx, dy, d_post.astype(h_post.dtype), d_res.astype(h_res.dtype)


_mhc_post_kernel.defvjp(_mhc_post_kernel_fwd, _mhc_post_kernel_bwd)


def _mhc_post(x, y, h_post, h_res, impl=None):
    impl = _mhc_impl(x, ("post_fwd", "post_bwd"), impl)
    with jax.named_scope("pt_mhc"):
        if impl == "xla":
            return _mhc_post_xla(x, y, h_post, h_res)
        return _mhc_post_kernel(x, y.astype(x.dtype), h_post, h_res,
                                impl == "interpret")


@register_op("mhc_post", inputs=("X", "Y", "HPost", "HRes"),
             outputs=("Out",))
def mhc_post(ins, attrs):
    """The write half: Out = H_res X + outer(H_post, Y), X [B, n, T, C],
    Y [B, T, C] the sublayer's output, HPost [B, n, T], HRes
    [B, n, n, T]; float32 arithmetic, Out in X's dtype.  The kernels
    pt_mhc_post_fwd / pt_mhc_post_bwd where mhc_pre's runs, under the
    same counter."""
    return {"Out": _mhc_post(ins["X"], ins["Y"], ins["HPost"], ins["HRes"])}

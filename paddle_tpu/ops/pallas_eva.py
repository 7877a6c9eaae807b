"""EVA attention (Zheng et al., "Efficient Attention via Control
Variates", arXiv:2302.04542, in the deterministic form of the EvaByte
release): a query scores two kinds of key in ONE softmax, the tokens of
its own window exactly and one learned summary for every chunk of every
earlier window.  docs/EVABYTE_BLOCK.md has the equations,
docs/FLASH_ATTENTION.md "The staircase" the grids.

Token-major throughout: q, k, v [B, T, H*D] as the projections leave
them, mu and phi [H, D], summaries [B, T/chunk, H*D].  With windows
ALIGNED to multiples of `window` (W) and chunks of `chunk` (c) tokens:

  pool        k~_j = sum_m softmax_m(mu . k_{cj+m}) k_{cj+m}
              v~_j = sum_m softmax_m(phi . k_{cj+m}) v_{cj+m}
  aggregate   query i, window w = i // W, sees the tokens t of window w
              with t <= i and the chunks j with c j // W < w; one
              softmax at `scale` over both.

Two entries, each an XLA form (plain jax.numpy: what runs off the TPU
and what the tests hold the kernels to) and a kernel form:

  eva_pool       pt_eva_pool_fwd / pt_eva_pool_bwd: K and V read once
                 where they lie, 1/chunk of them written; the backward
                 forms the two softmaxes again from K (they are 16
                 numbers a chunk) and keeps no residual.
  eva_attention  design (a) of ISSUE 55 with the merge inside: the
                 window part IS causal flash attention on the free
                 reshape [B T/W, W, H*D] (ops/pallas_kernels.py, its
                 kernels and its backward unchanged); the chunk part is
                 the staircase pt_eva_chunk_fwd / pt_eva_chunk_bwd over
                 (q, k~, v~), whose grid is the LIVE (q block,
                 chunk-key block) pairs and no other (`staircase`, two
                 scalar-prefetch arrays): with blocks that divide W and
                 W/c every pair is wholly visible or wholly dead, so
                 there is no mask and no step for a query's own or a
                 later window.  A q block's running softmax STARTS
                 from the window part's (out, lse), the state (acc, m,
                 l) = (out, lse, 1) of the rule `flash_attention_lse`
                 documents, so its last step writes the one softmax
                 over both key sets (a merge in XLA cost a third of the
                 forward: PERF.md section 6, PR 55).  The row statistic
                 is read where the window part's call left it, [B T/W,
                 H, W], a [1, bq] block along the lanes, never a [..,
                 W, 128] copy in HBM: the forward holds its scores keys
                 down, so its running statistics ARE such rows; the
                 backward stands the row up (`_column`).  Backward: given
                 the (Out, LSE) of the one softmax, each part's
                 backward kernel forms p = exp(s - LSE) and
                 dS = p (dO v^T - rowsum(dO Out)) over its own keys;
                 the staircase's dq starts from the window part's.

No [T, W] or [T, T/c] score array exists in HBM in either direction.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops import pallas_kernels as pk

_F32 = jnp.float32
_HIGHEST = lax.Precision.HIGHEST
_LANES = 128
_NEG_INF = pk._NEG_INF
# blocks pinned by tools/eva_price.py on a v5e (PERF.md section 6, PR 55)
_POOL_ROWS = 2048             # tokens a pooling grid step reads
_WINDOW_FWD_BLOCK = 2048      # the window part's forward: a window whole
_POOL_VMEM = 48 << 20         # what the summariser's kernels may ask for
_CHUNK_VMEM = 64 << 20        # what the staircase kernels may ask for
# the backward keeps a head's whole dq [T, 128] float32 in VMEM
_CHUNK_BWD_MAX_T = 32768


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------

def check_shapes(x, heads, window, chunk):
    """(b, t, d) of a token-major operand [B, T, H*D], or raises: the
    head count divides the width, the chunk the window, and T is whole
    chunks and, past one window, whole windows (a caller pads the END
    of a stream: causal, so nothing a real position reads changes)."""
    b, t, width = x.shape
    if width % heads:
        raise ValueError("eva_attention: %s is not [B, T, %d heads x D]"
                         % (x.shape, heads))
    if chunk < 1 or window % chunk:
        raise ValueError("eva_attention: chunk %d does not divide window "
                         "%d" % (chunk, window))
    if t % chunk or (t > window and t % window):
        raise ValueError(
            "eva_attention: %d tokens are not whole chunks of %d and, "
            "past one window, whole windows of %d: pad the end of the "
            "stream" % (t, chunk, window))
    return b, t, width // heads


def chunk_blocks(window, chunk, block_q=None, block_k=None):
    """(block_q, block_k) of the staircase: block_q divides the window
    and block_k the chunk keys a window gives (W/c), so that a block
    pair is wholly visible or wholly dead."""
    cpw = window // chunk
    bq = block_q or min(window, 2048)
    bk = block_k or min(cpw, 512)
    if window % bq or cpw % bk:
        raise ValueError("eva_attention: blocks %d x %d do not divide "
                         "the window %d and its %d chunk keys"
                         % (bq, bk, window, cpw))
    return bq, bk


def kernel_geom_ok(t, d, window, chunk):
    """Whether the kernels can tile these sizes: one head a 128-lane
    block, chunks of whole sublane tiles, a window's chunk keys whole
    bfloat16 sublane tiles, and a head's dq in VMEM."""
    rows = min(t, _POOL_ROWS)
    return d == _LANES and chunk % 8 == 0 and (window // chunk) % 16 == 0 \
        and t % rows == 0 and rows % (16 * chunk) == 0 \
        and t <= _CHUNK_BWD_MAX_T


def staircase(t, window, chunk, block_q, block_k, major="q"):
    """The staircase's grid, on the host: int32 arrays (qi, kj) of the
    live block pairs.  q block i lies in window i block_q // W and sees
    the chunk keys of the windows before it, chunk-key blocks 0 ..
    (i block_q // W) (W/c) / block_k - 1: every one of them whole, and
    none of its own or a later window.  major "q": a q block's pairs
    side by side, kj rising (the forward's running softmax); "k": a
    chunk-key block's pairs side by side (the backward's dk~, dv~)."""
    cpw = window // chunk
    pairs = [(i, j) for i in range(t // block_q)
             for j in range((i * block_q // window) * cpw // block_k)]
    if major == "k":
        pairs.sort(key=lambda p: (p[1], p[0]))
    qi = np.array([p[0] for p in pairs], np.int32)
    kj = np.array([p[1] for p in pairs], np.int32)
    return qi, kj


# ---------------------------------------------------------------------------
# the XLA forms
# ---------------------------------------------------------------------------

def _heads(x, heads):
    b, t, width = x.shape
    return x.reshape(b, t, heads, width // heads)


def eva_pool_xla(k, v, mu, phi, heads, chunk):
    """(k~, v~) [B, T/chunk, H*D] in k's and v's dtypes; float32
    inside."""
    b, t, width = k.shape
    d = width // heads
    kf = k.astype(_F32).reshape(b, t // chunk, chunk, heads, d)
    vf = v.astype(_F32).reshape(b, t // chunk, chunk, heads, d)

    def weights(vec):
        return jax.nn.softmax(jnp.einsum(
            "bjmhd,hd->bjmh", kf, vec.astype(_F32), precision=_HIGHEST),
            axis=2)

    def pooled(w, x, dtype):
        return jnp.einsum("bjmh,bjmhd->bjhd", w, x, precision=_HIGHEST) \
            .reshape(b, t // chunk, width).astype(dtype)

    return pooled(weights(mu), kf, k.dtype), \
        pooled(weights(phi), vf, v.dtype)


def eva_attention_xla(q, k, v, ks, vs, heads, window, chunk, scale):
    """(out [B, T, H*D] in q's dtype, lse float32 [B T/W, H, W], a
    window a row as the kernels keep it): the two score arrays side by
    side, one softmax."""
    b, t, width = q.shape
    qf, kf, vf, ksf, vsf = (_heads(x.astype(_F32), heads)
                            for x in (q, k, v, ks, vs))
    i = jnp.arange(t)[:, None]
    tok = jnp.arange(t)[None, :]
    tok_ok = (tok // window == i // window) & (tok <= i)
    chk_ok = (jnp.arange(t // chunk)[None, :] * chunk) // window \
        < i // window
    s = jnp.concatenate([
        jnp.where(tok_ok, jnp.einsum("bqhd,bkhd->bhqk", qf, kf,
                                     precision=_HIGHEST) * scale,
                  _NEG_INF),
        jnp.where(chk_ok, jnp.einsum("bqhd,bkhd->bhqk", qf, ksf,
                                     precision=_HIGHEST) * scale,
                  _NEG_INF)], axis=-1)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    out = jnp.einsum("bhqk,bkhd->bqhd", p[..., :t], vf,
                     precision=_HIGHEST) \
        + jnp.einsum("bhqk,bkhd->bqhd", p[..., t:], vsf,
                     precision=_HIGHEST)
    w = min(window, t)
    lse = lse.reshape(b, heads, t // w, w).transpose(0, 2, 1, 3)
    return out.reshape(b, t, width).astype(q.dtype), \
        lse.reshape(b * t // w, heads, w)


# ---------------------------------------------------------------------------
# the summariser
# ---------------------------------------------------------------------------

def _lane_sum(x):
    """Sum over the 128 lanes, on every lane."""
    return jnp.broadcast_to(jnp.sum(x, axis=-1, keepdims=True), x.shape)


def _pool_weights(k3, vec):
    """softmax over a chunk's positions of vec . k, [n, chunk, 128],
    the same number on every lane."""
    a = _lane_sum(k3 * vec)
    e = jnp.exp(a - jnp.max(a, axis=1, keepdims=True))
    return e / jnp.sum(e, axis=1, keepdims=True)


def _pool_fwd_kernel(k_ref, v_ref, mu_ref, phi_ref, ks_ref, vs_ref, *,
                     chunk):
    n = k_ref.shape[1] // chunk
    k3 = k_ref[0].astype(_F32).reshape(n, chunk, _LANES)
    v3 = v_ref[0].astype(_F32).reshape(n, chunk, _LANES)
    ks_ref[0] = jnp.sum(_pool_weights(k3, mu_ref[...]) * k3,
                        axis=1).astype(ks_ref.dtype)
    vs_ref[0] = jnp.sum(_pool_weights(k3, phi_ref[...]) * v3,
                        axis=1).astype(vs_ref.dtype)


def _pool_bwd_kernel(k_ref, v_ref, mu_ref, phi_ref, dks_ref, dvs_ref,
                     dk_ref, dv_ref, dmu_ref, dphi_ref, *, chunk):
    """With w = softmax(a), a_m = mu . k_m, k~ = sum_m w_m k_m:
    da_m = w_m (k_m - k~) . dk~, dk_m = w_m dk~ + da_m mu, dmu = sum_m
    da_m k_m; the same through phi for v~, whose logits read k too."""
    rows = k_ref.shape[1]
    n = rows // chunk
    k3 = k_ref[0].astype(_F32).reshape(n, chunk, _LANES)
    v3 = v_ref[0].astype(_F32).reshape(n, chunk, _LANES)
    dks = dks_ref[0].astype(_F32)[:, None, :]
    dvs = dvs_ref[0].astype(_F32)[:, None, :]
    mu, phi = mu_ref[...], phi_ref[...]
    wk, wv = _pool_weights(k3, mu), _pool_weights(k3, phi)
    ks = jnp.sum(wk * k3, axis=1, keepdims=True)
    vs = jnp.sum(wv * v3, axis=1, keepdims=True)
    da = wk * _lane_sum((k3 - ks) * dks)
    db = wv * _lane_sum((v3 - vs) * dvs)
    dk_ref[0] = (wk * dks + da * mu + db * phi).reshape(
        rows, _LANES).astype(dk_ref.dtype)
    dv_ref[0] = (wv * dvs).reshape(rows, _LANES).astype(dv_ref.dtype)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dmu_ref[...] = jnp.zeros_like(dmu_ref)
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    # the sum over the rows of this step, on the block's 8 sublanes
    dmu_ref[0] += jnp.broadcast_to(
        jnp.sum(da * k3, axis=(0, 1))[None, :], dmu_ref.shape[1:])
    dphi_ref[0] += jnp.broadcast_to(
        jnp.sum(db * k3, axis=(0, 1))[None, :], dphi_ref.shape[1:])


def _pool_specs(t, chunk, rows=None):
    rows = min(t, rows or _POOL_ROWS)
    tokens = pl.BlockSpec((1, rows, _LANES), lambda b, h, i: (b, i, h))
    vector = pl.BlockSpec((1, _LANES), lambda b, h, i: (0, h))
    pooled = pl.BlockSpec((1, rows // chunk, _LANES),
                          lambda b, h, i: (b, i, h))
    return rows, tokens, vector, pooled


def _params(interpret, semantics, vmem=None):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics, vmem_limit_bytes=vmem)}


@functools.partial(jax.jit, static_argnames=("heads", "chunk",
                                             "interpret", "rows"))
def eva_pool_fwd_pallas(k, v, mu, phi, heads, chunk, interpret=False,
                        rows=None):
    """rows: the tokens a grid step reads (default _POOL_ROWS)."""
    b, t, width = k.shape
    rows, tokens, vector, pooled = _pool_specs(t, chunk, rows)
    return pl.pallas_call(
        functools.partial(_pool_fwd_kernel, chunk=chunk),
        name="pt_eva_pool_fwd",
        grid=(b, heads, t // rows),
        in_specs=[tokens, tokens, vector, vector],
        out_specs=[pooled, pooled],
        out_shape=[jax.ShapeDtypeStruct((b, t // chunk, width), k.dtype),
                   jax.ShapeDtypeStruct((b, t // chunk, width), v.dtype)],
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "parallel"),
                  _POOL_VMEM),
    )(k, v, mu.astype(_F32).reshape(1, width),
      phi.astype(_F32).reshape(1, width))


@functools.partial(jax.jit, static_argnames=("heads", "chunk",
                                             "interpret", "rows"))
def eva_pool_bwd_pallas(k, v, mu, phi, dks, dvs, heads, chunk,
                        interpret=False, rows=None):
    """(dk, dv, dmu, dphi): dk and dv in k's and v's dtypes, dmu and
    dphi float32 [H, D]."""
    b, t, width = k.shape
    rows, tokens, vector, pooled = _pool_specs(t, chunk, rows)
    part = pl.BlockSpec((1, 8, _LANES), lambda b, h, i: (b, 0, h))
    dk, dv, dmu, dphi = pl.pallas_call(
        functools.partial(_pool_bwd_kernel, chunk=chunk),
        name="pt_eva_pool_bwd",
        grid=(b, heads, t // rows),
        in_specs=[tokens, tokens, vector, vector, pooled, pooled],
        out_specs=[tokens, tokens, part, part],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, 8, width), _F32),
                   jax.ShapeDtypeStruct((b, 8, width), _F32)],
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary"),
                  _POOL_VMEM),
    )(k, v, mu.astype(_F32).reshape(1, width),
      phi.astype(_F32).reshape(1, width), dks, dvs)
    return dk, dv, dmu[:, 0].sum(0).reshape(mu.shape).astype(mu.dtype), \
        dphi[:, 0].sum(0).reshape(phi.shape).astype(phi.dtype)


# ---------------------------------------------------------------------------
# the staircase: queries against the chunk keys of earlier windows, begun
# from the window part's partial softmax
# ---------------------------------------------------------------------------

def _live_blocks(qi, block_q, block_k, window, cpw):
    """Chunk-key blocks q block qi sees (`staircase`)."""
    return (qi * block_q // window) * cpw // block_k


def _column(row):
    """A row statistic as it lies in HBM, [1, n] along the lanes ->
    [n, 128], an entry a sublane and the same on every lane: what the
    backward's [n, keys] score array broadcasts against.  One in-kernel
    transpose; as an XLA broadcast it was a 134 MB array a layer at the
    cell's shape (PERF.md section 6, PR 55)."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _chunk_fwd_kernel(qi_ref, kj_ref, q_ref, ks_ref, vs_ref, ow_ref,
                      lw_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                      scale, block_q, block_k, window, cpw):
    """A q block's running softmax STARTS from the window part's (out,
    lse): a normalised partial sum is the state (acc, m, l) = (out,
    lse, 1) of `flash_attention_lse`'s rule, so the block's last step
    writes the one softmax over both key sets and no merge is left.

    The scores are held KEYS DOWN, [block_k, block_q]: the running
    statistics are then rows [1, block_q] along the lanes, as the
    statistic lies in HBM, and the reductions over a step's keys run
    down the sublanes, not across the lanes; the accumulator is the
    transpose [128, block_q] of the block's output, turned once at the
    block's first and last step."""
    p = pl.program_id(2)
    qi, kj = qi_ref[p], kj_ref[p]

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = ow_ref[0].astype(_F32).T
        m_ref[...] = lw_ref[0]
        l_ref[...] = jnp.ones_like(l_ref)

    ks, vs = ks_ref[0], vs_ref[0]
    s = lax.dot_general(ks, q_ref[0], (((1,), (1,)), ((), ())),
                        preferred_element_type=_F32) * scale
    m_prev = m_ref[...]
    m_next = jnp.maximum(m_prev, jnp.max(s, axis=0, keepdims=True))
    e = jnp.exp(s - m_next)
    alpha = jnp.exp(m_prev - m_next)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(e, axis=0, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + lax.dot_general(
        vs, e.astype(vs.dtype), (((0,), (0,)), ((), ())),
        preferred_element_type=_F32)
    m_ref[...] = m_next

    @pl.when(kj == _live_blocks(qi, block_q, block_k, window, cpw) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / l_ref[...]).T.astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l_ref[...])


def _chunk_geometry(q, heads, window, chunk, block_q, block_k):
    b, t, width = q.shape
    if width // heads != _LANES:
        raise ValueError("eva_attention: the staircase kernels take "
                         "heads of %d, not %d" % (_LANES, width // heads))
    bq, bk = chunk_blocks(window, chunk, block_q, block_k)
    return b, t, width, bq, bk, window // chunk


def _chunk_specs(heads, window, bq, bk, n_windows):
    """BlockSpecs of the staircase's operands at step p, its q block
    qi[p] and chunk-key block kj[p]: token-major rows of q, out, dO
    (`q_rows`) and of k~, v~ (`k_rows`), and the row statistic in the
    WINDOW-MAJOR layout the window part's flash call returns it in,
    [B T/W, H, W] seen as [B T/W H, 1, W], a window and head a row along
    the lanes (`_column` stands it up in the kernel): q block i is
    entries (i bq) % W of window i bq // W."""
    q_rows = pl.BlockSpec((1, bq, _LANES),
                          lambda b, h, p, qi, kj: (b, qi[p], h))
    k_rows = pl.BlockSpec((1, bk, _LANES),
                          lambda b, h, p, qi, kj: (b, kj[p], h))
    per_window = window // bq
    stat = pl.BlockSpec(
        (1, 1, bq), lambda b, h, p, qi, kj: (
            (b * n_windows + qi[p] // per_window) * heads + h, 0,
            qi[p] % per_window))
    return q_rows, k_rows, stat


def _stat_rows(lse):
    """[B T/W, H, W] -> [B T/W H, 1, W]: free."""
    n, h, w = lse.shape
    return lse.reshape(n * h, 1, w)


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "chunk", "scale", "block_q", "block_k",
    "interpret"))
def eva_chunk_fwd_pallas(q, ks, vs, out_w, lse_w, heads, window, chunk,
                         scale, block_q=None, block_k=None,
                         interpret=False):
    """The window part's partial softmax (out_w [B, T, H*D], lse_w
    float32 [B T/W, H, W]) carried on over the chunk keys of the
    earlier windows: (out, lse) of the ONE softmax over both, in the
    same layouts.  The rows of window 0 see no chunk key and are no
    grid step: the outputs alias out_w and lse_w, whose rows stand."""
    b, t, width, bq, bk, cpw = _chunk_geometry(
        q, heads, window, chunk, block_q, block_k)
    qi, kj = staircase(t, window, chunk, bq, bk, "q")
    q_rows, k_rows, stat = _chunk_specs(heads, window, bq, bk,
                                        t // window)
    lse3 = _stat_rows(lse_w)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, heads, len(qi)),
        in_specs=[q_rows, k_rows, k_rows, q_rows, stat],
        out_specs=[q_rows, stat],
        scratch_shapes=[pltpu.VMEM((_LANES, bq), _F32),
                        pltpu.VMEM((1, bq), _F32),
                        pltpu.VMEM((1, bq), _F32)])
    out, lse = pl.pallas_call(
        functools.partial(_chunk_fwd_kernel, scale=scale, block_q=bq,
                          block_k=bk, window=window, cpw=cpw),
        name="pt_eva_chunk_fwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(lse3.shape, _F32)],
        # operands 5 and 6, the two scalar-prefetch arrays counted
        input_output_aliases={5: 0, 6: 1},
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary"),
                  _CHUNK_VMEM),
    )(jnp.asarray(qi), jnp.asarray(kj), q, ks, vs, out_w, lse3)
    return out, lse.reshape(lse_w.shape)


def _chunk_bwd_kernel(qi_ref, kj_ref, q_ref, ks_ref, vs_ref, do_ref,
                      o_ref, lse_ref, dqw_ref, dq_ref, dks_ref, dvs_ref,
                      dq_acc, dks_acc, dvs_acc, *, scale, block_q,
                      block_k, window, cpw, n_q):
    """Chunk-key blocks outer, their q blocks inner: dk~ and dv~ carry
    across a block's q sweep, and the head's whole dq [T, 128] float32
    stays in VMEM: it STARTS from the window part's dq at the head's
    first pair and is written at its last, so the sum of the two parts'
    dq is formed here (window 0's rows, which no pair touches, leave as
    the window part's)."""
    p = pl.program_id(2)
    qi, kj = qi_ref[p], kj_ref[p]
    # the first q block that sees chunk-key block kj: the first of the
    # window after the one the block's chunks lie in
    first_q = (kj * block_k // cpw + 1) * (window // block_q)

    @pl.when(p == 0)
    def _init_dq():
        dq_acc[...] = dqw_ref[0].astype(_F32)

    @pl.when(qi == first_q)
    def _init():
        dks_acc[...] = jnp.zeros_like(dks_acc)
        dvs_acc[...] = jnp.zeros_like(dvs_acc)

    q, ks, vs, do = q_ref[0], ks_ref[0], vs_ref[0], do_ref[0]
    s = lax.dot_general(q, ks, (((1,), (1,)), ((), ())),
                        preferred_element_type=_F32) * scale
    prob = jnp.exp(s - _column(lse_ref[0])[:, :1])
    delta = jnp.sum(do.astype(_F32) * o_ref[0].astype(_F32), axis=-1,
                    keepdims=True)
    dp = lax.dot_general(do, vs, (((1,), (1,)), ((), ())),
                         preferred_element_type=_F32)
    ds = prob * (dp - delta) * scale
    dvs_acc[...] += lax.dot_general(
        prob.astype(do.dtype), do, (((0,), (0,)), ((), ())),
        preferred_element_type=_F32)
    dks_acc[...] += lax.dot_general(
        ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
        preferred_element_type=_F32)
    rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
    dq_acc[rows, :] += lax.dot_general(
        ds.astype(ks.dtype), ks, (((1,), (0,)), ((), ())),
        preferred_element_type=_F32)

    @pl.when(qi == n_q - 1)
    def _finalize():
        dks_ref[0] = dks_acc[...].astype(dks_ref.dtype)
        dvs_ref[0] = dvs_acc[...].astype(dvs_ref.dtype)

    @pl.when(p == pl.num_programs(2) - 1)
    def _finalize_dq():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "heads", "window", "chunk", "scale", "block_q", "block_k",
    "interpret"))
def eva_chunk_bwd_pallas(q, ks, vs, out, lse, do, dq_w, heads, window,
                         chunk, scale, block_q=None, block_k=None,
                         interpret=False):
    """(dq, dk~, dv~): the chunk part's gradients from the out and lse
    (float32 [B T/W, H, W]) of the one softmax, dq begun from the
    window part's dq_w (which the result aliases)."""
    b, t, width, bq, bk, cpw = _chunk_geometry(
        q, heads, window, chunk, block_q, block_k)
    qi, kj = staircase(t, window, chunk, bq, bk, "k")
    q_rows, k_rows, stat = _chunk_specs(heads, window, bq, bk,
                                        t // window)
    whole = pl.BlockSpec((1, t, _LANES),
                         lambda b, h, p, qi, kj: (b, 0, h))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, heads, len(qi)),
        in_specs=[q_rows, k_rows, k_rows, q_rows, q_rows, stat, whole],
        out_specs=[whole, k_rows, k_rows],
        scratch_shapes=[pltpu.VMEM((t, _LANES), _F32),
                        pltpu.VMEM((bk, _LANES), _F32),
                        pltpu.VMEM((bk, _LANES), _F32)])
    dq, dks, dvs = pl.pallas_call(
        functools.partial(_chunk_bwd_kernel, scale=scale, block_q=bq,
                          block_k=bk, window=window, cpw=cpw,
                          n_q=t // bq),
        name="pt_eva_chunk_bwd",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(ks.shape, ks.dtype),
                   jax.ShapeDtypeStruct(vs.shape, vs.dtype)],
        # operand 8 (dq_w), the two scalar-prefetch arrays counted
        input_output_aliases={8: 0},
        interpret=interpret,
        **_params(interpret, ("parallel", "parallel", "arbitrary"),
                  _CHUNK_VMEM),
    )(jnp.asarray(qi), jnp.asarray(kj), q, ks, vs, do, out,
      _stat_rows(lse), dq_w)
    # the chunks of the last window are no query's key: never a grid
    # step, so what the call left there is not a gradient
    seen = (t - window) // chunk
    rows = lax.broadcasted_iota(jnp.int32, (1, t // chunk, 1), 1) < seen
    return dq, jnp.where(rows, dks, jnp.zeros_like(dks)), \
        jnp.where(rows, dvs, jnp.zeros_like(dvs))


# ---------------------------------------------------------------------------
# the aggregation: window part, then the staircase from where it ended
# ---------------------------------------------------------------------------

def _windows(x, window):
    """[B, T, C] -> [B T/W, W, C]: free."""
    b, t, c = x.shape
    return x.reshape(b * t // window, window, c)


def _flash_call(heads, scale, interpret, block=None):
    return dict(causal=True, scale=scale, heads=heads, block_q=block,
                block_k=block,
                impl="interpret" if interpret else "pallas")


def _aggregate_fwd(q, k, v, ks, vs, heads, window, chunk, scale,
                   interpret):
    """(out [B, T, H*D], lse float32 [B T/W, H, W]: a window a row, as
    the window part's flash call keeps it)."""
    t = q.shape[1]
    w = min(window, t)
    # a block a direction (tools/eva_price.py): the forward kernel
    # wants a window whole
    out_w, lse_w = pk._flash_attention_fwd(
        _windows(q, w), _windows(k, w), _windows(v, w),
        **_flash_call(heads, scale, interpret,
                      min(w, _WINDOW_FWD_BLOCK) if w > 1024 else None))
    out_w = out_w.reshape(q.shape)
    if t <= window:
        return out_w, lse_w
    return eva_chunk_fwd_pallas(
        q, ks, vs, out_w, lse_w, heads=heads, window=window, chunk=chunk,
        scale=scale, interpret=interpret)


def _aggregate_bwd(q, k, v, ks, vs, out, lse, do, heads, window, chunk,
                   scale, interpret):
    """(dq, dk, dv, dk~, dv~) from the out and lse of the one
    softmax."""
    t = q.shape[1]
    w = min(window, t)
    dq, dk, dv = pk._flash_attention_bwd(
        _windows(q, w), _windows(k, w), _windows(v, w), _windows(out, w),
        lse, _windows(do, w), **_flash_call(heads, scale, interpret))
    dq, dk, dv = dq.reshape(q.shape), dk.reshape(k.shape), \
        dv.reshape(v.shape)
    if t <= window:
        return dq, dk, dv, jnp.zeros_like(ks), jnp.zeros_like(vs)
    dq, dks, dvs = eva_chunk_bwd_pallas(
        q, ks, vs, out, lse, do, dq, heads=heads, window=window,
        chunk=chunk, scale=scale, interpret=interpret)
    return dq, dk, dv, dks, dvs


# ---------------------------------------------------------------------------
# differentiable entries (a recompute segment's replay and jax.vjp
# differentiate these; the registered grad ops call the backward
# functions on the saved outputs: ops/eva_ops.py)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def eva_pool_kernels(k, v, mu, phi, heads, chunk, interpret):
    return eva_pool_fwd_pallas(k, v, mu, phi, heads=heads, chunk=chunk,
                               interpret=interpret)


def _eva_pool_kernels_fwd(k, v, mu, phi, heads, chunk, interpret):
    return eva_pool_kernels(k, v, mu, phi, heads, chunk, interpret), \
        (k, v, mu, phi)


def _eva_pool_kernels_bwd(heads, chunk, interpret, res, g):
    return eva_pool_bwd_pallas(*res, *g, heads=heads, chunk=chunk,
                               interpret=interpret)


eva_pool_kernels.defvjp(_eva_pool_kernels_fwd, _eva_pool_kernels_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def eva_attention_kernels(q, k, v, ks, vs, heads, window, chunk, scale,
                          interpret):
    """(out, lse); lse carries no gradient (a cotangent on it is
    dropped: nothing in the IR reads LSE but the grad op)."""
    return _aggregate_fwd(q, k, v, ks, vs, heads, window, chunk, scale,
                          interpret)


def _eva_attention_kernels_fwd(q, k, v, ks, vs, heads, window, chunk,
                               scale, interpret):
    out, lse = _aggregate_fwd(q, k, v, ks, vs, heads, window, chunk,
                              scale, interpret)
    return (out, lse), (q, k, v, ks, vs, out, lse)


def _eva_attention_kernels_bwd(heads, window, chunk, scale, interpret,
                               res, g):
    return _aggregate_bwd(*res, g[0], heads, window, chunk, scale,
                          interpret)


eva_attention_kernels.defvjp(_eva_attention_kernels_fwd,
                             _eva_attention_kernels_bwd)

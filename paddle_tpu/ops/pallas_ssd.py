"""The chunked state-space scan of a Mamba-2 mixer (Dao & Gu,
"Transformers are SSMs", arXiv:2405.21060, section 6), forward and
backward, as two Pallas TPU kernels and the same algorithm in
jax.numpy.  docs/GRANITE4_BLOCK.md has the equations.

Per head h (H heads of size P, state size N, ONE group: B and C are
shared by all heads), with x_t [P], B_t, C_t [N], dt_t > 0, A < 0:

    a_t = exp(dt_t A)            S_t = a_t S_{t-1} + dt_t x_t B_t^T
    y_t = S_t C_t + D x_t        S in R^{P x N}, S_{-1} = 0

Chunked at L tokens: with cs_t the sum of dt A from the chunk's first
token to t (inclusive), G = C B^T [L, L] and W[t, s] = exp(cs_t -
cs_s) for s <= t, else 0,

    y = (G * W * dt_s) x  +  exp(cs_t) (C S_prev^T)  +  D x
    S_next = exp(cs_L) S_prev + (x * dt_s exp(cs_L - cs_s))^T B

where S_prev is the state the chunk starts from.  Only exp(cs_t -
cs_s) with s <= t and exp(cs_L - cs_s) are ever formed: differences of
the cumulative sums, never a quotient of their exponentials.

Operands are token-major: x and y [B, T, H*P], dt [B, T, H], B and C
[B, T, N].  A grid step takes one chunk and one 128-lane block of x,
which holds 128 / P heads side by side (P 64: two, P 128: one).  The
heads of a block share its tiles the way the token-major flash kernels'
do (pallas_kernels._head_tile): a head's [L, L] matrix multiplies the
block with the other head's lanes zeroed, so the heads' products add
to the block, and what differs by head and by row (exp(cs_t), dt_s
exp(cs_L - cs_s)) is a [L, 128] array whose lanes hold their head's
column.

Grid (B, T / L, H P / 128), the last two sequential: the chunk axis
carries the running state of every lane block in a float32 VMEM
scratch [H P / 128, 128, N] (the recurrence across chunks is carried
IN the kernel; there is no XLA scan between kernels), and G, which
all heads share, is formed once a chunk, at the first lane block, into
a scratch.  The forward writes the state each chunk STARTS from
(`states`, float32 [B, T / L, H P, N]): the residual the backward
reads.  The backward walks the chunks from the last to the first
(index maps c -> T / L - 1 - c) and carries d S the same way.

What XLA does round the kernels (`_prep`, `_finish_grads`): the
cumulative sums cs, float32 [B, T, H], 2 MB at the cell's size, in the
two layouts the kernels read them in (rows along sublanes, and
[B, H, 1, T] with rows along lanes: a chunk's [L, L] decay matrix
needs cs_t down its rows and cs_s along its columns, and a kernel
transposes nothing), and after the backward the suffix sums that turn
d cs into d (dt A), d dt and d A.

MXU operands are in x's dtype (bfloat16 under AMP); dt, cs, every
exponential, the running state, d S and every accumulation are
float32.
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
_NEG = -1e30
_HIGHEST = lax.Precision.HIGHEST


def check_shapes(x, dt, bm, chunk):
    """(B, T, H, P, N) of the operands, or a ValueError that says what
    does not fit.  Nothing is padded: a length that is no multiple of
    the chunk raises."""
    b, t, width = x.shape
    h = dt.shape[-1]
    if width % h:
        raise ValueError("ssd_scan: X's width %d is no multiple of the "
                         "%d heads Dt has" % (width, h))
    if t % chunk:
        raise ValueError(
            "ssd_scan: %d tokens are no multiple of the chunk size %d; "
            "nothing is padded" % (t, chunk))
    return b, t, h, width // h, bm.shape[-1]


def kernel_geom_ok(p, chunk):
    """Whether the kernels can tile these sizes: a head size of 64 or
    128 (whole 128-lane blocks of whole heads) and chunks of whole
    sublane tiles."""
    return p in (64, 128) and chunk % 8 == 0


# ---------------------------------------------------------------------------
# the same algorithm in jax.numpy: what a CPU runs, and what jax
# differentiates where no kernel runs
# ---------------------------------------------------------------------------

def ssd_chunked_xla(x, dt, a, bm, cm, d, chunk):
    """(y [B, T, H*P] in x's dtype, states float32 [B, T/L, H*P, N]:
    the state each chunk starts from).  Float32 throughout."""
    b, t, h, p, n = check_shapes(x, dt, bm, chunk)
    nc = t // chunk
    xh = x.astype(_F32).reshape(b, nc, chunk, h, p)
    dtc = dt.astype(_F32).reshape(b, nc, chunk, h)
    cs = jnp.cumsum(dtc * a.astype(_F32), axis=2)
    bc = bm.astype(_F32).reshape(b, nc, chunk, n)
    cc = cm.astype(_F32).reshape(b, nc, chunk, n)
    g = jnp.einsum("bctn,bcsn->bcts", cc, bc, precision=_HIGHEST)
    causal = (jnp.arange(chunk)[:, None] >= jnp.arange(chunk)[None])
    seg = cs[:, :, :, None, :] - cs[:, :, None, :, :]      # [.., t, s, h]
    causal = causal[None, None, :, :, None]
    w = jnp.where(causal, jnp.exp(jnp.where(causal, seg, 0.0)), 0.0)
    m = g[..., None] * w * dtc[:, :, None, :, :]
    y = jnp.einsum("bctsh,bcshp->bcthp", m, xh, precision=_HIGHEST)
    to_end = jnp.exp(cs[:, :, -1:, :] - cs) * dtc
    s_local = jnp.einsum("bclh,bclhp,bcln->bchpn", to_end, xh, bc,
                         precision=_HIGHEST)
    a_chunk = jnp.exp(cs[:, :, -1, :])                     # [B, nc, H]

    def pass_on(s, inp):
        a_c, s_c = inp
        return a_c[..., None, None] * s + s_c, s

    _, starts = lax.scan(
        pass_on, jnp.zeros((b, h, p, n), _F32),
        (a_chunk.transpose(1, 0, 2), s_local.transpose(1, 0, 2, 3, 4)))
    starts = starts.transpose(1, 0, 2, 3, 4)               # [B, nc, ..]
    y = y + jnp.einsum("bcln,bchpn->bclhp", cc, starts,
                       precision=_HIGHEST) * jnp.exp(cs)[..., None]
    y = y + d.astype(_F32)[:, None] * xh
    return (y.reshape(b, t, h * p).astype(x.dtype),
            starts.reshape(b, nc, h * p, n))


# ---------------------------------------------------------------------------
# what the kernels share
# ---------------------------------------------------------------------------

def _prep(dt, a, chunk):
    """dt [B, T, H], a [H] -> float32 (dt, cs) with rows along
    sublanes, [B, T, H], and with rows along lanes, [B, H, 1, T]."""
    b, t, h = dt.shape
    dt = dt.astype(_F32)
    cs = jnp.cumsum((dt * a.astype(_F32)).reshape(b, t // chunk, chunk, h),
                    axis=2).reshape(b, t, h)

    def rows_on_lanes(v):
        return v.transpose(0, 2, 1).reshape(b, h, 1, t)

    return dt, cs, rows_on_lanes(dt), rows_on_lanes(cs)


def _dot(a, b, dims):
    return lax.dot_general(a, b, (dims, ((), ())),
                           preferred_element_type=_F32)


_NT = ((1,), (1,))      # a [m, k], b [n, k] -> [m, n]
_NN = ((1,), (0,))      # a [m, k], b [k, n] -> [m, n]
_TN = ((0,), (0,))      # a [k, m], b [k, n] -> [m, n]


def _head_columns(dtc_ref, csc_ref, dtr_ref, csr_ref, hk, j, hb):
    """Head hk * hb + j's dt and cs, as columns [L, 1] (picked out of
    the [L, H] blocks by a masked sum over the lanes: the head index
    is a grid index) and as rows [1, L]."""
    dtc, csc = dtc_ref[0], csc_ref[0]
    pick = lax.broadcasted_iota(jnp.int32, dtc.shape, 1) == hk * hb + j
    dt_col = jnp.sum(jnp.where(pick, dtc, 0.0), axis=1, keepdims=True)
    cs_col = jnp.sum(jnp.where(pick, csc, 0.0), axis=1, keepdims=True)
    return pick, dt_col, cs_col, dtr_ref[0, j], csr_ref[0, j]


def _decay(cs_col, cs_row):
    """W[t, s] = exp(cs_t - cs_s) for s <= t, else 0."""
    ln = cs_col.shape[0]
    causal = lax.broadcasted_iota(jnp.int32, (ln, ln), 0) \
        >= lax.broadcasted_iota(jnp.int32, (ln, ln), 1)
    return jnp.exp(jnp.where(causal, cs_col - cs_row, _NEG))


def _lanes_of(j, p, shape, axis):
    """The lanes (axis 1) or sublanes (axis 0) of head slot j."""
    i = lax.broadcasted_iota(jnp.int32, shape, axis)
    return (i >= j * p) & (i < (j + 1) * p)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(x_ref, b_ref, c_ref, dtc_ref, csc_ref, dtr_ref, csr_ref,
                d_ref, y_ref, st_ref, s_ref, g_ref, *, hb, p):
    c, hk = pl.program_id(1), pl.program_id(2)
    ln = x_ref.shape[1]
    dtype = x_ref.dtype

    @pl.when(c == 0)
    def _first_chunk():
        s_ref[hk] = jnp.zeros(s_ref.shape[1:], _F32)

    @pl.when(hk == 0)
    def _scores():
        g_ref[...] = _dot(c_ref[0], b_ref[0], _NT)

    x = x_ref[0]
    s_prev = s_ref[hk]
    st_ref[0, 0] = s_prev
    y = jnp.zeros((ln, _LANES), _F32)
    e_lane = jnp.zeros((ln, _LANES), _F32)      # exp(cs_t), by head
    w_lane = jnp.zeros((ln, _LANES), _F32)      # dt_s exp(cs_L - cs_s)
    a_rows = jnp.zeros((_LANES, 1), _F32)       # exp(cs_L), by head
    for j in range(hb):
        _, dt_col, cs_col, dt_row, cs_row = _head_columns(
            dtc_ref, csc_ref, dtr_ref, csr_ref, hk, j, hb)
        m = (g_ref[...] * _decay(cs_col, cs_row) * dt_row).astype(dtype)
        mine = _lanes_of(j, p, (ln, _LANES), 1)
        y = y + _dot(m, jnp.where(mine, x, jnp.zeros_like(x)), _NN)
        cs_last = cs_col[ln - 1:ln, :]
        e_lane = jnp.where(mine, jnp.exp(cs_col), e_lane)
        w_lane = jnp.where(mine, dt_col * jnp.exp(cs_last - cs_col),
                           w_lane)
        a_rows = jnp.where(_lanes_of(j, p, (_LANES, 1), 0),
                           jnp.exp(cs_last), a_rows)
    xf = x.astype(_F32)
    y = y + _dot(c_ref[0], s_prev.astype(dtype), _NT) * e_lane \
        + xf * d_ref[...]
    y_ref[0] = y.astype(y_ref.dtype)
    s_ref[hk] = a_rows * s_prev + _dot((xf * w_lane).astype(dtype),
                                       b_ref[0], _TN)


def _specs(b, t, h, p, n, chunk, rev):
    """BlockSpecs by operand kind over the grid (B, T/L, H P/128);
    `rev` walks the chunks from the last to the first."""
    nc, hb = t // chunk, _LANES // p

    def ch(c):
        return nc - 1 - c if rev else c

    return {
        "x": pl.BlockSpec((1, chunk, _LANES),
                          lambda i, c, k: (i, ch(c), k)),
        "bc": pl.BlockSpec((1, chunk, n), lambda i, c, k: (i, ch(c), 0)),
        "col": pl.BlockSpec((1, chunk, h), lambda i, c, k: (i, ch(c), 0)),
        "row": pl.BlockSpec((1, hb, 1, chunk),
                            lambda i, c, k: (i, k, 0, ch(c))),
        "d": pl.BlockSpec((1, _LANES), lambda i, c, k: (0, k)),
        "state": pl.BlockSpec((1, 1, _LANES, n),
                              lambda i, c, k: (i, ch(c), k, 0)),
    }


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=64 << 20)}


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_fwd_pallas(x, dt, a, bm, cm, d, chunk, interpret=False):
    """-> (y [B, T, H*P] in x's dtype, states float32
    [B, T/L, H*P, N])."""
    b, t, h, p, n = check_shapes(x, dt, bm, chunk)
    nc, hb, nk = t // chunk, _LANES // p, h * p // _LANES
    dtc, csc, dtr, csr = _prep(dt, a, chunk)
    d_lane = jnp.repeat(d.astype(_F32), p).reshape(1, h * p)
    sp = _specs(b, t, h, p, n, chunk, rev=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p),
        name="pt_ssd_fwd",
        grid=(b, nc, nk),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["col"], sp["col"],
                  sp["row"], sp["row"], sp["d"]],
        out_specs=[sp["x"], sp["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, h * p, n), _F32)],
        scratch_shapes=[pltpu.VMEM((nk, _LANES, n), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        interpret=interpret,
        **_params(interpret),
    )(x, bm.astype(x.dtype), cm.astype(x.dtype), dtc, csc, dtr, csr,
      d_lane)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
# With Q[t, s] = dy_t . x_s (a head's), E = W * G * Q and R_s = x_s .
# (dS_next B_s), per chunk and head:
#   d x_s   = sum_t (W G)[t, s] dt_s dy_t + dt_s e^{cs_L - cs_s} dS_next
#             B_s + D dy_s
#   d dt_s  = sum_t E[t, s]  +  e^{cs_L - cs_s} R_s          (direct)
#   d G     = sum over heads of W * dt_s * Q;  d C = dG B, d B = dG^T C
#   d C_t  += e^{cs_t} S_prev^T dy_t;   d B_s += dt_s e^{cs_L-cs_s}
#             dS_next^T x_s
#   d cs_t  = sum_s E[t, s] dt_s - dt_t sum_u E[u, t] + dy_t . y_inter_t
#             - dt_t e^{cs_L - cs_t} R_t, and at t = L besides
#             sum_s dt_s e^{cs_L - cs_s} R_s + e^{cs_L} <dS_next, S_prev>
#   d S_prev = e^{cs_L} dS_next + (dy * e^{cs_t})^T C
# Sums over s of a [t, s] matrix come out as columns [L, 1], sums over
# t as rows [1, L]; the kernel writes each in the layout it comes in
# and XLA adds the two (`_finish_grads`).

def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, dtc_ref, csc_ref, dtr_ref,
                csr_ref, d_ref, st_ref,
                dx_ref, db_ref, dc_ref, ddtc_ref, dcsc_ref, ddtr_ref,
                dcsr_ref, dd_ref, ds_ref, g_ref, dg_ref, *, hb, p):
    c, hk = pl.program_id(1), pl.program_id(2)
    ln = x_ref.shape[1]
    dtype = x_ref.dtype

    @pl.when(c == 0)
    def _last_chunk():
        ds_ref[hk] = jnp.zeros(ds_ref.shape[1:], _F32)

    @pl.when(hk == 0)
    def _start_chunk():
        g_ref[...] = _dot(c_ref[0], b_ref[0], _NT)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        db_ref[...] = jnp.zeros_like(db_ref)
        dc_ref[...] = jnp.zeros_like(dc_ref)
        ddtc_ref[...] = jnp.zeros_like(ddtc_ref)
        dcsc_ref[...] = jnp.zeros_like(dcsc_ref)

    x, dy, bm, cm = x_ref[0], dy_ref[0], b_ref[0], c_ref[0]
    xf, dyf = x.astype(_F32), dy.astype(_F32)
    s_prev, ds_next = st_ref[0, 0], ds_ref[hk]
    c_s = _dot(cm, s_prev.astype(dtype), _NT)         # C S_prev^T
    b_ds = _dot(bm, ds_next.astype(dtype), _NT)       # B dS_next^T
    s_ds = jnp.sum(ds_next * s_prev, axis=1, keepdims=True)   # [128, 1]
    dx = jnp.zeros((ln, _LANES), _F32)
    e_lane = jnp.zeros((ln, _LANES), _F32)
    w_lane = jnp.zeros((ln, _LANES), _F32)
    a_rows = jnp.zeros((_LANES, 1), _F32)
    last_row = lax.broadcasted_iota(jnp.int32, (ln, 1), 0) == ln - 1
    for j in range(hb):
        pick, dt_col, cs_col, dt_row, cs_row = _head_columns(
            dtc_ref, csc_ref, dtr_ref, csr_ref, hk, j, hb)
        mine = _lanes_of(j, p, (ln, _LANES), 1)
        rows_mine = _lanes_of(j, p, (_LANES, 1), 0)
        dyj = jnp.where(mine, dy, jnp.zeros_like(dy))
        w = _decay(cs_col, cs_row)
        q = _dot(dyj, x, _NT)                                 # [t, s]
        wg = w * g_ref[...]
        e1 = wg * q
        ddt_row = jnp.sum(e1, axis=0, keepdims=True)          # [1, L]
        dg_ref[...] += w * dt_row * q
        dx = dx + _dot((wg * dt_row).astype(dtype), dyj, _TN)
        cs_last = cs_col[ln - 1:ln, :]
        to_end = jnp.exp(cs_last - cs_col)
        w_col = dt_col * to_end
        r_col = jnp.sum(jnp.where(mine, xf * b_ds, 0.0), axis=1,
                        keepdims=True)
        at_last = jnp.sum(w_col * r_col, axis=0, keepdims=True) \
            + jnp.exp(cs_last) * jnp.sum(
                jnp.where(rows_mine, s_ds, 0.0), axis=0, keepdims=True)
        dcs_col = jnp.sum(e1 * dt_row, axis=1, keepdims=True) \
            + jnp.exp(cs_col) * jnp.sum(jnp.where(mine, dyf * c_s, 0.0),
                                        axis=1, keepdims=True) \
            - w_col * r_col + jnp.where(last_row, at_last, 0.0)
        ddtc_ref[0] += jnp.where(pick, to_end * r_col, 0.0)
        dcsc_ref[0] += jnp.where(pick, dcs_col, 0.0)
        ddtr_ref[0, j] = ddt_row
        dcsr_ref[0, j] = -dt_row * ddt_row
        e_lane = jnp.where(mine, jnp.exp(cs_col), e_lane)
        w_lane = jnp.where(mine, w_col, w_lane)
        a_rows = jnp.where(rows_mine, jnp.exp(cs_last), a_rows)
    dy_e = (dyf * e_lane).astype(dtype)
    dc_ref[0] += _dot(dy_e, s_prev.astype(dtype), _NN)
    db_ref[0] += _dot((xf * w_lane).astype(dtype), ds_next.astype(dtype),
                      _NN)
    dx_ref[0] = (dx + b_ds * w_lane + dyf * d_ref[...]).astype(
        dx_ref.dtype)
    dd_ref[0, 0] = jnp.sum(dyf * xf, axis=0, keepdims=True)
    ds_ref[hk] = a_rows * ds_next + _dot(dy_e, cm, _TN)

    @pl.when(hk == pl.num_programs(2) - 1)
    def _end_chunk():
        dg = dg_ref[...].astype(dtype)
        dc_ref[0] += _dot(dg, bm, _NN)
        db_ref[0] += _dot(dg, cm, _TN)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_bwd_pallas(x, dt, a, bm, cm, d, states, dy, chunk,
                   interpret=False):
    """The six input gradients (x, dt, a, bm, cm, d), each in its
    input's dtype, from the chunk-start states the forward kept."""
    b, t, h, p, n = check_shapes(x, dt, bm, chunk)
    nc, hb, nk = t // chunk, _LANES // p, h * p // _LANES
    dtc, csc, dtr, csr = _prep(dt, a, chunk)
    d_lane = jnp.repeat(d.astype(_F32), p).reshape(1, h * p)
    sp = _specs(b, t, h, p, n, chunk, rev=True)
    dd_spec = pl.BlockSpec((1, 1, 1, _LANES),
                           lambda i, c, k: (i, nc - 1 - c, 0, k))

    def like(v, dtype=_F32):
        return jax.ShapeDtypeStruct(v.shape, dtype)

    dx, db, dc, ddtc, dcsc, ddtr, dcsr, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p),
        name="pt_ssd_bwd",
        grid=(b, nc, nk),
        in_specs=[sp["x"], sp["x"], sp["bc"], sp["bc"], sp["col"],
                  sp["col"], sp["row"], sp["row"], sp["d"], sp["state"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["col"], sp["col"],
                   sp["row"], sp["row"], dd_spec],
        out_shape=[like(x, x.dtype), like(bm), like(cm), like(dtc),
                   like(dtc), like(dtr), like(dtr),
                   jax.ShapeDtypeStruct((b, nc, 1, h * p), _F32)],
        scratch_shapes=[pltpu.VMEM((nk, _LANES, n), _F32),
                        pltpu.VMEM((chunk, chunk), _F32),
                        pltpu.VMEM((chunk, chunk), _F32)],
        interpret=interpret,
        **_params(interpret),
    )(x, dy.astype(x.dtype), bm.astype(x.dtype), cm.astype(x.dtype), dtc,
      csc, dtr, csr, d_lane, states)

    def rows_on_sublanes(v):
        return v.reshape(b, h, t).transpose(0, 2, 1)

    d_dt, d_a = _finish_grads(
        dtc, a, ddtc + rows_on_sublanes(ddtr),
        dcsc + rows_on_sublanes(dcsr), chunk)
    d_d = jnp.sum(dd.reshape(-1, h, p), axis=(0, 2))
    return (dx, d_dt.astype(dt.dtype), d_a.astype(a.dtype),
            db.astype(bm.dtype), dc.astype(cm.dtype), d_d.astype(d.dtype))


def _finish_grads(dt, a, d_dt_direct, d_cs, chunk):
    """cs is the chunk's running sum of dt A: d (dt A)_t is the sum of
    d cs_u over the chunk's u >= t; then d dt = direct + A d(dt A) and
    d A = sum dt d(dt A).  All float32, [B, T, H]."""
    b, t, h = dt.shape
    d_la = jnp.flip(jnp.cumsum(jnp.flip(
        d_cs.reshape(b, t // chunk, chunk, h), 2), axis=2), 2
    ).reshape(b, t, h)
    af = a.astype(_F32)
    return d_dt_direct + af * d_la, jnp.sum(dt * d_la, axis=(0, 1))

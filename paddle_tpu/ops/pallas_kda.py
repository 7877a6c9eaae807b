"""The chunked delta-rule recurrence of a Kimi Delta Attention mixer
(KDA; Kimi Linear, arXiv:2510.26692), forward and backward, as two
Pallas TPU kernels and the same algorithm in jax.numpy.
docs/LING3_BLOCK.md has the equations.

Per head (H heads, key and value size D), with q_t, k_t [D], v_t [D],
a log-decay PER KEY CHANNEL g_t [D] <= 0 and a write strength beta_t in
(0, 1), from a zero state S in R^{D x D}:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

Chunked at C tokens (the WY form).  With G_r the sum of g from the
chunk's first token to r (inclusive), Z the transposed state the chunk
starts from (Z = S^T, [D_v, D_k]: the key channels lie along the lanes,
so a decay a channel scales columns), and the row-scaled operands
Kg = K * e^G, Qg = Q * e^G, Kend = K * e^(G_C - G):

    M[r, s] = sum_c k_r[c] k_s[c] e^(G_r[c] - G_s[c])      s <  r
    P[r, s] = sum_c q_r[c] k_s[c] e^(G_r[c] - G_s[c])      s <= r
    T  = (I + diag(beta) M)^-1                 unit lower triangular
    W  = T (beta Kg)      U = T (beta V)       Ut = U - W Z^T
    O  = Qg Z^T + P Ut
    Z' = Z * e^(G_C) + Ut^T Kend

e^(-G) alone is never formed: at g = -5 a token a chunk of 64 tokens
would need e^320.  M and P are made a SUB-BLOCK of 16 rows at a time
against a reference row (the sub-block's first): the rows carry
e^(G_r - G_ref) <= 1 and the columns e^(G_ref - G_s), which is <= 1
for the columns before the sub-block.  What the columns INSIDE the
sub-block carry is the static choice `bounded`, which the op takes from
the gate's form (kda_ops.kda_scan, attr `decay`):

  * bounded (a gate that keeps g >= BOUNDED_G_MIN a token: kda_gate's
    sigmoid form at -5): the same factors, e^(min(G_ref - G_s, 80)), at
    most e^75 inside the sub-block (15 steps of at most 5); columns
    after it are masked.  One product a sub-block.
  * unbounded (any g <= 0: kda_gate's softplus form): nothing above
    e^0 is formed and no clamp can change a result.  The sub-block's
    product is kept for the columns BEFORE it only (the clamp is
    min(., 0), which those columns never reach); the pairs inside the
    16 x 16 diagonal blocks are made by the LEVEL at which r and s
    part: at level b = 8, 4, 2, 1, r lies in the odd block of b rows
    of a pair of blocks and s in the even one before it, and the
    reference is the odd block's first row, which lies between s and r
    in time, so both factors are <= 1 (`_levels`; four more products
    of the whole chunk, masked to their pairs; P's diagonal, decay
    e^0, is the rows' q . k).  A decay is still a difference of the
    chunk's running sums, so its exponent carries the rounding of
    |G|: one part in 2^24 of the chunk's total log-decay.

The inverse: the 16 x 16 diagonal blocks by doubling, (I + X)(I + X^2)
(I + X^4)(I + X^8) with X = -N_b, N_b^16 = 0; the blocks below them by
Y = D^-1 N_off, Y^4 = 0, T = (I - Y)(I + Y^2) D^-1.  Ten products of
C x C matrices, all float32 (precision `highest` on the MXU).

Operands are token-major: q, k, v, g [B, T, H*D] and beta [B, T, H].
D is 128: a head is one 128-lane block.  A grid step takes one head
and one BLOCK of `block_chunks` chunks (256 tokens at 4 x 64) and walks
its chunks in a loop; grid (B, H, T / block), the last axis sequential:
it carries the running state in a float32 VMEM scratch [D, D].

The residuals of a forward pass, both float32 whatever the operands
are, are what the backward cannot make cheaply:

  * `states` [B, T / block, H*D, D]: the state each BLOCK starts from
    (33.5 MB a layer at 4,096 tokens and 32 heads; one state a chunk
    would be 134 MB a layer);
  * `inverse` [B, H, T / block, C, block]: each chunk's T, a block's
    four side by side so that the 64-wide matrices are not padded to
    128 lanes in HBM (64 x 256 a grid step; 33.5 MB a layer at the
    same shape).  T depends on K, G and beta of its own chunk alone,
    not on the state, and its ten dependent products are the longest
    chain of either kernel: it is formed once a step, here.

The backward walks the blocks from the last to the first; inside a
block it first runs the chunks forward again from the saved state with
the saved inverses (the scores M and P, W, U, Ut and the state chain:
a few products each, and keeping them would be the 134 MB a layer of a
state a chunk and as much again for W and Ut), keeping each chunk's
start state, M, P, W and Ut in VMEM, then walks them in reverse
carrying dZ.  The loops over a block's chunks are unrolled: a chunk's
scores (and in the forward its inverse) do not wait for the state, so
the scheduler runs them beside the chunk before's state products (on
the chip 4.89 -> 4.49 ms a forward call and 7.74 -> 6.65 a backward
call at 1 x 4,096 x 32 heads, the same bits; PERF.md section 6, PR 41;
the backward without its own inverses: PR 46).

What XLA does round the kernels (`_prep`, `_finish`): the chunk-local
running sums G (float32, [B, T, H*D]), beta as [B, H, T, 1] columns,
and after the backward the reverse running sums that turn dG into dg.

MXU operands are in q's dtype (bfloat16 under AMP); g, G, every
exponential, the inverse, the running state, dZ and every accumulation
are float32.  With float32 operands every product is `highest`.
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
_SUB = 16                 # rows of a sub-block of M and P
_MAX_CHUNK = 4 * _SUB     # _inverse's series ends at four diagonal blocks
_MAX_EXP = 80.0           # e^80 < float32's largest; 15 steps of 5 < 80
# the least g a token for which the bounded path is exact: a column of
# a sub-block is at most 15 steps from its reference row
BOUNDED_G_MIN = -_MAX_EXP / (_SUB - 1)
_HIGHEST = lax.Precision.HIGHEST


def check_shapes(q, v, beta, chunk, block_chunks):
    """(B, T, H, D) of the operands, or a ValueError that says what
    does not fit.  Nothing is padded: a length that is no multiple of
    block_chunks x chunk raises."""
    b, t, width = q.shape
    h = beta.shape[-1]
    if width % h or v.shape != q.shape:
        raise ValueError(
            "kda_scan: Q %s and V %s must be alike and a multiple of the "
            "%d heads Beta has wide" % (q.shape, v.shape, h))
    if chunk % _SUB or not 0 < chunk <= _MAX_CHUNK:
        raise ValueError("kda_scan: the chunk size %d is no multiple of "
                         "%d up to %d" % (chunk, _SUB, _MAX_CHUNK))
    if t % (chunk * block_chunks):
        raise ValueError(
            "kda_scan: %d tokens are no multiple of %d (%d chunks of %d, "
            "one saved state); nothing is padded"
            % (t, chunk * block_chunks, block_chunks, chunk))
    return b, t, h, width // h


def kernel_geom_ok(d):
    """Whether the kernels can tile these sizes: a head is one
    128-lane block."""
    return d == _LANES


# ---------------------------------------------------------------------------
# the same algorithm in jax.numpy: what a CPU runs, and what jax
# differentiates where no kernel runs
# ---------------------------------------------------------------------------

def kda_chunked_xla(q, k, v, g, beta, chunk, block_chunks):
    """(o [B, T, H*D] in v's dtype, states float32 [B, T/block, H*D, D]:
    the transposed state each block starts from, inverse float32
    [B, H, T/block, C, block]: a block's T side by side).  Float32
    throughout; decays as differences G_r - G_s, the inverse as a
    triangular solve, against the identity too."""
    from jax.scipy.linalg import solve_triangular

    b, t, h, d = check_shapes(q, v, beta, chunk, block_chunks)
    nc = t // chunk

    def heads(x):                       # -> [nc, B, H, C, D]
        return x.astype(_F32).reshape(b, nc, chunk, h, d).transpose(
            1, 0, 3, 2, 4)

    qc, kc, vc = heads(q), heads(k), heads(v)
    gc = jnp.cumsum(heads(g), axis=3)
    bc = beta.astype(_F32).reshape(b, nc, chunk, h).transpose(1, 0, 3, 2)
    row = jnp.arange(chunk)
    lower = row[:, None] >= row[None]
    strict = row[:, None] > row[None]

    def one_chunk(z, inp):
        qx, kx, vx, gx, bx = inp
        # e^(G_r - G_s) a channel, only where s <= r
        seg = jnp.where(lower[..., None],
                        gx[:, :, :, None, :] - gx[:, :, None, :, :], 0.0)
        decay = jnp.where(lower[..., None], jnp.exp(seg), 0.0)
        m = jnp.where(strict, jnp.einsum(
            "bhrc,bhsc,bhrsc->bhrs", kx, kx, decay, precision=_HIGHEST),
            0.0)
        p = jnp.einsum("bhrc,bhsc,bhrsc->bhrs", qx, kx, decay,
                       precision=_HIGHEST)
        eg = jnp.exp(gx)
        eye = jnp.eye(chunk, dtype=_F32)
        rhs = jnp.concatenate(
            [bx[..., None] * jnp.concatenate([kx * eg, vx], axis=-1),
             jnp.broadcast_to(eye, m.shape)], axis=-1)
        wu = solve_triangular(eye + bx[..., None] * m, rhs,
                              lower=True, unit_diagonal=True)
        w, u, t_inv = wu[..., :d], wu[..., d:2 * d], wu[..., 2 * d:]
        ut = u - jnp.einsum("bhrk,bhvk->bhrv", w, z, precision=_HIGHEST)
        o = jnp.einsum("bhrk,bhvk->bhrv", qx * eg, z, precision=_HIGHEST) \
            + jnp.einsum("bhrs,bhsv->bhrv", p, ut, precision=_HIGHEST)
        g_last = gx[:, :, -1:, :]
        z_next = z * jnp.exp(g_last) + jnp.einsum(
            "bhrv,bhrk->bhvk", ut, kx * jnp.exp(g_last - gx),
            precision=_HIGHEST)
        return z_next, (o, z, t_inv)

    _, (o, starts, t_inv) = lax.scan(
        one_chunk, jnp.zeros((b, h, d, d), _F32), (qc, kc, vc, gc, bc))
    o = o.transpose(1, 0, 3, 2, 4).reshape(b, t, h * d)
    nb = nc // block_chunks
    states = starts[::block_chunks].transpose(1, 0, 2, 3, 4).reshape(
        b, nb, h * d, d)
    # [nb, chunks, B, H, r, s] -> [B, H, nb, r, chunks x s]
    inverse = t_inv.reshape(nb, block_chunks, b, h, chunk, chunk).transpose(
        2, 3, 0, 4, 1, 5).reshape(b, h, nb, chunk, block_chunks * chunk)
    return o.astype(v.dtype), states, inverse


# ---------------------------------------------------------------------------
# what the kernels share
# ---------------------------------------------------------------------------

def _prep(g, beta, chunk):
    """g [B, T, H*D], beta [B, T, H] -> float32 (G: the chunk-local
    running sum of g, [B, T, H*D]; beta as columns [B, H, T, 1])."""
    b, t, width = g.shape
    gsum = jnp.cumsum(g.astype(_F32).reshape(b, t // chunk, chunk, width),
                      axis=2).reshape(b, t, width)
    return gsum, beta.astype(_F32).transpose(0, 2, 1)[..., None]


_NT = ((1,), (1,))      # a [m, k], b [n, k] -> [m, n]
_NN = ((1,), (0,))      # a [m, k], b [k, n] -> [m, n]
_TN = ((0,), (0,))      # a [k, m], b [k, n] -> [m, n]


def _dot(a, b, dims, dtype):
    """a . b with both cast to `dtype`, accumulated in float32;
    `highest` where the operands are float32."""
    return lax.dot_general(
        a.astype(dtype), b.astype(dtype), (dims, ((), ())),
        preferred_element_type=_F32,
        precision=_HIGHEST if dtype == _F32 else None)


_LEVELS = (8, 4, 2, 1)    # the blocks of rows a diagonal block halves into


def _masks(chunk, bounded=True):
    r = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    c = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mk = {"lower": r >= c, "strict": r > c, "eye": r == c,
          "diag_block": (r // _SUB) == (c // _SUB)}
    if not bounded:
        mk["before"] = (r // _SUB) > (c // _SUB)
        # level b: r in the odd block of b rows, s in the even one
        # before it
        mk.update({b: ((r // b) == (c // b) + 1) & ((r // b) % 2 == 1)
                   for b in _LEVELS})
    return mk


def _sub_blocks(q, k, gsum, bounded=True):
    """For each sub-block i of 16 rows: (left [32, D]: K_i and Q_i
    times el, right [C, D]: K times er, el [16, D], er [C, D]) with
    el = e^(G_r - G_ref) and er = e^(min(G_ref - G_s, 80)), G_ref the
    sub-block's first row; unbounded, er = e^(min(G_ref - G_s, 0)):
    the columns before the sub-block never reach the clamp, and the
    others are `_levels`'.  All float32."""
    out = []
    for i in range(gsum.shape[0] // _SUB):
        rows = slice(i * _SUB, (i + 1) * _SUB)
        gi = gsum[rows]
        ref = gi[0:1]
        el = jnp.exp(gi - ref)
        er = jnp.exp(jnp.minimum(ref - gsum,
                                 _MAX_EXP if bounded else 0.0))
        left = jnp.concatenate([k[rows] * el, q[rows] * el], axis=0)
        out.append((left, k * er, el, er))
    return out


def _levels(q, k, gsum):
    """The pairs (r, s), s < r, INSIDE the 16 x 16 diagonal blocks, by
    the level b at which they part: (b, left [2C, D]: K and Q times
    el, right [C, D]: K times er, el, er [C, D]) with el = e^(G_r -
    G_ref(r)), G_ref(r) the first row of r's block of b rows, and er =
    e^(min(G_ref'(s) - G_s, 0)), G_ref'(s) the first row of the block
    AFTER s's.  For a level's pairs (mask b of `_masks`) the two
    references are one row, between s and r in time: both exponents
    are <= 0 whatever g <= 0 is.  The clamp touches only a chunk's
    last block, whose `next block` wraps and whose pairs are masked.
    All float32."""
    c = gsum.shape[0]
    row = lax.broadcasted_iota(jnp.int32, gsum.shape, 0)
    refs, ref = {1: gsum}, gsum
    for b in (1, 2, 4):
        # the first row of a block of 2b rows: of its own block of b
        # in the even one, of the block before in the odd one (a roll
        # by b moves the rows down: row r takes row r - b)
        ref = jnp.where((row // b) % 2 == 1, pltpu.roll(ref, b, 0), ref)
        refs[2 * b] = ref
    out = []
    for b in _LEVELS:
        el = jnp.exp(gsum - refs[b])
        er = jnp.exp(jnp.minimum(
            pltpu.roll(refs[b], c - b, 0) - gsum, 0.0))
        left = jnp.concatenate([k * el, q * el], axis=0)
        out.append((b, left, k * er, el, er))
    return out


def _scores(q, k, gsum, mk, dtype, bounded=True):
    """(M strictly lower, P lower) of one chunk, float32 [C, C]."""
    m_rows, p_rows = [], []
    for left, right, _, _ in _sub_blocks(q, k, gsum, bounded):
        mp = _dot(left, right, _NT, dtype)
        m_rows.append(mp[:_SUB])
        p_rows.append(mp[_SUB:])
    m = jnp.concatenate(m_rows, axis=0)
    p = jnp.concatenate(p_rows, axis=0)
    if bounded:
        return (jnp.where(mk["strict"], m, 0.0),
                jnp.where(mk["lower"], p, 0.0))
    c = gsum.shape[0]
    m = jnp.where(mk["before"], m, 0.0)
    # a token's own pair decays by e^0
    p = jnp.where(mk["before"], p, 0.0) + jnp.where(
        mk["eye"], jnp.sum(q * k, axis=1, keepdims=True), 0.0)
    for b, left, right, _, _ in _levels(q, k, gsum):
        mp = _dot(left, right, _NT, dtype)
        m = m + jnp.where(mk[b], mp[:c], 0.0)
        p = p + jnp.where(mk[b], mp[c:], 0.0)
    return m, p


def _inverse(n, mk):
    """(I + n)^-1 of a strictly lower triangular float32 [C, C], C a
    multiple of 16 and at most 64."""
    def mm(a, b):
        return _dot(a, b, _NN, _F32)

    eye = jnp.where(mk["eye"], 1.0, 0.0).astype(_F32)
    x = jnp.where(mk["diag_block"], -n, 0.0)
    n_off = jnp.where(mk["diag_block"], 0.0, n)
    d_inv = eye + x
    for _ in range(3):                  # X^2, X^4, X^8
        x = mm(x, x)
        d_inv = d_inv + mm(d_inv, x)
    y = mm(d_inv, n_off)
    r = d_inv - mm(y, d_inv)
    return r + mm(mm(y, y), r)


def _chunk_forward(q, k, v, gsum, beta, z, mk, dtype, bounded,
                   t_inv=None):
    """One chunk from the transposed state z: (o, z_next, and what the
    backward keeps: t_inv, m, p, w, ut).  `t_inv`: the chunk's inverse
    where the forward pass kept it, else it is formed here."""
    m, p = _scores(q, k, gsum, mk, dtype, bounded)
    if t_inv is None:
        t_inv = _inverse(beta * m, mk)
    eg = jnp.exp(gsum)
    w = _dot(t_inv, beta * (k * eg), _NN, dtype)
    u = _dot(t_inv, beta * v, _NN, dtype)
    ut = u - _dot(w, z, _NT, dtype)
    o = _dot(q * eg, z, _NT, dtype) + _dot(p, ut, _NN, dtype)
    g_last = gsum[-1:]
    z_next = z * jnp.exp(g_last) \
        + _dot(ut, k * jnp.exp(g_last - gsum), _TN, dtype)
    return o, z_next, (t_inv, m, p, w, ut)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _side_by_side(c, chunk):
    """The lanes of chunk c's inverse in its block's [C, block] array.
    Static: Mosaic takes no lane offset it cannot prove a multiple of
    128, so the inverses pass through a [chunks, C, C] scratch that the
    chunk loops index by their first axis."""
    return slice(c * chunk, (c + 1) * chunk)


def _load(refs, rows):
    """q, k, v as float32, G, and beta's column of the chunk `rows`."""
    q_ref, k_ref, v_ref, g_ref, b_ref = refs
    return (q_ref[0, rows, :].astype(_F32), k_ref[0, rows, :].astype(_F32),
            v_ref[0, rows, :].astype(_F32), g_ref[0, rows, :],
            b_ref[0, 0, rows, :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, st_ref, ti_ref,
                z_ref, t_all, *, chunk, block_chunks, bounded):
    dtype = q_ref.dtype
    mk = _masks(chunk, bounded)

    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        z_ref[...] = jnp.zeros(z_ref.shape, _F32)

    st_ref[0, 0] = z_ref[...]

    def one_chunk(c, carry):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        q, k, v, gsum, beta = _load((q_ref, k_ref, v_ref, g_ref, b_ref),
                                    rows)
        o, z_next, (t_inv, *_) = _chunk_forward(
            q, k, v, gsum, beta, z_ref[...], mk, dtype, bounded)
        o_ref[0, rows, :] = o.astype(o_ref.dtype)
        t_all[c] = t_inv
        z_ref[...] = z_next
        return carry

    lax.fori_loop(0, block_chunks, one_chunk, 0, unroll=True)
    for c in range(block_chunks):
        ti_ref[0, 0, 0, :, _side_by_side(c, chunk)] = t_all[c]


def _specs(t, d, chunk, block, rev):
    """BlockSpecs by operand kind over the grid (B, H, T / block);
    `rev` walks the blocks from the last to the first."""
    nb = t // block

    def blk(j):
        return nb - 1 - j if rev else j

    return {
        "x": pl.BlockSpec((1, block, d), lambda i, h, j: (i, blk(j), h)),
        "beta": pl.BlockSpec((1, 1, block, 1),
                             lambda i, h, j: (i, h, blk(j), 0)),
        "state": pl.BlockSpec((1, 1, d, d),
                              lambda i, h, j: (i, blk(j), h, 0)),
        "inverse": pl.BlockSpec((1, 1, 1, chunk, block),
                                lambda i, h, j: (i, h, blk(j), 0, 0)),
    }


def _params(interpret):
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=64 << 20)}


@functools.partial(jax.jit, static_argnames=(
    "chunk", "block_chunks", "interpret", "bounded"))
def kda_fwd_pallas(q, k, v, g, beta, chunk, block_chunks, bounded=True,
                   interpret=False):
    """-> (o [B, T, H*D] in v's dtype, states float32
    [B, T/block, H*D, D], inverse float32 [B, H, T/block, C, block]).
    bounded: g >= BOUNDED_G_MIN a token is the caller's promise; False
    is exact for any g <= 0."""
    b, t, h, d = check_shapes(q, v, beta, chunk, block_chunks)
    block = chunk * block_chunks
    gsum, bcol = _prep(g, beta, chunk)
    sp = _specs(t, d, chunk, block, rev=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk,
                          block_chunks=block_chunks, bounded=bounded),
        name="pt_kda_fwd",
        grid=(b, h, t // block),
        in_specs=[sp["x"], sp["x"], sp["x"], sp["x"], sp["beta"]],
        out_specs=[sp["x"], sp["state"], sp["inverse"]],
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype),
                   jax.ShapeDtypeStruct((b, t // block, h * d, d), _F32),
                   jax.ShapeDtypeStruct((b, h, t // block, chunk, block),
                                        _F32)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32),
                        pltpu.VMEM((block_chunks, chunk, chunk), _F32)],
        interpret=interpret,
        **_params(interpret),
    )(q, k.astype(q.dtype), v.astype(q.dtype), gsum, bcol)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
# Per chunk, given dO and dZ' (the gradient of the state the chunk
# leaves), with Vb = beta V, Kb = beta Kg:
#   dUt = P^T dO + Kend dZ'^T           dP = lower(dO Ut^T)
#   dQg = dO Z                           dKend = Ut dZ'
#   dZ  = dO^T Qg + dZ' e^(G_C) - dUt^T W
#   dW  = -dUt Z                         dU = dUt
#   dT  = dU Vb^T + dW Kb^T              dVb = T^T dU,  dKb = T^T dW
#   dN  = -strict(T^T dT T^T)            dM = beta dN
#   d beta = rows(dVb V) + rows(dKb Kg) + rows(dN M)
# M and P reach Q, K and G through the sub-blocks' factors (_sub_blocks):
# with left = [K_i el; Q_i el], right = K er and dmp = [dM_i; dP_i],
#   dleft = dmp right,  dright = dmp^T left
#   dK_i += dleft_k el,  dQ_i += dleft_q el,  dK += dright er
#   dG_i += x,  dG += -y
#   x = (dleft_k K_i + dleft_q Q_i) el,   y = dright K er
# (the reference row's own share, cols(y) - cols(x), is zero: both sum
# the same pairs (r, s), one by its row and one by its column)
# The unbounded path's levels (`_levels`) are factors of the same form
# over the whole chunk, dmp masked to a level's pairs, whose two
# references are one row: the same lines, and the same zero share.
# P's diagonal q_r . k_r gives dQ_r += dP_rr K_r, dK_r += dP_rr Q_r.
# and the row-scaled operands give
#   dQ += dQg e^G, dK += dKg e^G + dKend e^(G_C - G),
#   dG += dQg Qg + dKg Kg - dKend Kend,
#   dG[last row] += cols(dKend Kend) + cols(dZ' Z) e^(G_C)

def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, b_ref, do_ref, st_ref, ti_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
                dz_ref, z_all, t_all, m_all, p_all, w_all, ut_all,
                *, chunk, block_chunks, bounded):
    dtype = q_ref.dtype
    mk = _masks(chunk, bounded)
    ins = (q_ref, k_ref, v_ref, g_ref, b_ref)
    row = lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    @pl.when(pl.program_id(2) == 0)
    def _last_block():
        dz_ref[...] = jnp.zeros(dz_ref.shape, _F32)

    def rows_of(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    for c in range(block_chunks):
        t_all[c] = ti_ref[0, 0, 0, :, _side_by_side(c, chunk)]

    # the block's chunks forward again, from the state and with the
    # inverses the forward kept
    def again(c, z):
        q, k, v, gsum, beta = _load(ins, rows_of(c))
        _, z_next, (_, m, p, w, ut) = _chunk_forward(
            q, k, v, gsum, beta, z, mk, dtype, bounded, t_inv=t_all[c])
        z_all[c], m_all[c], p_all[c] = z, m, p
        w_all[c], ut_all[c] = w, ut
        return z_next

    lax.fori_loop(0, block_chunks, again, st_ref[0, 0], unroll=True)

    def back(i, carry):
        c = block_chunks - 1 - i
        rows = rows_of(c)
        q, k, v, gsum, beta = _load(ins, rows)
        do = do_ref[0, rows, :].astype(_F32)
        z, t_inv, m, p = z_all[c], t_all[c], m_all[c], p_all[c]
        w, ut, dz_next = w_all[c], ut_all[c], dz_ref[...]
        eg = jnp.exp(gsum)
        g_last = gsum[-1:]
        a_end = jnp.exp(g_last)
        e_end = jnp.exp(g_last - gsum)
        qg, kg, kend = q * eg, k * eg, k * e_end

        dut = _dot(p, do, _TN, dtype) + _dot(kend, dz_next, _NT, dtype)
        dp = jnp.where(mk["lower"], _dot(do, ut, _NT, dtype), 0.0)
        dqg = _dot(do, z, _NN, dtype)
        dkend = _dot(ut, dz_next, _NN, dtype)
        dz_ref[...] = _dot(do, qg, _TN, dtype) + dz_next * a_end \
            - _dot(dut, w, _TN, dtype)
        d_last = jnp.sum(dz_next * z, axis=0, keepdims=True) * a_end
        dw = -_dot(dut, z, _NN, dtype)
        dt = _dot(dut, beta * v, _NT, dtype) + _dot(dw, beta * kg, _NT,
                                                    dtype)
        dvb = _dot(t_inv, dut, _TN, dtype)
        dkb = _dot(t_inv, dw, _TN, dtype)
        dn = jnp.where(mk["strict"], -_dot(
            _dot(t_inv, dt, _TN, _F32), t_inv, _NT, _F32), 0.0)
        dm = beta * dn
        dbeta = jnp.sum(dvb * v + dkb * kg, axis=1, keepdims=True) \
            + jnp.sum(dn * m, axis=1, keepdims=True)
        dkg = beta * dkb

        dq = dqg * eg
        dk = dkg * eg + dkend * e_end
        z_end = dkend * kend
        dg = dqg * qg + dkg * kg - z_end + jnp.where(
            row == chunk - 1,
            jnp.sum(z_end, axis=0, keepdims=True) + d_last, 0.0)
        if not bounded:
            # the pairs inside the diagonal blocks, level by level
            dpd = jnp.sum(jnp.where(mk["eye"], dp, 0.0), axis=1,
                          keepdims=True)
            dq = dq + dpd * k
            dk = dk + dpd * q
            for b, left, right, el, er in _levels(q, k, gsum):
                dmp = jnp.concatenate([jnp.where(mk[b], dm, 0.0),
                                       jnp.where(mk[b], dp, 0.0)], axis=0)
                dleft = _dot(dmp, right, _NN, dtype)
                dright = _dot(dmp, left, _TN, dtype)
                dl_k, dl_q = dleft[:chunk], dleft[chunk:]
                dq = dq + dl_q * el
                dk = dk + dl_k * el + dright * er
                dg = dg + (dl_k * k + dl_q * q) * el - dright * k * er
            # what is left for the sub-blocks: the columns before them
            dm = jnp.where(mk["before"], dm, 0.0)
            dp = jnp.where(mk["before"], dp, 0.0)
        dq_rows, dk_rows, dg_rows = [], [], []
        for j, (left, right, el, er) in enumerate(
                _sub_blocks(q, k, gsum, bounded)):
            sub = slice(j * _SUB, (j + 1) * _SUB)
            dmp = jnp.concatenate([dm[sub], dp[sub]], axis=0)
            dleft = _dot(dmp, right, _NN, dtype)
            dright = _dot(dmp, left, _TN, dtype)
            dl_k, dl_q = dleft[:_SUB], dleft[_SUB:]
            x = (dl_k * k[sub] + dl_q * q[sub]) * el
            y = dright * k * er
            dk = dk + dright * er
            dg = dg - y
            dq_rows.append(dl_q * el)
            dk_rows.append(dl_k * el)
            dg_rows.append(x)
        dq_ref[0, rows, :] = (dq + jnp.concatenate(dq_rows, axis=0)
                              ).astype(dq_ref.dtype)
        dk_ref[0, rows, :] = (dk + jnp.concatenate(dk_rows, axis=0)
                              ).astype(dk_ref.dtype)
        dv_ref[0, rows, :] = (beta * dvb).astype(dv_ref.dtype)
        dg_ref[0, rows, :] = dg + jnp.concatenate(dg_rows, axis=0)
        db_ref[0, 0, rows, :] = dbeta
        return carry

    lax.fori_loop(0, block_chunks, back, 0, unroll=True)


@functools.partial(jax.jit, static_argnames=(
    "chunk", "block_chunks", "interpret", "bounded"))
def kda_bwd_pallas(q, k, v, g, beta, states, inverse, do, chunk,
                   block_chunks, bounded=True, interpret=False):
    """The five input gradients (q, k, v, g, beta), each in its input's
    dtype, from the block-start states and the chunks' inverses the
    forward kept.  bounded: as the forward's."""
    b, t, h, d = check_shapes(q, v, beta, chunk, block_chunks)
    block = chunk * block_chunks
    gsum, bcol = _prep(g, beta, chunk)
    sp = _specs(t, d, chunk, block, rev=True)

    def like(x, dtype=_F32):
        return jax.ShapeDtypeStruct(x.shape, dtype)

    cc, cd = (block_chunks, chunk, chunk), (block_chunks, chunk, d)
    dq, dk, dv, dgsum, dbcol = pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk,
                          block_chunks=block_chunks, bounded=bounded),
        name="pt_kda_bwd",
        grid=(b, h, t // block),
        in_specs=[sp["x"], sp["x"], sp["x"], sp["x"], sp["beta"], sp["x"],
                  sp["state"], sp["inverse"]],
        out_specs=[sp["x"], sp["x"], sp["x"], sp["x"], sp["beta"]],
        out_shape=[like(q, q.dtype), like(q, q.dtype), like(q, q.dtype),
                   like(gsum), like(bcol)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32),
                        pltpu.VMEM((block_chunks, d, d), _F32),
                        pltpu.VMEM(cc, _F32), pltpu.VMEM(cc, _F32),
                        pltpu.VMEM(cc, _F32), pltpu.VMEM(cd, _F32),
                        pltpu.VMEM(cd, _F32)],
        interpret=interpret,
        **_params(interpret),
    )(q, k.astype(q.dtype), v.astype(q.dtype), gsum, bcol,
      do.astype(q.dtype), states, inverse)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype),
            _finish(dgsum, chunk).astype(g.dtype),
            dbcol[..., 0].transpose(0, 2, 1).astype(beta.dtype))


def _finish(dgsum, chunk):
    """G is the chunk's running sum of g: d g_t is the sum of d G_r over
    the chunk's r >= t."""
    b, t, width = dgsum.shape
    return jnp.flip(jnp.cumsum(jnp.flip(
        dgsum.reshape(b, t // chunk, chunk, width), 2), axis=2), 2
    ).reshape(b, t, width)

"""EVA attention's kernels (ops/pallas_eva.py) in interpret mode
against their XLA forms, and both against what they must reduce to.

* the summariser (pt_eva_pool_fwd / pt_eva_pool_bwd) and the
  aggregation (a window's causal flash merged with the staircase
  pt_eva_chunk_fwd / pt_eva_chunk_bwd) against `eva_pool_xla` and
  `eva_attention_xla` on out, dq, dk, dv, dmu, dphi, float32 and
  bfloat16, the gradients through the summaries included;
* the two identities: chunks of ONE token, and a sequence no longer
  than a window, are causal attention (`_plain_attention`), the first
  within every window's own tokens and one key a token before it;
* the staircase's grid walked on the host: each live (q block,
  chunk-key block) pair once, none of a query's own or a later window,
  the forward's and the backward's first and last steps where the
  kernels expect them;
* lengths that are not whole chunks or, past a window, whole windows
  are refused; the ops count their impl and name their kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import pallas_eva as pe
from paddle_tpu.ops import pallas_kernels as pk

D = 128
F32_TOL = 2e-5
BF16_TOL = 3e-2


def _operands(t, heads, dtype=jnp.float32, seed=0, d=D):
    rng = np.random.default_rng(seed)

    def normal(scale, *shape):
        return jnp.asarray(rng.normal(0, scale, shape), jnp.float32)

    q, k, v, g = (normal(s, 1, t, heads * d).astype(dtype)
                  for s in (0.3, 0.3, 1.0, 1.0))
    return q, k, v, g, normal(0.3, heads, d), normal(0.3, heads, d)


def _xla(q, k, v, mu, phi, heads, window, chunk):
    ks, vs = pe.eva_pool_xla(k, v, mu, phi, heads, chunk)
    return pe.eva_attention_xla(q, k, v, ks, vs, heads, window, chunk,
                                (q.shape[-1] // heads) ** -0.5)[0]


def _kernels(q, k, v, mu, phi, heads, window, chunk):
    ks, vs = pe.eva_pool_kernels(k, v, mu, phi, heads, chunk, True)
    return pe.eva_attention_kernels(
        q, k, v, ks, vs, heads, window, chunk,
        (q.shape[-1] // heads) ** -0.5, True)[0]


def _out_and_grads(fn, q, k, v, g, mu, phi, *geometry):
    def f(q, k, v, mu, phi):
        out = fn(q, k, v, mu, phi, *geometry)
        return (out.astype(jnp.float32) * g.astype(jnp.float32)).sum(), out

    grads, out = jax.grad(f, argnums=(0, 1, 2, 3, 4), has_aux=True)(
        q, k, v, mu, phi)
    return dict(zip(("out", "dq", "dk", "dv", "dmu", "dphi"),
                    (out, *grads)))


def _errors(got, want):
    return {n: float(jnp.abs(got[n].astype(jnp.float32)
                             - want[n].astype(jnp.float32)).max()
                     / jnp.abs(want[n].astype(jnp.float32)).max())
            for n in want}


# (tokens, heads, window, chunk): four windows with 16 chunk keys each;
# two windows of 32 chunk keys; three windows, one head
GEOMETRIES = [(512, 2, 128, 8), (512, 2, 256, 8), (768, 1, 256, 16)]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, F32_TOL),
                                       (jnp.bfloat16, BF16_TOL)])
@pytest.mark.parametrize("t,heads,window,chunk", GEOMETRIES)
def test_kernels_against_the_xla_form(t, heads, window, chunk, dtype, tol):
    q, k, v, g, mu, phi = _operands(t, heads, dtype)
    geometry = (heads, window, chunk)
    got = _out_and_grads(_kernels, q, k, v, g, mu, phi, *geometry)
    want = _out_and_grads(_xla, q, k, v, g, mu, phi, *geometry)
    errors = _errors(got, want)
    assert max(errors.values()) <= tol, errors
    assert got["out"].dtype == dtype and got["dk"].dtype == dtype
    assert got["dmu"].dtype == jnp.float32
    # mu and phi are read: their gradients are not zero
    assert float(jnp.abs(want["dmu"]).max()) > 0
    assert float(jnp.abs(want["dphi"]).max()) > 0


def test_the_summariser_alone_against_the_xla_form():
    """The pooled keys and values and all four gradients, the cotangents
    of k~ and v~ given apart."""
    _, k, v, _, mu, phi = _operands(512, 2, seed=3)
    rng = np.random.default_rng(4)
    dks, dvs = (jnp.asarray(rng.normal(0, 1, (1, 64, 256)), jnp.float32)
                for _ in range(2))
    (ks, vs), vjp = jax.vjp(
        lambda *a: pe.eva_pool_xla(*a, 2, 8), k, v, mu, phi)
    got = pe.eva_pool_fwd_pallas(k, v, mu, phi, heads=2, chunk=8,
                                 interpret=True)
    np.testing.assert_allclose(got[0], ks, atol=2e-6)
    np.testing.assert_allclose(got[1], vs, atol=2e-6)
    want = vjp((dks, dvs))
    for rows in (None, 256):
        grads = pe.eva_pool_bwd_pallas(k, v, mu, phi, dks, dvs, heads=2,
                                       chunk=8, interpret=True, rows=rows)
        for a, b in zip(grads, want):
            assert float(jnp.abs(a - b).max() / jnp.abs(b).max()) <= 2e-5
    # a chunk's weights sum to one: with mu = phi = 0 the summary is
    # the chunk's mean
    zero = jnp.zeros_like(mu)
    ks0, vs0 = pe.eva_pool_fwd_pallas(k, v, zero, zero, heads=2, chunk=8,
                                      interpret=True)
    np.testing.assert_allclose(ks0, k.reshape(1, 64, 8, 256).mean(2),
                               atol=2e-6)
    np.testing.assert_allclose(vs0, v.reshape(1, 64, 8, 256).mean(2),
                               atol=2e-6)


# -- the two identities ----------------------------------------------------------

def _plain(q, k, v, heads, window=0):
    """`_plain_attention` on token-major operands."""
    out = pk._plain_attention(
        pk._split_heads(q, heads), pk._split_heads(k, heads),
        pk._split_heads(v, heads), True, (q.shape[-1] // heads) ** -0.5,
        window=window)
    return pk._merge_heads(out)


@pytest.mark.parametrize("form", ["xla", "interpret"])
@pytest.mark.parametrize("t,window", [(256, 256), (128, 256), (256, 4096)])
def test_a_sequence_within_one_window_is_causal_attention(form, t, window):
    """T <= W: one window, no chunk key: out, dq, dk and dv are plain
    causal attention's, and mu and phi move nothing."""
    q, k, v, g, mu, phi = _operands(t, 2, seed=5)
    fn = _xla if form == "xla" else _kernels
    got = _out_and_grads(fn, q, k, v, g, mu, phi, 2, window, 8)
    want = _out_and_grads(lambda q, k, v, mu, phi: _plain(q, k, v, 2),
                          q, k, v, g, mu, phi)
    for n in ("out", "dq", "dk", "dv"):
        assert _errors(got, want)[n] <= F32_TOL, n
    assert not float(jnp.abs(got["dmu"]).max())
    assert not float(jnp.abs(got["dphi"]).max())


@pytest.mark.parametrize("form", ["xla", "interpret"])
def test_chunks_of_one_token_are_causal_attention(form):
    """c = 1: a chunk's softmax is over its one token, so k~ = k and
    v~ = v whatever mu and phi: the token keys of a query's own window
    and every token of the earlier windows, each once: causal
    attention over the whole sequence."""
    q, k, v, g, mu, phi = _operands(512, 2, seed=6)
    if form == "xla":
        got = _out_and_grads(_xla, q, k, v, g, mu, phi, 2, 128, 1)
    else:
        # the op's geometry rule sends c = 1 to the XLA form on a chip
        # (a chunk is less than a sublane tile); interpret mode has no
        # tiles
        assert not pe.kernel_geom_ok(512, D, 128, 1)
        got = _out_and_grads(_kernels, q, k, v, g, mu, phi, 2, 128, 1)
    want = _out_and_grads(lambda q, k, v, mu, phi: _plain(q, k, v, 2),
                          q, k, v, g, mu, phi)
    for n in ("out", "dq", "dk", "dv"):
        assert _errors(got, want)[n] <= F32_TOL, n
    assert float(jnp.abs(got["dmu"]).max()) <= 1e-6


def test_the_window_is_aligned_and_not_sliding():
    """Query W (the first of window 1) sees ONE token key, its own, and
    the W/c chunk keys of window 0, by hand; a sliding band would give
    it W token keys."""
    q, k, v, _, mu, phi = _operands(512, 2, seed=7)
    ks, vs = pe.eva_pool_xla(k, v, mu, phi, 2, 8)
    out = pe.eva_attention_xla(q, k, v, ks, vs, 2, 128, 8, D ** -0.5)[0]
    for h in range(2):
        lanes = slice(h * D, (h + 1) * D)
        keys = np.concatenate([k[0, 128:129, lanes], ks[0, :16, lanes]])
        values = np.concatenate([v[0, 128:129, lanes], vs[0, :16, lanes]])
        s = keys @ np.asarray(q[0, 128, lanes]) * D ** -0.5
        p = np.exp(s - s.max())
        np.testing.assert_allclose(out[0, 128, lanes],
                                   (p / p.sum()) @ values, atol=1e-5)
    band = _plain(q, k, v, 2, window=128)
    assert float(jnp.abs(band[0, 128] - out[0, 128]).max()) > 0.05


# -- the staircase's grid, walked on the host ------------------------------------

@pytest.mark.parametrize("t,window,chunk,bq,bk", [
    (8192, 2048, 16, 1024, 128), (8192, 2048, 16, 2048, 128),
    (8192, 2048, 16, 512, 64), (32768, 2048, 16, 1024, 128),
    (512, 128, 8, 128, 16), (768, 256, 16, 64, 8), (2048, 2048, 16, 1024,
                                                   128)])
def test_the_staircase_walks_each_live_pair_once_and_no_dead_one(
        t, window, chunk, bq, bk):
    cpw = window // chunk
    assert pe.chunk_blocks(window, chunk, bq, bk) == (bq, bk)
    # the pairs of blocks that hold a visible (query, chunk) pair, by
    # brute force over the rule itself
    i = np.arange(t)[:, None]
    j = np.arange(t // chunk)[None, :]
    visible = (j * chunk) // window < i // window
    blocks = visible.reshape(t // bq, bq, t // chunk // bk, bk)
    live = blocks.any(axis=(1, 3))
    # every block pair is wholly visible or wholly dead: no mask
    assert (blocks.all(axis=(1, 3)) == live).all()
    for major in ("q", "k"):
        qi, kj = pe.staircase(t, window, chunk, bq, bk, major)
        assert qi.dtype == kj.dtype == np.int32
        walked = list(zip(qi.tolist(), kj.tolist()))
        assert len(set(walked)) == len(walked)             # each once
        assert set(walked) == set(zip(*np.nonzero(live)))  # all, no other
        # none of the query's own or a later window
        assert all((b * bk * chunk) // window < (a * bq) // window
                   for a, b in walked)
    assert len(walked) == int(live.sum())
    # the forward: a q block's pairs side by side, kj rising from 0 (the
    # step that zeroes the running softmax) to the block's last live
    # one (the step that writes)
    qi, kj = pe.staircase(t, window, chunk, bq, bk, "q")
    for a in set(qi.tolist()):
        mine = kj[qi == a]
        assert (np.diff(np.nonzero(qi == a)[0]) == 1).all()
        assert mine.tolist() == list(range(len(mine)))
        assert len(mine) == pe._live_blocks(a, bq, bk, window, cpw)
    # window 0's q blocks are no step at all
    assert (qi * bq >= window).all() if len(qi) else t <= window
    # the backward: a chunk-key block's q blocks side by side, from the
    # first block of the next window (the step that zeroes dk~, dv~) to
    # the last block of the sequence (the step that writes them)
    qi, kj = pe.staircase(t, window, chunk, bq, bk, "k")
    for b in set(kj.tolist()):
        mine = qi[kj == b]
        first = (b * bk // cpw + 1) * (window // bq)
        assert mine.tolist() == list(range(first, t // bq))
    # the chunks of the last window are nobody's key
    assert (kj * bk < (t - window) // chunk).all()


def test_the_cells_staircase_in_numbers():
    """8,192 bytes in four windows: windows 1-3 read 128, 256 and 384
    chunk keys; at 2,048 x 128 blocks 1 + 2 + 3 = 6 steps a head."""
    qi, kj = pe.staircase(8192, 2048, 16, *pe.chunk_blocks(2048, 16))
    assert pe.chunk_blocks(2048, 16) == (2048, 128)
    assert len(qi) == 6
    assert [int((qi == a).sum()) for a in range(4)] == [0, 1, 2, 3]
    assert pe.kernel_geom_ok(8192, 128, 2048, 16)
    assert not pe.kernel_geom_ok(8192, 64, 2048, 16)


@pytest.mark.parametrize("bq,bk", [(768, 128), (1024, 96), (4096, 128)])
def test_blocks_that_do_not_divide_are_refused(bq, bk):
    with pytest.raises(ValueError, match="do not divide"):
        pe.chunk_blocks(2048, 16, bq, bk)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_the_staircase_reads_the_statistic_where_it_lies(direction):
    """No lane-replicated copy of the row statistic is made round the
    staircase calls (134 MB a layer and pass at the cell's shape): the
    calls take a free reshape of the [B T/W, H, W] statistic."""
    q, k, v, g, mu, phi = _operands(512, 2, seed=5)
    ks, vs = pe.eva_pool_xla(k, v, mu, phi, 2, 8)
    lse = jnp.zeros((4, 2, 128), jnp.float32)
    geometry = dict(heads=2, window=128, chunk=8, scale=D ** -0.5,
                    interpret=True)
    if direction == "forward":
        jaxpr = jax.make_jaxpr(lambda *a: pe.eva_chunk_fwd_pallas(
            *a, **geometry))(q, ks, vs, v, lse)
    else:
        jaxpr = jax.make_jaxpr(lambda *a: pe.eva_chunk_bwd_pallas(
            *a, **geometry))(q, ks, vs, v, lse, g, k)
    (inner,) = [e.params["jaxpr"] for e in jaxpr.eqns]     # the jit
    shapes = {var.aval.shape for e in inner.eqns
              if e.primitive.name != "pallas_call" for var in e.outvars}
    assert (8, 1, 128) in shapes            # [B T/W H, 1, W]: a reshape
    assert (8, 128, D) not in shapes        # [B T/W H, W, 128 lanes]


def test_a_row_statistic_stands_up():
    """`_column`, the backward kernel's relayout of a q block's
    statistic: [1, n] along the lanes -> [n, 128] lane-replicated."""
    row = jnp.arange(256, dtype=jnp.float32)[None, :]
    column = pe._column(row)
    assert column.shape == (256, 128)
    assert (column == row[0][:, None]).all()


# -- the ops ---------------------------------------------------------------------

def _attrs(op, **attrs):
    return get_op_def(op).canonical_attrs(attrs)


@pytest.mark.parametrize("t,window,chunk", [
    (520, 128, 16),      # not whole chunks
    (320, 128, 8),       # past a window, not whole windows
    (512, 128, 12)])     # the chunk divides no window
def test_lengths_that_do_not_fit_are_refused(t, window, chunk):
    q, k, v, _, mu, phi = _operands(t, 1)
    attrs = _attrs("eva_pool", heads=1, window=window, chunk=chunk)
    with pytest.raises(ValueError, match="eva_attention"):
        get_op_def("eva_pool").compute(
            {"K": k, "V": v, "Mu": mu, "Phi": phi}, attrs)


def _counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_ops_count_their_impl_and_the_grad_ops_read_what_was_saved(
        impl):
    q, k, v, g, mu, phi = _operands(512, 2, seed=8)
    attrs = _attrs("eva_pool", heads=2, window=128, chunk=8, impl=impl)
    before = _counts()
    pool, attn = get_op_def("eva_pool"), get_op_def("eva_attention")
    pooled = pool.compute({"K": k, "V": v, "Mu": mu, "Phi": phi}, attrs)
    ins = {"Q": q, "K": k, "V": v, **pooled}
    outs = attn.compute(ins, attrs)
    assert outs["LSE"].shape == (4, 2, 128)       # a window a row
    assert outs["LSE"].dtype == jnp.float32
    want = _xla(q, k, v, mu, phi, 2, 128, 8)
    assert float(jnp.abs(outs["Out"] - want).max()) <= 1e-5
    grad = get_op_def("eva_attention_grad")
    assert grad.reads_saved({**ins, **outs}, attrs) == (impl != "xla")
    d_attn = grad.compute({**ins, **outs, "Out@GRAD": g}, attrs)
    pool_grad = get_op_def("eva_pool_grad")
    assert pool_grad.reads_saved(
        {"K": k, "V": v, "Mu": mu, "Phi": phi, **pooled}, attrs) \
        == (impl != "xla")
    d_pool = pool_grad.compute(
        {"K": k, "V": v, "Mu": mu, "Phi": phi,
         "KSum@GRAD": d_attn["KSum@GRAD"],
         "VSum@GRAD": d_attn["VSum@GRAD"]}, attrs)
    whole = _out_and_grads(_xla, q, k, v, g, mu, phi, 2, 128, 8)
    got = {"dq": d_attn["Q@GRAD"],
           "dk": d_attn["K@GRAD"] + d_pool["K@GRAD"],
           "dv": d_attn["V@GRAD"] + d_pool["V@GRAD"],
           "dmu": d_pool["Mu@GRAD"], "dphi": d_pool["Phi@GRAD"]}
    errors = _errors(got, {n: whole[n] for n in got})
    assert max(errors.values()) <= F32_TOL, errors
    used = {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}
    assert used[("eva_pool", impl)] == 1
    # the xla impl's grad op differentiates the forward op's compute,
    # which counts itself again
    assert used[("eva_attention", impl)] == (2 if impl == "xla" else 1)
    assert used[("eva_attention_grad",
                 "saved" if impl != "xla" else "recompute")] == 1


def test_a_head_size_the_kernels_cannot_tile_takes_the_xla_form():
    """Heads of 64: the op resolves to xla whatever the platform says,
    and counts it."""
    q, k, v, _, mu, phi = _operands(256, 2, d=64)
    attrs = _attrs("eva_pool", heads=2, window=128, chunk=8,
                   impl="interpret")
    before = _counts()
    get_op_def("eva_pool").compute(
        {"K": k, "V": v, "Mu": mu, "Phi": phi}, attrs)
    assert _counts()[("eva_pool", "xla")] \
        == before.get(("eva_pool", "xla"), 0) + 1


def test_the_kernels_names():
    """The Mosaic calls carry fixed names, under the ops' scopes."""
    q, k, v, g, mu, phi = _operands(512, 2)
    text = jax.jit(lambda *a: _out_and_grads(_kernels, *a, 2, 128, 8)) \
        .lower(q, k, v, g, mu, phi).as_text(debug_info=True)
    for name in ("pt_eva_pool_fwd", "pt_eva_pool_bwd", "pt_eva_chunk_fwd",
                 "pt_eva_chunk_bwd", "pt_flash_fwd", "pt_flash_bwd_dkv"):
        assert name in text, name

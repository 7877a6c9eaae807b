"""A sliding window in the flash kernels (ISSUE 53): query i sees the
`window` keys that end at its own, i - window < j <= i.

The window is the second edge of `pk._Diagonal`, the ONE statement of
which block pair runs: the kernel bodies read `run` and `inside`, the
BlockSpec index maps its solved forms (`first_ki` beside `last_ki`,
`last_qi` beside `first_qi`), and the grids' inner axis is the band's
steps (`band_steps`), not all the blocks.  Here: the predicate against
its solved forms and against a brute-force count of allowed scores;
the calls' own grids and index maps walked on the host (every live
pair is one step, no pair below the band is one); forward, dq, dk and
dv of the kernels in interpret mode against `_plain_attention` with the
same window; a window that reaches every key against the causal call,
bit for bit; the counter, the names, the entries and the layer.  All
on the CPU.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import pallas_kernels as pk

Geometry = collections.namedtuple(
    "Geometry", "b h hkv tq tk d dv bq bk token_major window")

# b, h, hkv, tq, tk, d, dv, block_q, block_k, token-major, window
GEOMETRIES = {
    # one key a query: the diagonal alone
    "w1": Geometry(1, 2, 2, 48, 48, 8, 8, 16, 16, False, 1),
    "w1_bq_ne_bk": Geometry(1, 1, 1, 48, 48, 8, 8, 8, 16, False, 1),
    # blocks that divide the window: pairs wholly inside the band
    "w96_divides": Geometry(2, 2, 2, 256, 256, 8, 8, 32, 32, False, 96),
    "w96_bq_lt_bk": Geometry(1, 2, 2, 256, 256, 8, 8, 16, 48, False, 96),
    "w96_bq_gt_bk": Geometry(1, 2, 2, 256, 256, 8, 8, 96, 32, False, 96),
    # ... and that do not
    "w96_not": Geometry(1, 2, 2, 240, 240, 8, 8, 40, 40, False, 96),
    "w96_not_bq_ne_bk": Geometry(1, 1, 1, 240, 240, 8, 8, 24, 40, False,
                                 96),
    # a length that is no multiple of the block
    "w96_pads": Geometry(1, 2, 2, 200, 200, 8, 8, 32, 32, False, 96),
    "w40_pads_bq_ne_bk": Geometry(1, 1, 1, 100, 100, 8, 8, 16, 24, False,
                                  40),
    # tq != tk: the last tq queries of tk keys, and more queries than
    # keys (the first rows see nothing)
    "w40_tq_lt_tk": Geometry(1, 2, 2, 40, 104, 8, 8, 8, 16, False, 40),
    "w24_tq_lt_tk_off_a_block": Geometry(1, 1, 1, 24, 60, 8, 8, 8, 8,
                                         False, 24),
    "w16_tq_gt_tk": Geometry(1, 2, 2, 48, 16, 8, 8, 8, 8, False, 12),
    # the cell's window at a length it cuts
    "w1024_b256": Geometry(1, 1, 1, 1536, 1536, 8, 8, 256, 256, False,
                           1024),
    "w1024_b512": Geometry(1, 1, 1, 1536, 1536, 8, 8, 512, 512, False,
                           1024),
    "w1024_b384": Geometry(1, 1, 1, 1536, 1536, 8, 8, 384, 384, False,
                           1024),
    # grouped KV heads, both layouts
    "w24_group8_head_major": Geometry(1, 8, 1, 64, 64, 8, 8, 16, 16,
                                      False, 24),
    "w24_group8_token_major_d128": Geometry(1, 8, 1, 64, 64, 128, 128,
                                            16, 16, True, 24),
    "w24_group2_token_major_d64": Geometry(2, 4, 2, 64, 64, 64, 64, 16,
                                           16, True, 24),
    "w20_token_major_d64_pads": Geometry(1, 2, 2, 40, 56, 64, 64, 16, 16,
                                         True, 20),
    # latent attention's two head sizes
    "w24_latent_192_128": Geometry(1, 2, 2, 64, 64, 192, 128, 16, 16,
                                   False, 24),
}

# interpret mode walks every grid step on the host: the long ones run
# the one-sweep backward only
LONG = {"w1024_b256", "w1024_b512", "w1024_b384"}

KERNELS = ("fwd", "one_sweep", "dq", "dkv")
NAMES = {"fwd": "pt_flash_win_fwd", "one_sweep": "pt_flash_win_bwd_dkv",
         "dq": "pt_flash_win_bwd_dq", "dkv": "pt_flash_win_bwd_dkv"}
Q_OUTER = {"q": 1, "k": 2, "v": 2, "do": 1, "lse": 1, "delta": 1}
KV_OUTER = {"q": 2, "k": 1, "v": 1, "do": 2, "lse": 2, "delta": 2}
INPUTS = {"fwd": {n: Q_OUTER[n] for n in ("q", "k", "v")},
          "dq": Q_OUTER, "one_sweep": KV_OUTER, "dkv": KV_OUTER}


def _shapes(geo, dtype=jnp.float32):
    def one(heads, t, width):
        if geo.token_major:
            return jax.ShapeDtypeStruct((geo.b, t, heads * width), dtype)
        return jax.ShapeDtypeStruct((geo.b, heads, t, width), dtype)

    return (one(geo.h, geo.tq, geo.d), one(geo.hkv, geo.tk, geo.d),
            one(geo.hkv, geo.tk, geo.dv), one(geo.h, geo.tq, geo.dv))


def _static(geo, window=None):
    return dict(causal=True, scale=geo.d ** -0.5, block_q=geo.bq,
                block_k=geo.bk, interpret=True,
                heads=geo.h if geo.token_major else None,
                window=geo.window if window is None else window)


def _calls(geo, window=None):
    """{kernel: (name, grid, in_specs, out_specs)} of the
    `pl.pallas_call`s the forward, the one-sweep backward and the
    two-sweep backward make.  Traced abstractly: no kernel runs."""
    seen = []

    def record(kernel, *, name, grid, in_specs, out_specs, out_shape,
               **_):
        seen.append((name, grid, in_specs, out_specs
                     if isinstance(out_specs, (list, tuple))
                     else [out_specs]))
        return lambda *operands: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

    q, k, v, g = _shapes(geo)
    kw = _static(geo, window)
    tq_p = -(-geo.tq // geo.bq) * geo.bq
    o = jax.ShapeDtypeStruct(g.shape, g.dtype)
    lse = jax.ShapeDtypeStruct((geo.b * geo.h, tq_p), jnp.float32)
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pk.pl, "pallas_call", record)
        jax.eval_shape(lambda *a: pk._flash_fwd_pallas.__wrapped__(
            *a, **kw), q, k, v)
        jax.eval_shape(lambda *a: pk._flash_bwd_pallas.__wrapped__(
            *a, one_sweep_vmem=1 << 20, **kw), q, k, v, o, lse, g)
        jax.eval_shape(lambda *a: pk._flash_bwd_pallas.__wrapped__(
            *a, one_sweep_vmem=None, **kw), q, k, v, o, lse, g)
    assert len(seen) == 4
    return dict(zip(("fwd", "one_sweep", "dq", "dkv"), seen))


def _walk(spec, grid):
    """A BlockSpec's block index at every step: [3, *grid]."""
    steps = np.indices(grid)
    return np.stack([np.broadcast_to(np.asarray(x), grid)
                     for x in spec.index_map(*steps)])


def _allowed(tq, tk, window):
    """[tq, tk] bool by the two inequalities, written out."""
    i = np.arange(tq)[:, None] + (tk - tq)
    j = np.arange(tk)[None, :]
    return (j <= i) & (j > i - window)


# -- the band alone: predicate, solved forms, grid size -----------------------

@pytest.mark.parametrize("window", [1, 7, 16, 24, 45, 200])
@pytest.mark.parametrize("bq,bk", [(8, 8), (8, 16), (16, 8), (24, 16),
                                   (16, 40)])
@pytest.mark.parametrize("q_off", [0, 5, 16, 37, -8, -21])
def test_band_solves_its_own_predicate(bq, bk, q_off, window):
    diagonal = pk._Diagonal(bq, bk, q_off, window)
    nq, nk = 9, 7
    qi, ki = np.indices((nq, nk))
    live = np.asarray(diagonal.run(qi, ki))
    # the predicate is the scores': a pair runs iff it holds an allowed
    # score, and is `inside` iff every score of it is allowed
    i = np.arange(nq * bq)[:, None] + q_off
    j = np.arange(nk * bk)[None, :]
    scores = ((j <= i) & (j > i - window)).reshape(nq, bq, nk, bk)
    assert np.array_equal(live, scores.any((1, 3)))
    assert np.array_equal(np.asarray(diagonal.inside(qi, ki)),
                          scores.all((1, 3)))
    first_k = np.asarray(diagonal.first_ki(np.arange(nq), nk))
    last_k = np.asarray(diagonal.last_ki(np.arange(nq), nk))
    first_q = np.asarray(diagonal.first_qi(np.arange(nk), nq))
    last_q = np.asarray(diagonal.last_qi(np.arange(nk), nq))
    for r in range(nq):
        want = np.flatnonzero(live[r])
        if want.size:
            assert (first_k[r], last_k[r]) == (want.min(), want.max()), r
            assert live[r, want.min():want.max() + 1].all()   # contiguous
        assert 0 <= first_k[r] <= last_k[r] < nk
    for c in range(nk):
        want = np.flatnonzero(live[:, c])
        if want.size:
            assert (first_q[c], last_q[c]) == (want.min(), want.max()), c
            assert live[want.min():want.max() + 1, c].all()
        assert 0 <= first_q[c] <= last_q[c] < nq
    # the grid's inner axis: the longest band, never more than the
    # issue's bound, and Python ints all the way (it sizes a grid)
    kv_steps = diagonal.band_steps(nq, nk, "kv")
    q_steps = diagonal.band_steps(nq, nk, "q")
    assert isinstance(kv_steps, int) and isinstance(q_steps, int)
    assert kv_steps == max(1, live.sum(1).max())
    assert q_steps == max(1, live.sum(0).max())
    assert kv_steps <= min(nk, -(-(window + bq) // bk) + 1)
    assert q_steps <= min(nq, -(-(window + bk) // bq) + 1)


def test_no_window_is_the_diagonal_it_was():
    """Three fields as before: the fourth defaults to no window, whose
    band is everything under the diagonal."""
    old = pk._Diagonal(16, 8, 5)
    assert old == pk._Diagonal(16, 8, 5, 0) and old.window == 0
    qi, ki = np.indices((6, 9))
    assert np.array_equal(
        np.asarray(old.run(qi, ki)),
        ki * 8 <= 5 + qi * 16 + 15)
    assert old.first_ki(3, 9) == 0 and old.last_qi(3, 6) == 5
    assert old.band_block(2, 4, 9, "kv") == (4, True)


# -- the calls' own grids and maps, walked on the host -----------------------

@pytest.fixture(scope="module")
def maps():
    cache = {}

    def get(case):
        if case not in cache:
            cache[case] = _calls(GEOMETRIES[case])
        return cache[case]

    return get


def _band(geo, kernel, grid):
    """(abs, live): the absolute inner block of every step of a head's
    band grid and whether it runs, by the kernels' own `band_block` and
    `run`; and the full [nq, nk] map of pairs that run."""
    diagonal = pk._Diagonal(geo.bq, geo.bk, geo.tk - geo.tq, geo.window)
    nq, nk = -(-geo.tq // geo.bq), -(-geo.tk // geo.bk)
    outer, j = np.indices(grid[1:])
    if kernel in ("fwd", "dq"):
        inner, there = diagonal.band_block(outer, j, nk, "kv")
        live = np.asarray(diagonal.run(outer, inner)) & np.asarray(there)
    else:
        inner, there = diagonal.band_block(outer, j, nq, "q")
        live = np.asarray(diagonal.run(inner, outer)) & np.asarray(there)
    qi, ki = np.indices((nq, nk))
    return np.asarray(inner), live, np.asarray(diagonal.run(qi, ki))


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("case", sorted(GEOMETRIES))
def test_grid_walks_the_band_and_nothing_below_it(maps, case, kernel):
    geo = GEOMETRIES[case]
    name, grid, in_specs, _ = maps(case)[kernel]
    assert name == NAMES[kernel]
    nq, nk = -(-geo.tq // geo.bq), -(-geo.tk // geo.bk)
    hpb = 128 // geo.d if geo.token_major else 1
    assert grid[0] == geo.b * geo.h // hpb
    if kernel in ("fwd", "dq"):
        assert grid[1] == nq
        assert grid[2] <= min(nk, -(-(geo.window + geo.bq) // geo.bk) + 1)
    else:
        assert grid[1] == nk
        assert grid[2] <= min(nq, -(-(geo.window + geo.bk) // geo.bq) + 1)
    inner, live, pairs = _band(geo, kernel, grid)
    # every pair that runs is exactly one step of the grid
    seen = np.zeros_like(pairs, dtype=int)
    outer = np.indices(grid[1:])[0]
    at = (outer[live], inner[live]) if kernel in ("fwd", "dq") \
        else (inner[live], outer[live])
    np.add.at(seen, at, 1)
    assert np.array_equal(seen, pairs.astype(int))
    # no outer block's sweep is all dead where it has a pair to run,
    # and the longest band fills the axis: no step to spare
    assert live.sum(1).max() == grid[2] or not pairs.any()
    n_inner = nk if kernel in ("fwd", "dq") else nq
    for which, spec in zip(INPUTS[kernel], in_specs):
        rows = _walk(spec, grid)[1]                  # [g, outer, j]
        if INPUTS[kernel][which] == 1:
            assert (rows == rows[:, :, :1]).all(), which
            assert np.array_equal(rows[0, :, 0], np.arange(grid[1]))
            continue
        # a step that runs fetches its own block ...
        want = np.broadcast_to(inner[None], grid)
        at = np.broadcast_to(live[None], grid)
        assert np.array_equal(rows[at], want[at]), which
        # ... a dead one holds the last that ran: one fetch a live step
        some = live.any(1)
        changes = 1 + (rows[:, :, 1:] != rows[:, :, :-1]).sum(2)
        assert np.array_equal(
            changes[:, some],
            np.broadcast_to(live.sum(1)[some][None],
                            changes[:, some].shape)), which
        assert rows.min() >= 0 and rows.max() < n_inner, which


def test_the_cells_band():
    """`mellum2_12b_train_s16k`'s window layers: 1 x 16,384 tokens, 32
    query heads on 4 KV heads of 128 token-major, a window of 1,024 at
    the blocks `_default_block` gives it (the length's own 1,024: the
    chip's choice).  A q block meets 2 of the 16 kv blocks, the grid has
    2 steps a q block and every one of them runs but the first q
    block's spare; at 512-row blocks 3 of 32 (the issue's 3 or 4)."""
    block = pk._default_block(16384, 1024)
    assert block == 1024 == pk._default_block(16384)
    for block, steps, spare in ((1024, 2, 1), (512, 3, 3)):
        geo = Geometry(1, 32, 4, 16384, 16384, 128, 128, block, block,
                       True, 1024)
        calls = _calls(geo)
        nq = 16384 // block
        for kernel in ("fwd", "one_sweep"):
            name, grid, _, _ = calls[kernel]
            assert name == NAMES[kernel]
            assert grid == (32, nq, steps)
            _, live, pairs = _band(geo, kernel, grid)
            assert live.sum() == pairs.sum() == nq * steps - spare
        # the causal call's grid over the same operands: nq x nq
        _, grid, _, _ = _calls(geo, window=0)["fwd"]
        assert grid == (32, nq, nq)


def test_a_call_without_a_window_builds_what_it_built(maps):
    """Names, grids and maps of a causal call are those of
    tests/test_flash_causal_fetch.py: the blocks, not a band's steps."""
    geo = GEOMETRIES["w96_divides"]
    calls = _calls(geo, window=0)
    assert [calls[k][0] for k in KERNELS] == [
        "pt_flash_fwd", "pt_flash_bwd_dkv", "pt_flash_bwd_dq",
        "pt_flash_bwd_dkv"]
    nq = nk = 256 // 32
    assert calls["fwd"][1] == (4, nq, nk) == calls["dq"][1]
    assert calls["dkv"][1] == (4, nk, nq) == calls["one_sweep"][1]
    diagonal = pk._Diagonal(32, 32, 0)
    k_rows = _walk(calls["fwd"][2][1], calls["fwd"][1])[1]
    qi, ki = np.indices((nq, nk))
    assert np.array_equal(
        k_rows[0], np.minimum(ki, np.asarray(diagonal.last_ki(qi, nk))))
    assert maps("w96_divides")["fwd"][1] == (4, nq, 4)


# -- interpret mode: forward, dq, dk, dv against plain attention -------------

def _operands(geo, dtype=jnp.float32, seed=3):
    rng = np.random.RandomState(seed)
    # scores of a few units: a softmax far from uniform
    scale = (4.0, 4.0, 1.0, 1.0)
    return tuple(jnp.asarray(rng.randn(*s.shape) * m / geo.d ** 0.25,
                             dtype) for s, m in zip(_shapes(geo), scale))


def _plain(geo, q, k, v, g, window):
    """Out and the three gradients of plain attention, head-major."""
    def f(q, k, v):
        out = pk._plain_attention(q, k, v, True, geo.d ** -0.5,
                                  window=window)
        return (out * g).sum(), out

    grads, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return dict(zip(("out", "dq", "dk", "dv"), (out, *grads)))


def _kernels(geo, ops, sweeps, window=None):
    kw = _static(geo, window)
    out, lse = pk._flash_fwd_pallas(*ops[:3], **kw)
    vmem = (64 << 20) if sweeps == "one_sweep" else None
    grads = pk._flash_bwd_pallas(
        *ops[:3], out, lse, ops[3], one_sweep_vmem=vmem, **kw)
    return dict(zip(("out", "lse", "dq", "dk", "dv"), (out, lse, *grads)))


@functools.lru_cache(maxsize=None)
def _causal_kernels(case, sweeps):
    geo = GEOMETRIES[case]
    return _kernels(geo, _operands(geo), sweeps, window=0)


def _head_major(geo, x, kv=False):
    return pk._split_heads(x, geo.hkv if kv else geo.h) \
        if geo.token_major else x


@pytest.mark.parametrize("case,sweeps", [
    (case, sweeps) for case in sorted(GEOMETRIES)
    for sweeps in ("one_sweep", "two_sweeps")
    if not (case in LONG and sweeps == "two_sweeps")])
def test_kernels_against_plain_attention(case, sweeps):
    geo = GEOMETRIES[case]
    ops = _operands(geo)
    got = _kernels(geo, ops, sweeps)
    q, k, v, g = (_head_major(geo, x, kv=i in (1, 2))
                  for i, x in enumerate(ops))
    want = _plain(geo, q, k, v, g, geo.window)
    # rows that see no key (tq > tk) are 0 in both
    allowed = _allowed(geo.tq, geo.tk, geo.window)
    assert allowed.sum(1).max() == min(geo.window, geo.tk)
    for name in ("out", "dq", "dk", "dv"):
        a = np.asarray(_head_major(geo, got[name], kv=False)
                       if name in ("out", "dq")
                       else _head_major(geo, got[name], kv=True))
        b = np.asarray(want[name])
        assert a.shape == b.shape, name
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5,
                                   err_msg=name)
        # one key a query: its probability is 1 whatever q and k are
        assert np.abs(b).max() > 0 or (geo.window == 1
                                        and name in ("dq", "dk")), name
    # the window cut something: the causal answer is another
    if geo.window < geo.tk:
        full = pk._plain_attention(q, k, v, True, geo.d ** -0.5)
        assert np.abs(np.asarray(full) - np.asarray(want["out"])).max() \
            > 1e-3


@pytest.mark.parametrize("sweeps", ["one_sweep", "two_sweeps"])
@pytest.mark.parametrize("window", ["T", "T_plus", "huge"])
@pytest.mark.parametrize("case", ["w40_tq_lt_tk", "w16_tq_gt_tk",
                                  "w24_group2_token_major_d64"])
def test_a_window_that_reaches_every_key_is_the_causal_call(
        case, window, sweeps):
    """At the same blocks, to the last bit: out, lse, dq, dk, dv of the
    band kernels with window >= Tk against the causal kernels.  (Whole
    q blocks: a block that ends in padding rows takes the masked branch
    under a window, the same numbers formed by another expression.)"""
    geo = GEOMETRIES[case]
    w = {"T": geo.tk, "T_plus": geo.tk + 5, "huge": 1 << 20}[window]
    ops = _operands(geo)
    got, want = _kernels(geo, ops, sweeps, window=w), \
        _causal_kernels(case, sweeps)
    for name in want:
        a, b = (np.asarray(x[name], np.float32) for x in (got, want))
        assert a.shape == b.shape
        assert np.abs(a - b).max() == 0.0, name
    # and the entries do not even build the band for it
    _, kw = pk._call_args(ops[0], ops[1], causal=True, window=w,
                          impl="interpret",
                          heads=geo.h if geo.token_major else None)
    assert kw["window"] == 0
    assert kw["block_k"] == pk._default_block(geo.tk)


@pytest.mark.parametrize("case", ["w96_pads", "w40_pads_bq_ne_bk",
                                  "w20_token_major_d64_pads"])
def test_the_entries_send_such_a_window_to_the_causal_kernels(case):
    """Through the entries a window >= Tk IS the causal call, padded
    blocks and all: same kernels, same names, same bits."""
    geo = GEOMETRIES[case]
    ops = _operands(geo)
    call = dict(causal=True, block_q=geo.bq, block_k=geo.bk,
                impl="interpret", heads=geo.h if geo.token_major else None)
    want = pk._flash_attention_fwd(*ops[:3], **call)
    got = pk._flash_attention_fwd(*ops[:3], window=geo.tk, **call)
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    grads = [pk._flash_attention_bwd(*ops[:3], *want, ops[3], window=w,
                                     **call) for w in (geo.tk + 3, None)]
    for a, b in zip(*grads):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_bfloat16_token_major_at_the_cells_heads():
    """32 / 4 heads of 128 token-major in bfloat16, as the cell's
    window layers call it, at a length interpret mode can walk."""
    geo = Geometry(1, 32, 4, 96, 96, 128, 128, 16, 16, True, 40)
    ops = _operands(geo, jnp.bfloat16)
    got = _kernels(geo, ops, "one_sweep")
    q, k, v, g = (_head_major(geo, x.astype(jnp.float32), kv=i in (1, 2))
                  for i, x in enumerate(ops))
    want = _plain(geo, q, k, v, g, geo.window)
    for name in ("out", "dq", "dk", "dv"):
        a = np.asarray(_head_major(geo, got[name], kv=name in ("dk", "dv")),
                       np.float32)
        b = np.asarray(want[name])
        assert np.abs(a - b).max() <= 0.04 * np.abs(b).max() + 0.02, name


# -- the entries, the counter, the layer ---------------------------------------

def _window_counts():
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == "flash_attention_window"})


@pytest.mark.parametrize("entry,want", [
    ("band", {"band": 2}),
    ("one_block", {"all_live": 2}),
    ("no_window", {}),
    ("reaches_every_key", {}),
])
def test_counter_says_which_grid_a_windowed_call_got(entry, want):
    t, h, d = 64, 2, 8
    block = 64 if entry == "one_block" else 16
    window = {"band": 24, "one_block": 24, "no_window": None,
              "reaches_every_key": 64}[entry]
    rng = np.random.RandomState(1)
    q, k, v, g = (jnp.asarray(rng.randn(1, h, t, d), jnp.float32)
                  for _ in range(4))
    call = dict(causal=True, block_q=block, block_k=block,
                impl="interpret", window=window)
    before = _window_counts()
    out, lse = pk._flash_attention_fwd(q, k, v, **call)
    pk._flash_attention_bwd(q, k, v, out, lse, g, **call)
    assert _window_counts() - before == want


@pytest.mark.parametrize("entry", ["flash_attention",
                                   "flash_attention_lse"])
def test_public_entries_take_the_window(entry):
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 64, 8), jnp.float32)
               for _ in range(3))

    def loss(impl):
        def f(q, k, v):
            kw = dict(causal=True, window=24, block_q=16, block_k=16,
                      impl=impl)
            if entry == "flash_attention_lse" and impl != "xla":
                return pk.flash_attention_lse(q, k, v, **kw)[0].sum()
            return pk.flash_attention(q, k, v, **kw).sum()
        return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)

    (a, ga), (b, gb) = loss("interpret"), loss("xla")
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for x, y in zip(ga, gb):
        np.testing.assert_allclose(x, y, atol=2e-5)


def test_misuse_raises():
    q = jnp.zeros((1, 2, 32, 8), jnp.float32)
    with pytest.raises(ValueError, match="causal"):
        pk.flash_attention(q, q, q, causal=False, window=8, impl="xla")
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(q, q, q, causal=True, window=-1, impl="xla")
    with pytest.raises(ValueError, match="causal"):
        layers.flash_attention(
            layers.data("q", shape=[2, 32, 8], dtype="float32"),
            layers.data("k", shape=[2, 32, 8], dtype="float32"),
            layers.data("v", shape=[2, 32, 8], dtype="float32"),
            window=8)
    pages = jnp.zeros((4, 2, 16, 8), jnp.float32)
    with pytest.raises(NotImplementedError, match="window"):
        pk.flash_decode(jnp.zeros((1, 2, 8), jnp.float32), pages, pages,
                        jnp.zeros((1, 2), jnp.int32),
                        jnp.ones((1,), jnp.int32), window=16)


@pytest.mark.parametrize("window,want", [
    (1024, 1024), (1000, 512), (512, 512), (300, 256), (96, 128),
    (1, 128), (4096, 1024), (0, 1024)])
def test_default_block_follows_the_window(window, want):
    assert pk._default_block(16384, window) == want


@pytest.mark.parametrize("token_major", [False, True],
                         ids=["head_major", "token_major"])
def test_the_layer_and_its_grad_op(token_major):
    """`layers.flash_attention(window=)` through append_backward and
    the executor (off the chip: the XLA impl, `_plain_attention` with
    the window, and the `recompute` grad fallback) against numpy."""
    b, h, t, d, w = 2, 2, 48, 8, 10
    shape = [t, h * d] if token_major else [h, t, d]
    rng = np.random.RandomState(11)
    feed = {n: rng.randn(b, *shape).astype(np.float32) for n in "qkv"}
    q, k, v = (layers.data(n, shape=shape, dtype="float32") for n in "qkv")
    for x in (q, k, v):
        x.stop_gradient = False
    out = layers.flash_attention(q, k, v, causal=True, window=w,
                                 n_head=h if token_major else None)
    op = fluid.default_main_program().global_block().ops[-1]
    assert op.type == "flash_attention" and op.attrs["window"] == w
    loss = layers.reduce_sum(layers.square(out))
    fluid.backward.append_backward(loss)
    grad = [o for o in fluid.default_main_program().global_block().ops
            if o.type == "flash_attention_grad"]
    assert len(grad) == 1 and grad[0].attrs["window"] == w
    exe = fluid.Executor(fluid.CPUPlace())
    got = exe.run(feed=feed, fetch_list=[out, "q@GRAD", "k@GRAD",
                                         "v@GRAD"])

    def heads(x):
        return x.reshape(b, t, h, d).transpose(0, 2, 1, 3) \
            if token_major else x

    def f(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        p = jax.nn.softmax(jnp.where(_allowed(t, t, w), s, -jnp.inf), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p, v)

    ops = tuple(jnp.asarray(heads(feed[n])) for n in "qkv")
    want = (f(*ops), *jax.grad(lambda *a: (f(*a) ** 2).sum(),
                               argnums=(0, 1, 2))(*ops))
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(heads(np.asarray(a)), np.asarray(b_),
                                   atol=2e-5, rtol=2e-5)

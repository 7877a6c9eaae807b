"""A causal flash grid step that computes nothing fetches nothing
(ISSUE 48).

The three causal kernels skip the COMPUTE of a block pair above the
diagonal; since this PR the BlockSpec index maps of the operands that
walk the grid's inner axis hold the block of the nearest step that
runs over those steps (`pk._second_held`), so Pallas's pipeline sees an
unchanged block index and copies nothing.  Here the whole grid of each
kernel is walked on the host: the maps are the calls' own, taken from
the `pl.pallas_call`s the entries make, and the steps that run are the
kernels' own predicate (`pk._Diagonal.run`).  Then, in interpret mode,
every output against the same kernels with the maps left raw.  All on
the CPU.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_kernels as pk

Geometry = collections.namedtuple(
    "Geometry", "b h hkv tq tk d dv bq bk token_major")

# b, h, hkv, tq, tk, d, dv, block_q, block_k, token-major
GEOMETRIES = {
    "square": Geometry(2, 2, 2, 64, 64, 8, 8, 16, 16, False),
    "bq_lt_bk": Geometry(1, 2, 2, 64, 64, 8, 8, 8, 16, False),
    "bq_gt_bk": Geometry(1, 2, 2, 64, 64, 8, 8, 32, 8, False),
    "tq_lt_tk": Geometry(1, 2, 2, 16, 48, 8, 8, 8, 16, False),
    "tq_lt_tk_off_a_block": Geometry(1, 1, 1, 24, 60, 8, 8, 8, 8, False),
    "tq_gt_tk": Geometry(1, 2, 2, 48, 16, 8, 8, 8, 8, False),
    "pads": Geometry(1, 2, 2, 40, 40, 8, 8, 16, 16, False),
    "pads_bq_ne_bk": Geometry(1, 1, 1, 44, 52, 8, 8, 16, 8, False),
    "latent_192_128": Geometry(1, 2, 2, 48, 48, 192, 128, 16, 16, False),
    "grouped_head_major": Geometry(2, 4, 2, 64, 64, 8, 8, 16, 16, False),
    "token_major_d64": Geometry(2, 4, 4, 64, 64, 64, 64, 16, 16, True),
    "token_major_d128": Geometry(1, 2, 2, 64, 64, 128, 128, 16, 8, True),
    "grouped_token_major_d64": Geometry(
        2, 8, 2, 48, 48, 64, 64, 16, 16, True),
    "grouped_token_major_pads": Geometry(
        1, 4, 2, 40, 56, 64, 64, 16, 16, True),
}

# The cells' own calls: (dead steps, steps) of a grid, by the issue's
# arithmetic.  Walked on the host like the small ones: nothing runs.
CELLS = {
    # 4 x 8,192 tokens, 8 heads of 64 token-major, two heads a step
    "tfm_base_train_s8k": (
        Geometry(4, 8, 8, 8192, 8192, 64, 64, 1024, 1024, True),
        (448, 1024)),
    # 1 x 8,192, 32 query heads on 8 KV heads of 64, token-major
    "granite4_lfm2": (
        Geometry(1, 32, 8, 8192, 8192, 64, 64, 1024, 1024, True),
        (448, 1024)),
    # 2 x 4,096, 16 heads at 192 / 128, head-major
    "dsv2_lite_train_s4k": (
        Geometry(2, 16, 16, 4096, 4096, 192, 128, 1024, 1024, False),
        (192, 512)),
    # 64 x 512: one block a sequence
    "tfm_base_train_s512": (
        Geometry(64, 8, 8, 512, 512, 64, 64, 512, 512, True),
        (0, 32 * 8)),
}

KERNELS = ("fwd", "one_sweep", "dq", "dkv")

# Which grid axis (1 or 2) walks each operand's rows, as the kernels
# read the grid: (g, qi, ki) for the forward and the dq sweep,
# (g, ki, qi) for the dk/dv sweep, with or without dq.
Q_OUTER = {"q": 1, "k": 2, "v": 2, "do": 1, "lse": 1, "delta": 1}
KV_OUTER = {"q": 2, "k": 1, "v": 1, "do": 2, "lse": 2, "delta": 2}
INPUTS = {"fwd": {n: Q_OUTER[n] for n in ("q", "k", "v")},
          "dq": Q_OUTER, "one_sweep": KV_OUTER, "dkv": KV_OUTER}


def _shapes(geo, dtype=jnp.float32):
    """ShapeDtypeStructs of q, k, v, dO in the geometry's layout."""
    def one(heads, t, width):
        if geo.token_major:
            return jax.ShapeDtypeStruct((geo.b, t, heads * width), dtype)
        return jax.ShapeDtypeStruct((geo.b, heads, t, width), dtype)

    return (one(geo.h, geo.tq, geo.d), one(geo.hkv, geo.tk, geo.d),
            one(geo.hkv, geo.tk, geo.dv), one(geo.h, geo.tq, geo.dv))


def _static(geo, causal=True):
    return dict(causal=causal, scale=geo.d ** -0.5, block_q=geo.bq,
                block_k=geo.bk, interpret=True,
                heads=geo.h if geo.token_major else None)


def _raw_maps(monkeypatch):
    """The maps as they were: the inner axis' own index at every step."""
    monkeypatch.setattr(pk, "_second_held",
                        lambda diagonal, nq, nk, walks: pk._second)


def _calls(geo, causal, raw=False):
    """{kernel: (grid, in_specs, out_specs)} of the `pl.pallas_call`s
    that the forward, the one-sweep backward and the two-sweep backward
    make for this geometry.  Traced abstractly: no kernel runs."""
    seen = {}

    def record(kernel, *, name, grid, in_specs, out_specs, out_shape,
               **_):
        seen[name] = (grid, in_specs, out_specs
                      if isinstance(out_specs, (list, tuple))
                      else [out_specs])
        return lambda *operands: jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), out_shape)

    q, k, v, g = _shapes(geo)
    kw = _static(geo, causal)
    tq_p = -(-geo.tq // geo.bq) * geo.bq
    o = jax.ShapeDtypeStruct(g.shape, g.dtype)
    lse = jax.ShapeDtypeStruct((geo.b * geo.h, tq_p), jnp.float32)
    calls = {}
    with pytest.MonkeyPatch.context() as m:
        m.setattr(pk.pl, "pallas_call", record)
        if raw:
            _raw_maps(m)
        jax.eval_shape(lambda *a: pk._flash_fwd_pallas.__wrapped__(
            *a, **kw), q, k, v)
        calls["fwd"] = seen.pop("pt_flash_fwd")
        for vmem in (1 << 20, None):
            jax.eval_shape(lambda *a: pk._flash_bwd_pallas.__wrapped__(
                *a, one_sweep_vmem=vmem, **kw), q, k, v, o, lse, g)
            if vmem:
                calls["one_sweep"] = seen.pop("pt_flash_bwd_dkv")
            else:
                calls["dq"] = seen.pop("pt_flash_bwd_dq")
                calls["dkv"] = seen.pop("pt_flash_bwd_dkv")
        assert not seen
    return calls


def _walk(spec, grid):
    """A BlockSpec's block index at every step: [3, *grid]."""
    steps = np.indices(grid)
    return np.stack([np.broadcast_to(np.asarray(x), grid)
                     for x in spec.index_map(*steps)])


def _live(geo, kernel, grid):
    """[nq-or-nk, nk-or-nq] bool of a head's steps by the kernels' own
    predicate, laid out as the kernel's grid is."""
    diagonal = pk._Diagonal(geo.bq, geo.bk, geo.tk - geo.tq)
    outer, inner = np.indices(grid[1:])
    if kernel in ("fwd", "dq"):
        return np.asarray(diagonal.run(outer, inner))
    return np.asarray(diagonal.run(inner, outer))


def _grid(geo, kernel):
    nq, nk = -(-geo.tq // geo.bq), -(-geo.tk // geo.bk)
    hpb = 128 // geo.d if geo.token_major else 1
    inner = (nq, nk) if kernel in ("fwd", "dq") else (nk, nq)
    return (geo.b * geo.h // hpb, *inner)


@pytest.fixture(scope="module")
def maps():
    """{(case, causal, raw): _calls(...)}, filled as the tests ask."""
    cache = {}

    def get(case, causal=True, raw=False):
        key = case, causal, raw
        if key not in cache:
            geo = GEOMETRIES[case] if case in GEOMETRIES \
                else CELLS[case][0]
            cache[key] = _calls(geo, causal, raw)
        return cache[key]

    return get


# -- (a) a step that runs fetches exactly the block it fetched before --------

@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("case", sorted(GEOMETRIES))
def test_a_live_step_fetches_its_own_block(maps, case, kernel):
    geo = GEOMETRIES[case]
    grid, in_specs, out_specs = maps(case)[kernel]
    raw_grid, raw_in, raw_out = maps(case, raw=True)[kernel]
    assert grid == raw_grid == _grid(geo, kernel)
    live = np.broadcast_to(_live(geo, kernel, grid), grid)
    assert live.any()
    for name, spec, raw in zip(INPUTS[kernel], in_specs, raw_in):
        held, was = _walk(spec, grid), _walk(raw, grid)
        assert spec.block_shape == raw.block_shape, name
        # the rows are the raw index of the axis that walks them ...
        assert np.array_equal(was[1], np.indices(grid)[INPUTS[kernel][name]])
        assert np.array_equal(held[1][live], was[1][live]), name
        # ... and head, batch and lane block are untouched at EVERY step
        assert np.array_equal(held[[0, 2]], was[[0, 2]]), name
    # what a kernel writes, it writes where it wrote
    assert len(out_specs) == len(raw_out)
    for spec, raw in zip(out_specs, raw_out):
        assert np.array_equal(_walk(spec, grid), _walk(raw, grid))


# -- (b) a dead step holds the adjacent live step's block --------------------

@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("case", sorted(GEOMETRIES))
def test_a_dead_step_holds_the_adjacent_live_block(maps, case, kernel):
    """Dead steps END a q block's sweep over kv blocks (forward, dq)
    and START a kv block's sweep over q blocks (dk/dv): the operand
    that walks the sweep stays on the last, or is already on the first,
    block that runs.  So a sweep changes block once a live step, and
    not at all where nothing runs."""
    geo = GEOMETRIES[case]
    grid, in_specs, _ = maps(case)[kernel]
    live = _live(geo, kernel, grid)                 # [outer, inner]
    n_inner = grid[2]
    steps = np.arange(n_inner)
    if kernel in ("fwd", "dq"):
        # the last step that runs, by enumeration
        adjacent = np.where(live, steps, -1).max(1)
    else:
        adjacent = np.where(live, steps, n_inner).min(1)
    some = live.any(1)
    dead = ~live
    if case not in ("tq_lt_tk",):
        assert dead.any(), "the case has no dead step to look at"
    for name, spec in zip(INPUTS[kernel], in_specs):
        rows = _walk(spec, grid)[1]                 # [g, outer, inner]
        if INPUTS[kernel][name] == 1:
            # walks the OUTER axis: one block a sweep, as ever
            assert (rows == rows[:, :, :1]).all(), name
            continue
        want = np.broadcast_to(adjacent[None, :, None], grid)
        at = np.broadcast_to((dead & some[:, None])[None], grid)
        assert np.array_equal(rows[at], want[at]), name
        changes = 1 + (rows[:, :, 1:] != rows[:, :, :-1]).sum(2)
        assert np.array_equal(
            changes, np.broadcast_to(np.maximum(live.sum(1), 1)[None],
                                     changes.shape)), name
        assert rows.min() >= 0 and rows.max() < n_inner, name


@pytest.mark.parametrize("case", sorted(CELLS))
def test_the_cells_dead_steps(maps, case):
    """The issue's arithmetic at the cells' own shapes: how many grid
    steps of a call compute nothing, and that K and V (forward) and q,
    dO and both row statistics (backward) change block only at a step
    that runs."""
    geo, (dead, steps) = CELLS[case]
    calls = maps(case)
    for kernel in ("fwd", "one_sweep"):
        grid, in_specs, _ = calls[kernel]
        live = _live(geo, kernel, grid)
        assert grid[0] * live.size == steps
        assert grid[0] * int((~live).sum()) == dead
        for name, spec in zip(INPUTS[kernel], in_specs):
            if INPUTS[kernel][name] == 2:
                rows = _walk(spec, grid)[1]
                fetched = (1 + (rows[:, :, 1:] != rows[:, :, :-1])
                           .sum(2)).sum()
                assert fetched == grid[0] * int(live.sum()), name


# -- (c) a call that is not causal keeps the raw maps -------------------------

@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("case", ["square", "tq_lt_tk", "pads",
                                  "grouped_head_major",
                                  "grouped_token_major_d64"])
def test_a_full_call_keeps_the_raw_maps(maps, case, kernel):
    assert pk._second_held(None, 4, 4, walks="kv") is pk._second
    assert pk._second_held(None, 4, 4, walks="q") is pk._second
    grid, in_specs, out_specs = maps(case, causal=False)[kernel]
    _, raw_in, raw_out = maps(case, causal=False, raw=True)[kernel]
    index = np.indices(grid)
    for name, spec, raw in zip(INPUTS[kernel], in_specs, raw_in):
        got = _walk(spec, grid)
        assert np.array_equal(got, _walk(raw, grid)), name
        assert np.array_equal(got[1], index[INPUTS[kernel][name]]), name
    for spec, raw in zip(out_specs, raw_out):
        assert np.array_equal(_walk(spec, grid), _walk(raw, grid))


# -- the helper alone: predicate and its two solutions agree -----------------

@pytest.mark.parametrize("bq,bk", [(8, 8), (8, 16), (16, 8), (24, 16),
                                   (16, 40)])
@pytest.mark.parametrize("q_off", [0, 5, 16, 37, -8, -21])
def test_diagonal_solves_its_own_predicate(bq, bk, q_off):
    diagonal = pk._Diagonal(bq, bk, q_off)
    nq, nk = 9, 7
    qi, ki = np.indices((nq, nk))
    live = np.asarray(diagonal.run(qi, ki))
    last = np.asarray(diagonal.last_ki(np.arange(nq), nk))
    first = np.asarray(diagonal.first_qi(np.arange(nk), nq))
    for i in range(nq):
        want = np.flatnonzero(live[i])
        assert last[i] == (want.max() if want.size else 0), i
        # dead steps end a row, live ones are contiguous from 0
        assert live[i, :want.size].all()
    for j in range(nk):
        want = np.flatnonzero(live[:, j])
        assert first[j] == (want.min() if want.size else nq - 1), j
        assert live[nq - want.size:, j].all()
    assert diagonal.dead_steps(nk) == (not live.all())


# -- (d) interpret mode: every output, bit for bit, against the raw maps -----

RESULTS = ["square", "bq_lt_bk", "bq_gt_bk", "tq_lt_tk_off_a_block",
           "tq_gt_tk", "pads_bq_ne_bk", "latent_192_128",
           "grouped_head_major", "token_major_d64",
           "grouped_token_major_pads"]


def _run(geo, sweeps, dtype):
    rng = np.random.RandomState(7)
    q, k, v, g = (jnp.asarray(rng.randn(*s.shape), dtype)
                  for s in _shapes(geo))
    kw = _static(geo)
    # the un-jitted functions: a jit's cache would hand the second call
    # the first call's maps
    out, lse = pk._flash_fwd_pallas.__wrapped__(q, k, v, **kw)
    vmem = (64 << 20) if sweeps == "one_sweep" else None
    grads = pk._flash_bwd_pallas.__wrapped__(
        q, k, v, out, lse, g, one_sweep_vmem=vmem, **kw)
    return (q, k, v), dict(zip(("out", "lse", "dq", "dk", "dv"),
                               (out, lse, *grads)))


@pytest.mark.parametrize("sweeps", ["one_sweep", "two_sweeps"])
@pytest.mark.parametrize("case", RESULTS)
def test_results_are_those_of_the_raw_maps(case, sweeps, monkeypatch):
    geo = GEOMETRIES[case]
    dtype = jnp.bfloat16 if case == "token_major_d64" else jnp.float32
    (q, k, v), held = _run(geo, sweeps, dtype)
    with monkeypatch.context() as m:
        _raw_maps(m)
        _, raw = _run(geo, sweeps, dtype)
    for name in held:
        a, b = (np.asarray(x[name], np.float32) for x in (held, raw))
        assert a.shape == b.shape and held[name].dtype == raw[name].dtype
        assert np.abs(a - b).max() == 0.0, name
        assert np.isfinite(a[np.abs(a) < 1e29]).all(), name
        assert np.abs(a).max() > 0, name
    # and they are attention's: a map that held a LIVE step's block
    # wrong would still equal itself
    if geo.token_major:
        q, k, v = (pk._split_heads(x, n)
                   for x, n in zip((q, k, v), (geo.h, geo.hkv, geo.hkv)))
    want = pk._plain_attention(q, k, v, True, geo.d ** -0.5)
    got = held["out"]
    if geo.token_major:
        got = pk._split_heads(got, geo.h)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=5e-2 if dtype == jnp.bfloat16 else 2e-5)


# -- (e) the counter ----------------------------------------------------------

def _fetch_counts():
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == "flash_attention_causal_fetch"})


@pytest.mark.parametrize("layout", ["head_major", "token_major"])
@pytest.mark.parametrize("entry,want", [
    ("causal_blocks", {"held": 2}),
    ("causal_one_block", {"all_live": 2}),
    ("full_blocks", {}),
])
def test_counter_by_kind_of_entry(entry, want, layout):
    """Once a causal entry to the kernels, forward and backward:
    `held` where the grid has a step above the diagonal, `all_live`
    where a sequence is one block; an entry that is not causal adds no
    series."""
    t, h, d = 32, 2, 64
    block = 32 if entry == "causal_one_block" else 16
    rng = np.random.RandomState(1)
    shape = (1, t, h * d) if layout == "token_major" else (1, h, t, d)
    q, k, v, g = (jnp.asarray(rng.randn(*shape), jnp.float32)
                  for _ in range(4))
    call = dict(causal=entry != "full_blocks", block_q=block,
                block_k=block, impl="interpret",
                heads=h if layout == "token_major" else None)
    before = _fetch_counts()
    out, lse = pk._flash_attention_fwd(q, k, v, **call)
    pk._flash_attention_bwd(q, k, v, out, lse, g, **call)
    assert _fetch_counts() - before == want


def test_counter_of_the_lse_entry_and_of_autodiff():
    """`flash_attention_lse` (ring attention's entry) and a backward
    reached through the custom_vjp count as the op's entries do."""
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 32, 8), jnp.float32)
               for _ in range(3))
    before = _fetch_counts()
    jax.grad(lambda q: pk.flash_attention_lse(
        q, k, v, causal=True, block_q=16, block_k=16,
        impl="interpret")[0].sum())(q)
    assert _fetch_counts() - before == {"held": 2}
    before = _fetch_counts()
    pk.flash_attention_lse(q, k, v, causal=False, block_q=16, block_k=16,
                           impl="interpret")
    assert _fetch_counts() - before == {}

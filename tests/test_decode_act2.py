"""ISSUE 11 tentpole coverage — decode speed act II: chunked prefill,
copy-on-write prefix sharing, lossless speculative decoding.

The bit-parity trio the acceptance criteria pin:
  * chunked-prefill output == whole-prefill output,
  * shared-prefix decode == unshared decode (same physical bytes),
  * speculative greedy == non-speculative greedy token-for-token,
plus the q-len-k verify-kernel parity matrix, the generalized
zero-leak invariant (refcounts, COW, fork, truncate) under seeded
chaos, the deadline-aware preemption policy (with the legacy
tie-break pinned), and the chunked-join SLO acceptance leg.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.ops.paged_kv import OutOfPagesError, PagedKVCache


# ---------------------------------------------------------------------------
# allocator: refcounts, radix sharing, COW, fork, truncate
# ---------------------------------------------------------------------------

def _toks(rng, n):
    return [int(t) for t in rng.randint(2, 100, size=n)]


def _kv(rng, n, h=2, d=8):
    return (rng.randn(n, h, d).astype(np.float32),
            rng.randn(n, h, d).astype(np.float32))


def test_shared_prefill_refcounts_and_amortization():
    rng = np.random.RandomState(0)
    c = PagedKVCache(num_pages=16, page_size=4, num_heads=2,
                     head_dim=8, kv_share=True)
    prefix = _toks(rng, 8)                       # 2 full pages
    tail_a = _toks(rng, 3)
    k, v = _kv(rng, 11)
    s0 = c.prefill(k, v, tokens=prefix + tail_a)
    assert c.in_use_pages() == 3 and c.shared_pages() == 0
    # second prompt, same prefix: 2 pages shared, only the tail costs
    assert c.shared_prefix_tokens(prefix + _toks(rng, 5)) == 8
    k2, v2 = _kv(rng, 5)                         # tail-only k/v
    s1 = c.prefill(k2, v2, tokens=prefix + _toks(rng, 5))
    assert c.shared_pages() == 2
    assert c.in_use_pages() == 3 + 2             # 2 tail pages only
    ok, detail = c.check_accounting()
    assert ok, detail
    # frees in either order leave shared pages alive until the last ref
    c.free(s0)
    assert c.shared_pages() == 0 and c.in_use_pages() == 4
    ok, detail = c.check_accounting()
    assert ok, detail
    c.free(s1)
    assert c.in_use_pages() == 0 and c.free_pages() == 16
    ok, detail = c.check_accounting()
    assert ok, detail


def test_shared_bytes_identical_and_kernel_reads_them():
    """Shared-prefix decode must be bit-identical to unshared: the
    block tables differ, the physical bytes do not."""
    rng = np.random.RandomState(1)
    prefix_tok = _toks(rng, 8)
    k_pre, v_pre = _kv(rng, 8, h=2, d=8)
    tails = [_kv(rng, 3, h=2, d=8), _kv(rng, 5, h=2, d=8)]
    tail_toks = [_toks(rng, 3), _toks(rng, 5)]
    q = jnp.asarray(rng.randn(2, 2, 8).astype(np.float32))

    def outputs(share):
        c = PagedKVCache(num_pages=16, page_size=4, num_heads=2,
                         head_dim=8, kv_share=share)
        slots = []
        for (kt, vt), tt in zip(tails, tail_toks):
            k = np.concatenate([k_pre, kt])
            v = np.concatenate([v_pre, vt])
            slots.append(c.prefill(k, v, tokens=prefix_tok + tt
                                   if share else None))
        out = pk.flash_decode_reference(
            q, c.k_pages, c.v_pages, c.tables_for(slots),
            c.lens_for(slots))
        return np.asarray(out), c

    out_u, _ = outputs(False)
    out_s, cs = outputs(True)
    assert cs.shared_pages() == 2                # prefix shared
    assert np.array_equal(out_u, out_s)


def test_fork_cow_append_and_mid_fork_kill():
    rng = np.random.RandomState(2)
    c = PagedKVCache(num_pages=16, page_size=4, num_heads=2,
                     head_dim=8, kv_share=True)
    k, v = _kv(rng, 6)                           # 1.5 pages
    parent = c.prefill(k, v)
    child = c.fork(parent)
    assert c.seq_len(child) == 6
    assert c.in_use_pages() == 2 and c.shared_pages() == 2
    # divergent appends: the shared PARTIAL page copies-on-write
    ka, va = _kv(rng, 1)
    kb, vb = _kv(rng, 1)
    c.append([parent], ka, va)
    assert c.shared_pages() == 1                 # page 0 still shared
    c.append([child], kb, vb)
    ok, detail = c.check_accounting()
    assert ok, detail
    tp = np.asarray(c.tables_for([parent]))[0]
    tc = np.asarray(c.tables_for([child]))[0]
    assert tp[0] == tc[0] and tp[1] != tc[1]     # COW split page 1
    kp = np.asarray(c.k_pages)
    # both histories kept their first 6 tokens and diverge at 7
    assert np.array_equal(kp[tp[1], :, :2], kp[tc[1], :, :2])
    assert np.array_equal(kp[tp[1], 0, 2], np.asarray(ka)[0, 0])
    assert np.array_equal(kp[tc[1], 0, 2], np.asarray(kb)[0, 0])
    # mid-fork kill: the parent dies, the child's pages survive
    c.free(parent)
    ok, detail = c.check_accounting()
    assert ok, detail
    assert c.seq_len(child) == 7
    c.free(child)
    assert c.in_use_pages() == 0
    ok, detail = c.check_accounting()
    assert ok, detail


def test_fork_needs_kv_share():
    c = PagedKVCache(num_pages=4, page_size=4, num_heads=1,
                     head_dim=8, kv_share=False)
    s = c.prefill(*_kv(np.random.RandomState(0), 2, h=1))
    with pytest.raises(RuntimeError):
        c.fork(s)


def test_truncate_rewinds_pages_atomically():
    rng = np.random.RandomState(3)
    c = PagedKVCache(num_pages=16, page_size=4, num_heads=2,
                     head_dim=8)
    s = c.prefill(*_kv(rng, 14))                 # 4 pages
    assert c.in_use_pages() == 4
    c.truncate(s, 5)                             # back to 2 pages
    assert c.seq_len(s) == 5 and c.in_use_pages() == 2
    ok, detail = c.check_accounting()
    assert ok, detail
    with pytest.raises(ValueError):
        c.truncate(s, 6)                         # can't grow
    # the freed range is reusable immediately
    c.extend(s, *_kv(rng, 9))
    assert c.seq_len(s) == 14
    ok, detail = c.check_accounting()
    assert ok, detail


def test_extend_matches_whole_prefill_bytes():
    rng = np.random.RandomState(4)
    k, v = _kv(rng, 13)
    c1 = PagedKVCache(num_pages=8, page_size=4, num_heads=2,
                      head_dim=8)
    s1 = c1.prefill(k, v)
    c2 = PagedKVCache(num_pages=8, page_size=4, num_heads=2,
                      head_dim=8)
    s2 = c2.prefill(k[:3], v[:3])
    for lo, hi in ((3, 8), (8, 13)):
        c2.extend(s2, k[lo:hi], v[lo:hi])
    t1 = np.asarray(c1.tables_for([s1]))[0]
    t2 = np.asarray(c2.tables_for([s2]))[0]
    assert np.array_equal(np.asarray(c1.k_pages)[t1],
                          np.asarray(c2.k_pages)[t2])
    assert np.array_equal(np.asarray(c1.v_pages)[t1],
                          np.asarray(c2.v_pages)[t2])


def test_out_of_pages_atomic_under_cow_and_extend():
    rng = np.random.RandomState(5)
    c = PagedKVCache(num_pages=3, page_size=4, num_heads=1,
                     head_dim=8, kv_share=True)
    s = c.prefill(*_kv(rng, 6, h=1))             # 2 pages
    child = c.fork(s)
    c.append([s], *_kv(rng, 1, h=1))             # COW takes the free
    assert c.free_pages() == 0
    # child's partial page is re-shared by a second fork, so its next
    # append needs a COW — with zero free pages it must fail atomically
    c.fork(child)
    with pytest.raises(OutOfPagesError):
        c.append([child], *_kv(rng, 1, h=1))
    assert c.free_pages() == 0
    assert c.seq_len(child) == 6                 # untouched
    ok, detail = c.check_accounting()
    assert ok, detail


def test_generalized_invariant_under_seeded_chaos():
    """free + unique(in_use) == num_pages with consistent refcounts
    through a seeded storm of shared prefills, forks, appends,
    truncates (the speculation rewind) and frees."""
    rng = np.random.RandomState(1234)
    c = PagedKVCache(num_pages=48, page_size=4, num_heads=2,
                     head_dim=8, kv_share=True, max_seqs=16)
    prefixes = [_toks(rng, 8), _toks(rng, 12)]
    live = []
    for step in range(300):
        op = rng.randint(5)
        try:
            if op == 0 or not live:
                pre = prefixes[rng.randint(2)]
                tail = _toks(rng, int(rng.randint(1, 6)))
                toks = pre + tail
                live.append(c.prefill(*_kv(rng, len(toks)),
                                      tokens=toks))
            elif op == 1:
                live.append(c.fork(live[rng.randint(len(live))]))
            elif op == 2:
                c.append([live[rng.randint(len(live))]],
                         *_kv(rng, 1))
            elif op == 3:
                s = live[rng.randint(len(live))]
                ln = c.seq_len(s)
                if ln > 1:
                    c.truncate(s, int(rng.randint(1, ln + 1)))
            else:
                c.free(live.pop(rng.randint(len(live))))
        except OutOfPagesError:
            # backpressure, not corruption: drop one and continue
            if live:
                c.free(live.pop(0))
        ok, detail = c.check_accounting()
        assert ok, "step %d: %s" % (step, detail)
    c.reset()
    assert c.in_use_pages() == 0 and c.free_pages() == 48
    ok, detail = c.check_accounting()
    assert ok, detail


def test_page_pool_gauges_exported():
    from paddle_tpu.observability import metrics as obs_metrics

    rng = np.random.RandomState(6)
    c = PagedKVCache(num_pages=8, page_size=4, num_heads=1,
                     head_dim=8)
    c.prefill(*_kv(rng, 5, h=1))
    snap = obs_metrics.registry().snapshot()
    for g in ("paddle_tpu_paged_kv_pages_free",
              "paddle_tpu_paged_kv_pages_in_use",
              "paddle_tpu_paged_kv_pages_shared",
              "paddle_tpu_paged_kv_internal_frag_pct"):
        assert g in snap, g
    series = {s["labels"].get("cache"): s["value"]
              for s in snap["paddle_tpu_paged_kv_pages_in_use"]
              ["series"]}
    assert series[c._label] == 2.0


# ---------------------------------------------------------------------------
# q-len-k verify kernel parity (the ISSUE acceptance matrix)
# ---------------------------------------------------------------------------

def _setup_multi(lens, H=4, d=64, ps=16, dtype=jnp.float32,
                 int8=False, r=3, seed=1):
    rng = np.random.RandomState(seed)
    c = PagedKVCache(num_pages=64, page_size=ps, num_heads=H,
                     head_dim=d, dtype=dtype, kv_int8=int8)
    for t in lens:
        c.prefill(rng.randn(t, H, d).astype(np.float32),
                  rng.randn(t, H, d).astype(np.float32))
    slots = list(range(len(lens)))
    q = jnp.asarray(rng.randn(len(lens), r, H, d)
                    .astype(np.float32)).astype(dtype)
    return (c, q, c.tables_for(slots), c.lens_for(slots),
            c.kv_scales() if int8 else None)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("hp", [False, True])
def test_verify_kernel_parity_ragged_page_boundaries(d, dtype, hp):
    """q-len-3 interpret kernel == the multi-row reference replay,
    array_equal, on ragged lengths spanning page boundaries."""
    c, q, tab, ln, _ = _setup_multi([5, 33, 16, 4], d=d, dtype=dtype)
    ref = pk.flash_decode_reference(q, c.k_pages, c.v_pages, tab, ln)
    out = pk.flash_decode(q, c.k_pages, c.v_pages, tab, ln,
                          impl="interpret", head_pack=hp)
    assert out.shape == q.shape
    assert jnp.array_equal(ref, out)


@pytest.mark.parametrize("hp", [False, True])
def test_verify_kernel_parity_int8kv(hp):
    c, q, tab, ln, scales = _setup_multi([5, 33, 64], d=64, ps=32,
                                         int8=True)
    ref = pk.flash_decode_reference(q, c.k_pages, c.v_pages, tab, ln,
                                    kv_scales=scales)
    out = pk.flash_decode(q, c.k_pages, c.v_pages, tab, ln,
                          impl="interpret", head_pack=hp,
                          kv_scales=scales)
    assert jnp.array_equal(ref, out)


def test_verify_rows_bit_equal_sequential_steps():
    """THE lossless core: verify row r == a q-len-1 call at the
    truncated length (masked pages are exact no-ops in the merge), so
    speculative greedy can never diverge from sequential greedy."""
    for int8 in (False, True):
        c, q, tab, ln, scales = _setup_multi(
            [9, 33, 17], d=64, ps=32 if int8 else 16, r=4,
            int8=int8)
        out = pk.flash_decode(q, c.k_pages, c.v_pages, tab, ln,
                              impl="interpret", kv_scales=scales)
        for r in range(4):
            o1 = pk.flash_decode(q[:, r], c.k_pages, c.v_pages, tab,
                                 ln - (4 - 1 - r),
                                 impl="interpret", kv_scales=scales)
            assert jnp.array_equal(o1, out[:, r]), (int8, r)


def test_verify_qlen_past_sublane_tile():
    """R = 9 > the f32 8-row tile: the query block widens to 16
    sublanes and parity still holds (the spec_k8 bench shape)."""
    c, q, tab, ln, _ = _setup_multi([40, 7], d=64, r=9)
    ref = pk.flash_decode_reference(q, c.k_pages, c.v_pages, tab, ln)
    out = pk.flash_decode(q, c.k_pages, c.v_pages, tab, ln,
                          impl="interpret")
    assert jnp.array_equal(ref, out)


def test_spec_accept_length_rule():
    from paddle_tpu.decode import spec_accept_length

    assert spec_accept_length([5, 6, 7], [5, 6, 7, 9]) == 3  # full
    assert spec_accept_length([5, 6, 7], [5, 9, 7, 9]) == 1
    assert spec_accept_length([5, 6, 7], [4, 6, 7, 9]) == 0
    assert spec_accept_length([], [4]) == 0


# ---------------------------------------------------------------------------
# engine: the bit-parity trio + preemption policy
# ---------------------------------------------------------------------------

def _run_server(prompts, **cfg_kw):
    from paddle_tpu import serving

    cfg = dict(max_batch=4, max_new_tokens=10, page_size=16,
               num_pages=60, n_replicas=1, eos_id=1,
               default_deadline_s=120.0)
    cfg.update(cfg_kw)
    srv = serving.DecodeServer(
        config=serving.DecodeConfig(**cfg)).start()
    try:
        futs = [srv.submit(p) for p in prompts]
        outs = [list(f.result(timeout=120.0)[0]) for f in futs]
    finally:
        srv.stop()
    ok, detail = srv.page_accounting()
    assert ok, detail
    st = srv.stats()
    assert st["accounted"]
    for rep_st in st["replicas"].values():
        assert rep_st["cache"]["in_use_pages"] == 0
        if "draft_cache" in rep_st:
            assert rep_st["draft_cache"]["in_use_pages"] == 0
    return outs, st


@pytest.fixture(scope="module")
def seeded_prompts():
    rng = np.random.RandomState(0)
    return [rng.randint(2, 128, size=int(rng.randint(1, 40)))
            for _ in range(8)]


@pytest.fixture(scope="module")
def baseline_outputs(seeded_prompts):
    return _run_server(seeded_prompts)[0]


def test_chunked_prefill_bit_identical(seeded_prompts,
                                       baseline_outputs):
    outs, st = _run_server(seeded_prompts, prefill_chunk=8)
    assert outs == baseline_outputs
    assert st["decode"]["prefill_chunks"] > 0


def test_prefix_shared_decode_bit_identical(baseline_outputs,
                                            seeded_prompts):
    outs, st = _run_server(seeded_prompts, kv_share=True)
    assert outs == baseline_outputs


def test_shared_system_prompt_amortizes(seeded_prompts):
    rng = np.random.RandomState(9)
    sys_prompt = rng.randint(2, 128, size=48)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(2, 128, size=4)])
               for _ in range(6)]
    base, _ = _run_server(prompts)
    outs, st = _run_server(prompts, kv_share=True)
    assert outs == base
    peak_shared = max(r["cache"]["peak_shared_pages"]
                      for r in st["replicas"].values())
    assert peak_shared >= 3         # the 48-token prefix's full pages


def test_spec_decode_token_identical(seeded_prompts,
                                     baseline_outputs):
    outs, st = _run_server(seeded_prompts, spec_k=3)
    assert outs == baseline_outputs
    assert st["decode"]["spec_proposed"] > 0


def test_spec_decode_self_draft_full_acceptance(seeded_prompts,
                                                baseline_outputs):
    from paddle_tpu.serving.decode_engine import TinyDecodeLM

    outs, st = _run_server(
        seeded_prompts, spec_k=3,
        draft_factory=lambda i: TinyDecodeLM())
    assert outs == baseline_outputs
    assert st["spec_acceptance_rate"] == 1.0


def test_all_three_flags_compose(seeded_prompts, baseline_outputs):
    outs, st = _run_server(seeded_prompts, spec_k=2,
                           prefill_chunk=8, kv_share=True)
    assert outs == baseline_outputs


def test_spec_rewind_under_pool_pressure():
    """A pool too small for the verify window preempts (deadline-
    aware) and rewinds — every request answered, zero leaks."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(2, 128, size=6) for _ in range(6)]
    outs, st = _run_server(prompts, spec_k=3, num_pages=10,
                           page_size=4, max_new_tokens=8)
    assert len(outs) == 6
    assert st["decode"]["preemptions"] > 0


def test_flags_default_off():
    from paddle_tpu import serving
    from paddle_tpu.flags import get_flag

    assert get_flag("prefill_chunk") == 0
    assert get_flag("kv_share") is False
    assert get_flag("spec_k") == 0
    cfg = serving.DecodeConfig()
    assert cfg.prefill_chunk == 0 and cfg.spec_k == 0
    srv = serving.DecodeServer(config=cfg)
    rep = srv.replicas[0]
    assert rep.draft_cache is None and rep.draft_model is None
    assert rep.cache.kv_share is False


def test_preemption_legacy_tiebreak_youngest():
    """Regression pin: with every sequence equally unconstrained, the
    victim is the YOUNGEST (the pre-ISSUE-11 behavior)."""
    from paddle_tpu import serving
    from paddle_tpu.serving.decode_engine import _Seq

    srv = serving.DecodeServer(config=serving.DecodeConfig(
        n_replicas=0 or 1, default_deadline_s=100.0))
    rep = srv.replicas[0]
    reqs = [srv.admission.submit({"ids": np.asarray([2, 3])},
                                 deadline_s=100.0)
            for _ in range(3)]
    rep.active = [_Seq(r, [2, 3], 8) for r in reqs]
    import time as _time

    idx = srv._preempt_victim(rep, _time.monotonic())
    assert idx == len(rep.active) - 1


def test_preemption_spares_deadline_at_risk_youngest():
    """The new policy: a youngest sequence that would miss its
    deadline if re-prefilled is spared while an older unconstrained
    sequence exists."""
    from paddle_tpu import serving
    from paddle_tpu.serving.decode_engine import _Seq

    srv = serving.DecodeServer(config=serving.DecodeConfig(
        n_replicas=1, preempt_slack_s=0.25))
    rep = srv.replicas[0]
    r_old = srv.admission.submit({"ids": np.asarray([2, 3])},
                                 deadline_s=100.0)
    r_young = srv.admission.submit({"ids": np.asarray([2, 3])},
                                   deadline_s=0.2)   # at risk
    rep.active = [_Seq(r_old, [2, 3], 8), _Seq(r_young, [2, 3], 8)]
    import time as _time

    idx = srv._preempt_victim(rep, _time.monotonic())
    assert idx == 0                  # the OLDER, unconstrained one


# ---------------------------------------------------------------------------
# the chunked-join SLO acceptance leg (PR-10 monitor as instrument)
# ---------------------------------------------------------------------------

def _chunked_join_slo(join_len, chunk, threshold_s, page_size=64):
    from paddle_tpu import serving
    from paddle_tpu.observability import slo as obs_slo

    pages = -(-(join_len + 64) // page_size) + 40
    cfg = serving.DecodeConfig(
        max_batch=4, max_new_tokens=24, page_size=page_size,
        num_pages=pages, n_replicas=1, default_deadline_s=300.0,
        prefill_chunk=chunk)
    srv = serving.DecodeServer(config=cfg).start()
    monitor = None
    try:
        rng = np.random.RandomState(3)
        # warm every shape — including one full-length chunked join,
        # so every pow2 table-width bucket compiles BEFORE the
        # measured window (the serving prewarm story: the SLO claim
        # is about steady-state joins, not first-compile)
        srv.decode(rng.randint(2, 128, size=join_len),
                   max_new_tokens=2, timeout=300.0)
        warm = [srv.submit(rng.randint(2, 128, size=4))
                for _ in range(2)]
        for f in warm:
            f.result(timeout=300.0)
        monitor = obs_slo.install(obs_slo.SLOMonitor(slos=[
            obs_slo.decode_inter_token(threshold_s=threshold_s,
                                       objective=0.99,
                                       window_s=120.0,
                                       fast_fraction=0.25)])) \
            .start(interval_s=0.05)
        # running streams decode while the long prompt joins
        streams = [srv.submit(rng.randint(2, 128, size=6))
                   for _ in range(3)]
        joiner = srv.submit(rng.randint(2, 128, size=join_len),
                            max_new_tokens=4)
        for f in streams + [joiner]:
            f.result(timeout=300.0)
        verdict = monitor.verdict()
    finally:
        if monitor is not None:
            monitor.stop()
        srv.stop()
    st = srv.stats()
    assert st["decode"]["prefill_chunks"] >= join_len // chunk - 1
    ok, detail = srv.page_accounting()
    assert ok, detail
    return verdict["decode_inter_token_p99"]


def test_chunked_join_keeps_inter_token_slo():
    """A 2k-token prompt joins a running batch under chunked prefill;
    the PR-10 decode_inter_token objective stays attained and never
    fires (the fast-lane shape of the 32k acceptance leg below)."""
    v = _chunked_join_slo(join_len=2048, chunk=128,
                          threshold_s=0.25)
    assert v["firing"] is False, v
    assert v["attained"] >= 0.99, v


def test_chunked_join_32k_slo():
    """THE ISSUE acceptance leg: a 32k-token prompt joins a running
    batch under chunked prefill and decode_inter_token stays
    attained (slow lane — ~32k/512 chunks of page writes)."""
    v = _chunked_join_slo(join_len=32768, chunk=512,
                          threshold_s=0.5, page_size=64)
    assert v["firing"] is False, v
    assert v["attained"] >= 0.99, v

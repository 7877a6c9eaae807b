"""Sharded serving suite (ISSUE 14): tp-sharded inference replicas on
mesh slices, and disaggregated prefill/decode pools with paged-KV
page-list handoff.

Covers: the column-parallel inference annotation pass (chain guard
included), slice carving, THE tp2 CPU-mesh bit-parity acceptance leg
(sharded replica outputs array_equal to the unsharded predictor with
params provably dim-sharded), flag-off no-op bit-parity, the
mesh-sliced ReplicaPool through the full server (kill-mid-batch
failover per slice + swap_predictor re-sharding), the page-list
detach/adopt/release primitives with the zero-device-copy assertion
and in-transit accounting, disagg-vs-single-tier token parity,
kill-mid-handoff on BOTH sides (exactly-once + zero leaks +
re-prefill fallback), deadline propagation across the tier boundary,
the handoff observability instruments, and registry persistence
across restarts (manifest re-adoption + typed fingerprint-mismatch
error)."""

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import inference, layers, serving
from paddle_tpu.distributed import faultinject
from paddle_tpu.distributed.faultinject import FaultPlan
from paddle_tpu.flags import set_flags
from paddle_tpu.ops.paged_kv import PagedKVCache
from paddle_tpu.parallel.gspmd import (MeshPlan, annotate_tp_inference,
                                       carve_slices)


@pytest.fixture
def sharded_flag():
    set_flags({"serving_sharded": True})
    yield
    set_flags({"serving_sharded": False})


def _save_model(tmp_path, in_dim=8, hidden=16, out_dim=4, scale=1.0,
                name="model"):
    """Tiny fc net (all widths tp2-divisible) saved as an inference
    model; returns (dir, probe, expected outputs)."""
    fluid.framework.switch_main_program(fluid.Program())
    fluid.framework.switch_startup_program(fluid.Program())
    from paddle_tpu import unique_name

    unique_name.switch({})
    x = layers.data("x", shape=[in_dim], dtype="float32")
    h = layers.fc(x, size=hidden, act="relu")
    pred = layers.fc(h, size=out_dim)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    if scale != 1.0:
        # make distinct model versions for swap tests
        from paddle_tpu.core.scope import global_scope

        for n in ("fc_0.w_0", "fc_1.w_0"):
            v = global_scope().find_var(n)
            v.set(np.asarray(v.get()) * scale)
    d = str(tmp_path / name)
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    probe = np.random.RandomState(0).rand(8, in_dim).astype(np.float32)
    expect, = exe.run(feed={"x": probe}, fetch_list=[pred])
    return d, probe, np.asarray(expect)


# ---------------------------------------------------------------------------
# annotation pass + slice carving
# ---------------------------------------------------------------------------

def test_annotate_tp_inference_column_only(tmp_path):
    """Every divisible fc weight gets (None, 'tp'), its bias ('tp',);
    column-only on purpose (full-width contractions = bit-exact)."""
    d, _, _ = _save_model(tmp_path)
    set_flags({"serving_sharded": False})
    p = inference.create_predictor(inference.Config(d))
    names = annotate_tp_inference(p._program, MeshPlan(dp=1, tp=2))
    assert "fc_0.w_0" in names and "fc_1.w_0" in names
    gb = p._program.global_block()
    assert tuple(gb.vars["fc_0.w_0"].sharding) == (None, "tp")
    assert tuple(gb.vars["fc_0.b_0"].sharding) == ("tp",)
    assert tuple(gb.vars["fc_1.w_0"].sharding) == (None, "tp")


def test_annotate_tp_inference_chain_guard(tmp_path):
    """A weight whose downstream matmul cannot shard is DE-annotated:
    a sharded activation reaching an unsharded contraction would make
    XLA sum partial products — the bit-exactness guarantee requires
    the whole chain or nothing."""
    d, _, _ = _save_model(tmp_path, out_dim=1)   # head width 1: no tp
    p = inference.create_predictor(inference.Config(d))
    names = annotate_tp_inference(p._program, MeshPlan(dp=1, tp=2))
    assert names == [], names
    assert all(v.sharding is None
               for v in p._program.global_block().vars.values())


def test_carve_slices():
    devs = list(range(8))
    assert carve_slices(devs, 2) == [[0, 1], [2, 3], [4, 5], [6, 7]]
    assert carve_slices(devs, 3) == [[0, 1, 2], [3, 4, 5]]  # 2 left over
    with pytest.raises(ValueError):
        carve_slices(devs[:1], 2)


# ---------------------------------------------------------------------------
# THE acceptance leg: tp2 bit-parity + provably dim-sharded params
# ---------------------------------------------------------------------------

def test_sharded_predictor_tp2_bit_parity(tmp_path, sharded_flag):
    """A tp2 mesh-sliced predictor on the CPU mesh serves outputs
    bit-identical (array_equal) to the unsharded predictor, with its
    params provably dim-sharded across the slice."""
    d, probe, expect = _save_model(tmp_path)
    set_flags({"serving_sharded": False})
    base = inference.create_predictor(inference.Config(d))
    base_out, = base.run([probe])
    set_flags({"serving_sharded": True})
    p = inference.create_predictor(inference.Config(d))
    info = p.shard(MeshPlan(dp=1, tp=2))
    assert info is not None and len(info["annotated"]) == 4
    out, = p.run([probe])
    assert np.array_equal(out, base_out)
    # provably dim-sharded: each device of the slice holds half the
    # output dim of every annotated weight
    si = p.sharding_info()
    assert si["fc_0.w_0"] == ((None, "tp"), [(8, 8)])
    assert si["fc_1.w_0"] == ((None, "tp"), [(16, 2)])
    w = p._scope.find_var("fc_0.w_0").get()
    assert len({s.device for s in w.addressable_shards}) == 2


def test_sharded_predictor_flag_off_noop(tmp_path):
    """Flag-off, shard() is a no-op: returns None, zero IR bytes
    changed, outputs bit-identical to never calling it."""
    d, probe, _ = _save_model(tmp_path)
    set_flags({"serving_sharded": False})
    base = inference.create_predictor(inference.Config(d))
    base_out, = base.run([probe])
    p = inference.create_predictor(inference.Config(d))
    assert p.shard(MeshPlan(dp=1, tp=2)) is None
    assert all(v.sharding is None
               for v in p._program.global_block().vars.values())
    out, = p.run([probe])
    assert np.array_equal(out, base_out)
    assert p.sharding_info() == {}


# ---------------------------------------------------------------------------
# mesh-sliced ReplicaPool through the full server
# ---------------------------------------------------------------------------

def test_sliced_pool_serves_bit_identical(tmp_path, sharded_flag):
    """ServingConfig(mesh_plan=tp2, n_replicas=None) carves the
    8-device CPU mesh into 4 slices — one replica per slice — and the
    served outputs are array_equal to the unsharded predictor."""
    d, probe, _ = _save_model(tmp_path)
    set_flags({"serving_sharded": False})
    base = inference.create_predictor(inference.Config(d))
    base_out, = base.run([probe])
    set_flags({"serving_sharded": True})
    cfg = serving.ServingConfig(n_replicas=None, max_batch=8,
                                default_deadline_s=30.0,
                                mesh_plan=MeshPlan(dp=1, tp=2))
    factory = lambda i: inference.create_predictor(  # noqa: E731
        inference.Config(d))
    with serving.InferenceServer(factory, cfg) as srv:
        assert len(srv.pool.replicas) == 4
        mesh = srv.pool.mesh_stats()
        assert mesh["slices"] == 4 and mesh["slice_size"] == 2
        # every replica's slice is disjoint
        slices = [tuple(v) for v in mesh["replica_slices"].values()]
        assert len(set(slices)) == 4
        out, = srv.infer({"x": probe}, timeout=60.0)
        assert np.array_equal(out, base_out)
        assert srv.stats()["accounted"]


def test_sliced_pool_kill_mid_batch_failover(tmp_path, sharded_flag):
    """Kill-mid-batch failover works PER SLICE: a killed sharded
    replica's batch requeues onto a surviving slice and every request
    is answered exactly once with the unsharded predictor's output."""
    d, probe, _ = _save_model(tmp_path)
    set_flags({"serving_sharded": False})
    base = inference.create_predictor(inference.Config(d))
    base_out, = base.run([probe])
    set_flags({"serving_sharded": True})
    cfg = serving.ServingConfig(n_replicas=2, max_batch=4,
                                default_deadline_s=30.0,
                                restart_dead=False,
                                mesh_plan=MeshPlan(dp=1, tp=2))
    factory = lambda i: inference.create_predictor(  # noqa: E731
        inference.Config(d))
    plan = FaultPlan().on("serving_infer", 0, "kill")
    with serving.InferenceServer(factory, cfg) as srv:
        with faultinject.installed(plan):
            reqs = [srv.submit({"x": probe[i:i + 1]})
                    for i in range(6)]
            outs = [r.result(timeout=60.0)[0] for r in reqs]
        # the reference ran the 8 probe rows as one batch; here six
        # one-row requests ride batches of 4 and 2, and the killed
        # batch is re-run in whatever batch the survivor forms.  The
        # tp2 program is compiled per batch size, and below 4 rows the
        # CPU's partitioned matmul rounds another way (the sharded
        # predictor alone at 1, 2 and 3 rows: 0.3 to 0.8 float32 units
        # in the last place of the largest output; bit-equal from 4
        # up, which test_sharded_predictor_tp2_bit_parity holds).  So:
        # the right answer to 2 such units, whatever batch served it.
        atol = 2 * np.finfo(np.float32).eps * np.abs(base_out).max()
        for i, o in enumerate(outs):
            np.testing.assert_allclose(o, base_out[i:i + 1], rtol=0,
                                       atol=atol)
        st = srv.stats()
        assert st["accounted"]
        assert sum(1 for r in srv.pool.replicas if r.alive) == 1


def test_sliced_pool_swap_predictor_reshards(tmp_path, sharded_flag):
    """The PR-13 rollout primitive per slice: swap_predictor onto a
    prewarmed UNsharded predictor re-shards it onto the replica's
    slice — the swapped-in program serves sharded, bit-identical to
    its own unsharded reference."""
    d1, probe, _ = _save_model(tmp_path, name="v1")
    d2, _, _ = _save_model(tmp_path, scale=1.5, name="v2")
    set_flags({"serving_sharded": False})
    ref2 = inference.create_predictor(inference.Config(d2))
    ref2_out, = ref2.run([probe])
    # one prewarmed predictor PER replica, like the rollout controller
    # (sharing one incoming scope across slices would re-shard the
    # same compiled program per slice)
    incoming = [inference.create_predictor(inference.Config(d2))
                for _ in range(2)]
    set_flags({"serving_sharded": True})
    cfg = serving.ServingConfig(n_replicas=2, max_batch=8,
                                default_deadline_s=30.0,
                                mesh_plan=MeshPlan(dp=1, tp=2))
    factory = lambda i: inference.create_predictor(  # noqa: E731
        inference.Config(d1))
    with serving.InferenceServer(factory, cfg) as srv:
        for rep, inc in zip(list(srv.pool.replicas), incoming):
            srv.pool.swap_predictor(rep.index, inc, version="v2")
        out, = srv.infer({"x": probe}, timeout=60.0)
        assert np.array_equal(out, ref2_out)
        for rep in srv.pool.replicas:
            assert rep.predictor.sharding_info(), \
                "swapped-in predictor not re-sharded onto its slice"


# ---------------------------------------------------------------------------
# page-list handoff primitives (ops/paged_kv.py)
# ---------------------------------------------------------------------------

def test_detach_adopt_zero_copy_and_accounting():
    """detach/adopt move ONLY host metadata: the device pools are the
    SAME array objects before and after (zero full-KV copies on the
    handoff path — asserted by identity, since any device write would
    rebind a new functional array), in-transit pages count as in-use,
    and release frees them through the ordinary path."""
    rng = np.random.RandomState(0)
    cache = PagedKVCache(num_pages=8, page_size=4, num_heads=2,
                         head_dim=4, kv_share=False)
    k = rng.randn(6, 2, 4).astype(np.float32)
    v = rng.randn(6, 2, 4).astype(np.float32)
    slot = cache.prefill(k, v)
    kp, vp = cache.k_pages, cache.v_pages
    handle = cache.detach(slot)
    assert cache.k_pages is kp and cache.v_pages is vp
    assert set(handle) == {"id", "pages", "length"}
    assert handle["length"] == 6 and len(handle["pages"]) == 2
    assert cache.in_transit_pages() == 2
    assert cache.in_use_pages() == 2          # in transit IS in use
    ok, detail = cache.check_accounting()
    assert ok, detail
    new_slot = cache.adopt(handle)
    assert cache.k_pages is kp and cache.v_pages is vp
    assert cache.seq_len(new_slot) == 6
    assert cache.in_transit_pages() == 0
    assert list(np.asarray(cache.tables_for([new_slot])[0])[:2]) == \
        handle["pages"]
    ok, detail = cache.check_accounting()
    assert ok, detail
    with pytest.raises(KeyError):
        cache.adopt(handle)                    # settled handles die
    # abort path: detached pages released -> back on the free list
    h2 = cache.detach(new_slot)
    assert cache.release_in_transit(h2) == 2
    assert cache.free_pages() == 8 and cache.in_use_pages() == 0
    ok, detail = cache.check_accounting()
    assert ok, detail


def test_detach_adopt_preserves_shared_refcounts():
    """Under kv_share a detached slot's radix-shared prefix pages keep
    their other holders: the handle owns exactly the slot's
    references, and releasing it never frees a page someone else
    holds."""
    rng = np.random.RandomState(1)
    cache = PagedKVCache(num_pages=8, page_size=4, num_heads=2,
                         head_dim=4, kv_share=True)
    toks = list(range(8))
    k = rng.randn(8, 2, 4).astype(np.float32)
    v = rng.randn(8, 2, 4).astype(np.float32)
    s1 = cache.prefill(k, v, tokens=toks)
    s2 = cache.prefill(k, v, tokens=toks)      # fully shared
    assert cache.shared_pages() == 2
    h = cache.detach(s2)
    assert cache.shared_pages() == 2           # handle still holds
    cache.release_in_transit(h)
    assert cache.shared_pages() == 0
    assert cache.in_use_pages() == 2           # s1 keeps its pages
    cache.free(s1)
    ok, detail = cache.check_accounting()
    assert ok and cache.free_pages() == 8, detail


# ---------------------------------------------------------------------------
# disaggregated serving engine
# ---------------------------------------------------------------------------

_PROMPTS = [np.array([3, 4, 5], np.int64), np.array([7, 8], np.int64),
            np.array([9, 10, 11, 12, 13], np.int64)]


def _single_tier_reference():
    srv = serving.DecodeServer(config=serving.DecodeConfig(
        max_batch=4, n_replicas=1, max_new_tokens=8,
        default_deadline_s=60.0)).start()
    try:
        return [srv.decode(p, timeout=60.0) for p in _PROMPTS]
    finally:
        srv.stop()


def test_disagg_flag_off_is_single_tier():
    """Flag-off bit-parity: a default DecodeServer has NO prefill
    tier (stats()['disagg'] is None, zero prefill workers) — the
    validated PR-13 engine byte-for-byte."""
    srv = serving.DecodeServer(config=serving.DecodeConfig(
        max_batch=4, n_replicas=1)).start()
    try:
        assert srv.prefill_replicas == []
        assert srv._shared_cache is None
        assert srv.stats()["disagg"] is None
        assert srv.replicas[0].owns_cache
    finally:
        srv.stop()


def test_disagg_outputs_token_identical_and_zero_copy():
    """The disaggregated engine emits token-for-token the same
    outputs as the single-tier engine, the handoff moves only a page
    list (the shared pool arrays are identical objects across the
    prefill->adopt window of a whole run), and the shared pool drains
    to zero."""
    base = _single_tier_reference()
    cfg = serving.DecodeConfig(max_batch=4, n_replicas=2,
                               max_new_tokens=8,
                               default_deadline_s=60.0,
                               disagg_prefill=True,
                               n_prefill_replicas=2)
    srv = serving.DecodeServer(config=cfg).start()
    try:
        outs = [srv.decode(p, timeout=60.0) for p in _PROMPTS]
        st = srv.stats()
        assert st["disagg"]["handoffs_offered"] >= 3
        assert st["disagg"]["handoffs_adopted"] >= 3
        ok, detail = srv.page_accounting()
        assert ok, detail
    finally:
        srv.stop()
    assert all(np.array_equal(a, b) for a, b in zip(base, outs))
    sc = srv._shared_cache
    assert sc.in_use_pages() == 0 and sc.in_transit_pages() == 0


def test_disagg_rejects_spec_k():
    with pytest.raises(ValueError):
        serving.DecodeConfig(disagg_prefill=True, spec_k=2)


def test_disagg_kill_prefill_mid_handoff():
    """THE chaos window the tentpole names: a prefill replica killed
    after page allocation but BEFORE the decode tier adopts — pages
    released, the sequence re-prefills on the surviving prefill
    replica, exactly-once answers, zero leaks, outputs bit-identical
    to fault-free."""
    base = _single_tier_reference()
    cfg = serving.DecodeConfig(max_batch=4, n_replicas=1,
                               max_new_tokens=8,
                               default_deadline_s=60.0,
                               disagg_prefill=True,
                               n_prefill_replicas=2,
                               restart_dead=False)
    srv = serving.DecodeServer(config=cfg).start()
    plan = FaultPlan().on("serving_prefill", 0, "kill")
    try:
        with faultinject.installed(plan):
            reqs = [srv.submit(p, deadline_s=60.0) for p in _PROMPTS]
            outs = [r.result(timeout=60.0)[0] for r in reqs]
        st = srv.stats()
        assert st["disagg"]["prefill_kills"] == 1
        assert st["decode"]["failovers"] >= 1     # re-prefill fallback
        assert st["accounted"]
        ok, detail = srv.page_accounting()
        assert ok, detail
        # handoff observability (satellite): outcome counter + latency
        # histogram carry the run
        from paddle_tpu.observability import metrics as obs_metrics

        snap = obs_metrics.registry().snapshot()
        series = snap["paddle_tpu_disagg_handoffs_total"]["series"]
        by = {s["labels"]["outcome"]: s["value"] for s in series}
        assert by.get("adopted", 0) >= 3 and by.get("killed", 0) >= 1
        assert snap["paddle_tpu_disagg_handoff_seconds"]["series"][0][
            "count"] >= 3
    finally:
        srv.stop()
    assert all(np.array_equal(a, b) for a, b in zip(base, outs))
    sc = srv._shared_cache
    assert sc.in_use_pages() == 0 and sc.in_transit_pages() == 0


def test_disagg_kill_decode_after_adoption():
    """The other chaos window: a decode replica killed right after
    adopting a handoff — its slots freed on the SHARED pool (never a
    wholesale reset that would nuke the other tier), sequences
    re-prefill from token history, exactly-once + zero leaks."""
    base = _single_tier_reference()
    cfg = serving.DecodeConfig(max_batch=4, n_replicas=2,
                               max_new_tokens=8,
                               default_deadline_s=60.0,
                               disagg_prefill=True,
                               n_prefill_replicas=1,
                               restart_dead=False)
    srv = serving.DecodeServer(config=cfg).start()
    plan = FaultPlan().on("serving_decode", 1, "kill")
    try:
        with faultinject.installed(plan):
            reqs = [srv.submit(p, deadline_s=60.0) for p in _PROMPTS]
            outs = [r.result(timeout=60.0)[0] for r in reqs]
        st = srv.stats()
        assert st["decode"]["kills"] == 1
        assert st["accounted"]
        ok, detail = srv.page_accounting()
        assert ok, detail
    finally:
        srv.stop()
    assert all(np.array_equal(a, b) for a, b in zip(base, outs))
    sc = srv._shared_cache
    assert sc.in_use_pages() == 0 and sc.in_transit_pages() == 0


def test_disagg_deadline_propagates_across_tiers():
    """Deadline propagation across the tier boundary: a handoff whose
    request expires IN TRANSIT (seeded prefill-side delay) is released
    at adoption — pages freed, the request answered with the typed
    expiry, never silently parked."""
    cfg = serving.DecodeConfig(max_batch=4, n_replicas=1,
                               max_new_tokens=8,
                               default_deadline_s=60.0,
                               disagg_prefill=True,
                               n_prefill_replicas=1)
    srv = serving.DecodeServer(config=cfg).start()
    plan = FaultPlan().on("serving_prefill", 0, "delay=0.4")
    try:
        with faultinject.installed(plan):
            req = srv.submit(np.array([3, 4, 5], np.int64),
                             deadline_s=0.15)
            with pytest.raises(serving.DeadlineExpiredError):
                req.result(timeout=30.0)
        st = srv.stats()
        assert st["disagg"]["handoffs_expired"] == 1
        assert st["accounted"]
        ok, detail = srv.page_accounting()
        assert ok, detail
    finally:
        srv.stop()
    sc = srv._shared_cache
    assert sc.in_use_pages() == 0 and sc.in_transit_pages() == 0


def test_disagg_typed_handoff_exhaustion():
    """Every handoff lost (seeded drop on every prefill) exhausts the
    attempt budget into the typed HandoffError — exactly-once still
    holds (the reply is the typed error, never silence)."""
    cfg = serving.DecodeConfig(max_batch=4, n_replicas=1,
                               max_new_tokens=8,
                               default_deadline_s=60.0,
                               disagg_prefill=True,
                               n_prefill_replicas=1, max_attempts=2)
    srv = serving.DecodeServer(config=cfg).start()
    plan = FaultPlan()
    for i in range(16):
        plan.on("serving_prefill", i, "drop")
    try:
        with faultinject.installed(plan):
            req = srv.submit(np.array([3, 4], np.int64),
                             deadline_s=30.0)
            with pytest.raises(serving.HandoffError) as ei:
                req.result(timeout=30.0)
            assert ei.value.code == "handoff"
        st = srv.stats()
        assert st["disagg"]["handoffs_lost"] >= 2
        assert st["accounted"]
    finally:
        srv.stop()
    sc = srv._shared_cache
    assert sc.in_use_pages() == 0 and sc.in_transit_pages() == 0


# ---------------------------------------------------------------------------
# satellite: registry persistence across restarts
# ---------------------------------------------------------------------------

def test_registry_persists_and_readopts(tmp_path):
    """ModelRegistry(root) re-adopts its versions from the manifest on
    construction: a relaunched fleet recovers its catalog without
    re-registering, version numbers and dedupe-by-fingerprint
    intact."""
    d1, _, _ = _save_model(tmp_path, name="m_v1")
    # versions are deduped by PROGRAM fingerprint: a new version needs
    # new program bytes, not just new params
    d2, _, _ = _save_model(tmp_path, hidden=32, name="m_v2")
    root = str(tmp_path / "registry")
    reg = serving.ModelRegistry(root)
    v1 = reg.register("m", d1)
    v2 = reg.register("m", d2)
    assert (v1.version, v2.version) == (1, 2)
    # "process restart": a fresh registry over the same root
    reg2 = serving.ModelRegistry(root)
    assert reg2.adopted == 2
    assert [v.version for v in reg2.versions("m")] == [1, 2]
    assert reg2.get("m").fingerprint == v2.fingerprint
    assert reg2.get("m", 1).model_dir == d1
    # dedupe survives the restart: same bytes -> the EXISTING version
    assert reg2.register("m", d1).version == 1
    # and a genuinely new dir still mints v3, persisted for the next
    # relaunch
    d3, _, _ = _save_model(tmp_path, hidden=64, name="m_v3")
    assert reg2.register("m", d3).version == 3
    assert serving.ModelRegistry(root).adopted == 3


def test_registry_manifest_fingerprint_mismatch(tmp_path):
    """Re-adoption verifies every model dir's on-disk ProgramDesc
    against the manifest fingerprint — a rewritten dir surfaces the
    typed ManifestMismatchError instead of silently serving different
    bytes under the old version number."""
    d1, _, _ = _save_model(tmp_path, name="mm_v1")
    root = str(tmp_path / "registry")
    serving.ModelRegistry(root).register("m", d1)
    # rewrite the model dir with a DIFFERENT program
    _save_model(tmp_path, hidden=32, name="mm_v1")
    with pytest.raises(serving.ManifestMismatchError) as ei:
        serving.ModelRegistry(root)
    assert ei.value.code == "manifest_mismatch"
    assert "mismatch" in str(ei.value).lower() or \
        "fingerprint" in str(ei.value)

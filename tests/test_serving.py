"""Serving-tier suite (ISSUE 6): continuous-batching inference server
with admission control, deadline propagation, replica failover, and
graceful degradation.

Covers: typed feed validation (satellite), the compile-once bucket
cache, typed overload shedding, deadline sheds before batch formation
AND before result delivery, the max-wait latency bound, the
kill/drop/delayed-health failover acceptance leg with exact request-id
accounting, graceful drain, fault-plan teardown (no leak into a
flag-off run), the PADDLE_TPU_HEALTH_INTERVAL knob, NamedSharding
param replication, and (slow lane) the 2x-overload goodput/p99
acceptance leg via tools/serving_load.py.
"""

import importlib.util
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import inference, layers, serving
from paddle_tpu.distributed import faultinject
from paddle_tpu.distributed.faultinject import FaultPlan


def _tools_mod(name):
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _save_model(tmp_path, in_dim=8):
    """Tiny fc net saved as an inference model; returns (dir, probe,
    expected outputs for the probe)."""
    x = layers.data("x", shape=[in_dim], dtype="float32")
    h = layers.fc(x, size=16, act="relu")
    pred = layers.fc(h, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    probe = np.random.RandomState(0).rand(8, in_dim).astype(np.float32)
    expect, = exe.run(feed={"x": probe}, fetch_list=[pred])
    return d, probe, np.asarray(expect)


def _factory(model_dir):
    return lambda i: inference.create_predictor(
        inference.Config(model_dir))


class _SlowPredictor:
    """Predictor wrapper whose run() sleeps — a wedged/slow replica."""

    def __init__(self, inner, delay_s):
        self._inner = inner
        self._delay = delay_s

    def run(self, feeds):
        time.sleep(self._delay)
        return self._inner.run(feeds)

    def __getattr__(self, name):
        return getattr(self._inner, name)


# ---------------------------------------------------------------------------
# satellite: typed feed validation in the Predictor
# ---------------------------------------------------------------------------

def test_predictor_feed_validation_typed_errors(tmp_path):
    """A wrong name/shape/dtype feed raises FeedValidationError naming
    the offending feed BEFORE compilation — not an XLA trace error."""
    d, probe, expect = _save_model(tmp_path)
    p = inference.create_predictor(inference.Config(d))
    specs = p.feed_specs()
    assert "x" in specs and specs["x"][1] == np.dtype("float32")

    with pytest.raises(inference.FeedValidationError) as ei:
        p.run([probe.astype(np.float64)])           # wrong dtype
    assert "'x'" in str(ei.value) and "float64" in str(ei.value)
    with pytest.raises(inference.FeedValidationError) as ei:
        p.run([probe[:, :5]])                       # wrong trailing dim
    assert "'x'" in str(ei.value) and "shape" in str(ei.value)
    with pytest.raises(inference.FeedValidationError):
        p.run([probe.reshape(8, 2, 4)])             # wrong rank
    with pytest.raises(inference.FeedValidationError):
        p.run([probe, probe])                       # wrong feed count
    with pytest.raises(inference.FeedValidationError) as ei:
        p.validate_feeds({"y": probe})              # unknown + missing
    assert "missing" in str(ei.value)
    with pytest.raises(inference.FeedValidationError) as ei:
        p.validate_feeds({"x": probe, "y": probe})
    assert "'y'" in str(ei.value)
    # the valid feed still runs (any batch extent)
    out, = p.run([probe[:3]])
    np.testing.assert_allclose(out, expect[:3], rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# roundtrip + compile-once bucket cache
# ---------------------------------------------------------------------------

def test_server_roundtrip_and_compile_once_bucket_cache(tmp_path):
    """Mixed-size requests batch, pad to buckets, and come back
    per-request correct; the predictor's compile cache holds at most
    one entry per bucket (pad-to-bucket = compile-once)."""
    d, probe, expect = _save_model(tmp_path)
    cfg = serving.ServingConfig(n_replicas=1, max_batch=8,
                                max_wait_s=0.005,
                                default_deadline_s=10.0)
    with serving.InferenceServer(_factory(d), cfg) as srv:
        reqs, slices = [], []
        for rows, off in [(1, 0), (3, 1), (2, 4), (1, 6), (1, 7),
                          (2, 0), (3, 3)]:
            reqs.append(srv.submit({"x": probe[off:off + rows]}))
            slices.append((rows, off))
        for req, (rows, off) in zip(reqs, slices):
            out, = req.result(timeout=30)
            np.testing.assert_allclose(out, expect[off:off + rows],
                                       rtol=1e-5, atol=1e-6)
        st = srv.stats()
        assert st["accounted"] and st["admission"]["answered_ok"] == 7
        assert st["batcher"]["bucket_shapes"] <= len(cfg.buckets)
        # the compile-once contract, asserted at the compile cache
        n_compiled = len(
            srv.pool.replicas[0].predictor._compiled._cache)
        assert 0 < n_compiled <= len(cfg.buckets)
    assert srv.stats()["outstanding"] == 0


def test_default_buckets_and_bucket_for():
    assert serving.default_buckets(8) == (1, 2, 4, 8)
    assert serving.default_buckets(12) == (1, 2, 4, 8, 12)
    b = serving.ShapeBucketBatcher(None, None, buckets=(1, 2, 4, 8))
    assert b.bucket_for(3) == 4 and b.bucket_for(8) == 8
    assert b.bucket_for(9) == 9      # oversized: exact, uncached


# ---------------------------------------------------------------------------
# admission control + deadlines
# ---------------------------------------------------------------------------

def test_overload_sheds_with_typed_reply_never_silently(tmp_path):
    """Over capacity, submit() rejects with the typed OverloadedError
    immediately; every ADMITTED request is still answered."""
    d, probe, _ = _save_model(tmp_path)
    base = _factory(d)
    cfg = serving.ServingConfig(
        n_replicas=1, max_batch=2, max_wait_s=0.001,
        default_deadline_s=10.0, queue_capacity=4,
        dispatch_capacity=1)
    srv = serving.InferenceServer(
        lambda i: _SlowPredictor(base(i), 0.15), cfg).start()
    try:
        admitted, shed = [], 0
        for i in range(30):
            try:
                admitted.append(srv.submit({"x": probe[:1]}))
            except serving.OverloadedError:
                shed += 1
        assert shed > 0                       # typed, immediate
        for req in admitted:
            req.result(timeout=30)            # all admitted answered
        st = srv.stats()
        assert st["accounted"]
        assert st["admission"]["rejected_overloaded"] == shed
        assert st["admission"]["answered_ok"] == len(admitted)
    finally:
        srv.stop()


def test_deadline_sheds_before_batch_and_before_delivery(tmp_path):
    """Expired requests are answered with the typed expired error —
    before batch formation (no compute spent) and, for requests that
    expire while their batch computes, before result delivery."""
    d, probe, _ = _save_model(tmp_path)
    base = _factory(d)
    cfg = serving.ServingConfig(
        n_replicas=1, max_batch=2, max_wait_s=0.001,
        default_deadline_s=0.08, queue_capacity=64,
        dispatch_capacity=1)
    srv = serving.InferenceServer(
        lambda i: _SlowPredictor(base(i), 0.12), cfg).start()
    try:
        reqs = [srv.submit({"x": probe[:1]}) for _ in range(10)]
        outcomes = {"ok": 0, "expired": 0}
        for req in reqs:
            try:
                req.result(timeout=30)
                outcomes["ok"] += 1
            except serving.DeadlineExpiredError:
                outcomes["expired"] += 1
        assert outcomes["expired"] > 0
        st = srv.stats()
        assert st["accounted"]
        # compute was saved: far fewer batches ran than would have
        # without the pre-formation/pre-execution sheds
        ran = sum(r.batches for r in srv.pool.replicas)
        assert ran < len(reqs)
        shed_early = st["batcher"]["shed_expired"] + \
            st["pool"]["shed_expired_batches"]
        assert shed_early + st["admission"]["answered_expired"] > 0
    finally:
        srv.stop()


def test_max_wait_timer_bounds_latency_at_low_load(tmp_path):
    """A lone request must not wait for batch-mates beyond max_wait."""
    d, probe, expect = _save_model(tmp_path)
    cfg = serving.ServingConfig(n_replicas=1, max_batch=8,
                                max_wait_s=0.02,
                                default_deadline_s=10.0)
    with serving.InferenceServer(_factory(d), cfg) as srv:
        srv.infer({"x": probe[:1]}, timeout=30)   # warm the compile
        t0 = time.monotonic()
        out, = srv.infer({"x": probe[:1]}, timeout=30)
        latency = time.monotonic() - t0
        np.testing.assert_allclose(out, expect[:1], rtol=1e-5,
                                   atol=1e-6)
        assert latency < 1.0        # bounded; never waits to fill 8


# ---------------------------------------------------------------------------
# acceptance: failover + exactly-once + drain under a seeded fault plan
# ---------------------------------------------------------------------------

def test_failover_exactly_once_accounting_and_drain(tmp_path):
    """ISSUE 6 acceptance: under a seeded plan that kills one replica
    mid-batch, delays health replies, and drops one reply frame, the
    server answers EVERY admitted request exactly once (request-id
    accounting), keeps serving on the survivor with the failed batch
    transparently requeued, and drain() completes all in-flight work."""
    d, probe, expect = _save_model(tmp_path)
    plan = (FaultPlan()
            .on("serving_infer", 1, "kill")       # replica dies mid-batch
            .on("serving_infer", 3, "drop")       # reply frame lost
            .on("serving_health", 0, "delay=0.2"))  # slow health reply
    cfg = serving.ServingConfig(
        n_replicas=2, max_batch=4, max_wait_s=0.005,
        default_deadline_s=30.0, restart_dead=False,
        health_interval_s=0.05, queue_capacity=64)
    rng = np.random.RandomState(1)
    with faultinject.installed(plan) as inj:
        srv = serving.InferenceServer(_factory(d), cfg).start()
        reqs = []
        for i in range(24):
            row = int(rng.randint(0, len(probe)))
            reqs.append(srv.submit({"x": probe[row:row + 1]},
                                   request_id=f"req-{i}"))
            time.sleep(0.002)
        answered_ids = set()
        for req in reqs:
            out, = req.result(timeout=60)     # raises on a typed reply
            row = None                        # correctness through
            assert out.shape == (1, 1)        # failover
            answered_ids.add(req.id)
            assert not req.complete([out])    # second answer refused
        # exactly once: every admitted id answered, none twice
        assert answered_ids == {f"req-{i}" for i in range(24)}
        leftovers = srv.stop()
        st = srv.stats()
        assert leftovers == 0                 # drain fully clean
        assert st["accounted"] and st["outstanding"] == 0
        assert st["admission"]["admitted"] == 24
        assert st["admission"]["answered_ok"] == 24
        # the plan really fired and the batch failed over
        kinds = {k for _, _, k in inj.log}
        assert "kill" in kinds and "drop" in kinds
        assert st["pool"]["requeues"] >= 2
        # one survivor serving, the other dead; which of the two took
        # the second batch, and the kill, is the scheduler's timing
        (survivor,) = srv.pool.live_replicas()
        assert st["pool"]["replicas"][1 - survivor]["alive"] is False
    assert faultinject.maybe_injector() is None


def test_drain_answers_stragglers_with_typed_shutdown(tmp_path):
    """drain() completes what it can and answers the rest with the
    typed ShutdownError — nothing silent; post-drain submits reject."""
    d, probe, _ = _save_model(tmp_path)
    base = _factory(d)
    cfg = serving.ServingConfig(
        n_replicas=1, max_batch=2, max_wait_s=0.001,
        default_deadline_s=30.0, queue_capacity=64,
        dispatch_capacity=1)
    srv = serving.InferenceServer(
        lambda i: _SlowPredictor(base(i), 0.2), cfg).start()
    reqs = [srv.submit({"x": probe[:1]}) for _ in range(8)]
    leftovers = srv.stop(drain_timeout=0.3)   # too short for all 8
    outcomes = {"ok": 0, "shutdown": 0}
    for req in reqs:
        try:
            req.result(timeout=5)
            outcomes["ok"] += 1
        except serving.ShutdownError:
            outcomes["shutdown"] += 1
    assert outcomes["shutdown"] == leftovers > 0
    assert outcomes["ok"] + outcomes["shutdown"] == 8
    assert srv.stats()["accounted"]
    with pytest.raises(serving.ShutdownError):
        srv.submit({"x": probe[:1]})


def test_graceful_drain_completes_every_admitted_request(tmp_path):
    """With a sufficient timeout, drain is fully clean: zero typed-
    shutdown answers, all work completed."""
    d, probe, _ = _save_model(tmp_path)
    cfg = serving.ServingConfig(n_replicas=2, max_batch=4,
                                max_wait_s=0.002,
                                default_deadline_s=30.0)
    srv = serving.InferenceServer(_factory(d), cfg).start()
    reqs = [srv.submit({"x": probe[:2]}) for _ in range(12)]
    assert srv.stop() == 0                    # clean drain
    for req in reqs:
        assert len(req.result(timeout=1)) == 1
    c = srv.stats()["admission"]
    assert c["answered_ok"] == 12 and c["answered_shutdown"] == 0


# ---------------------------------------------------------------------------
# satellite: fault-plan teardown must not leak into a flag-off run
# ---------------------------------------------------------------------------

def test_fault_plan_teardown_does_not_leak_into_next_run(tmp_path,
                                                         monkeypatch):
    """A plan installed during a serving run must be fully torn down:
    the next (flag-off) run sees zero faults — no requeues, no dead
    replicas, all-ok accounting.  Covers both the programmatic and the
    env installation paths."""
    monkeypatch.delenv("PADDLE_TPU_FAULT_PLAN", raising=False)
    d, probe, _ = _save_model(tmp_path)
    cfg = serving.ServingConfig(n_replicas=2, max_batch=4,
                                max_wait_s=0.002,
                                default_deadline_s=30.0,
                                restart_dead=False)
    plan = FaultPlan().on("serving_infer", 0, "kill")
    with faultinject.installed(plan) as inj:
        srv = serving.InferenceServer(_factory(d), cfg).start()
        for _ in range(4):
            srv.infer({"x": probe[:1]}, timeout=30)
        srv.stop()
        assert inj.log                       # the plan really fired
    assert faultinject.maybe_injector() is None
    # env path: a plan text parsed from the env is dropped with it
    monkeypatch.setenv("PADDLE_TPU_FAULT_PLAN", "serving_infer@0:kill")
    assert faultinject.maybe_injector() is not None
    monkeypatch.delenv("PADDLE_TPU_FAULT_PLAN")
    assert faultinject.maybe_injector() is None
    # the subsequent flag-off run is fault-free
    srv2 = serving.InferenceServer(_factory(d), cfg).start()
    for _ in range(4):
        srv2.infer({"x": probe[:1]}, timeout=30)
    assert srv2.stop() == 0
    st = srv2.stats()
    assert st["pool"]["requeues"] == 0
    assert st["pool"]["batches_failed"] == 0
    assert srv2.pool.live_replicas() == [0, 1]
    assert st["admission"]["answered_ok"] == 4


# ---------------------------------------------------------------------------
# satellite: health-probe interval knob + pool observability
# ---------------------------------------------------------------------------

def test_health_interval_env_knob_consumed_by_pool(tmp_path,
                                                   monkeypatch):
    """PADDLE_TPU_HEALTH_INTERVAL drives the pool's probe cadence (the
    same knob distributed.rpc.health_probe_interval serves)."""
    from paddle_tpu.distributed.rpc import health_probe_interval

    monkeypatch.setenv("PADDLE_TPU_HEALTH_INTERVAL", "0.02")
    assert health_probe_interval() == 0.02
    d, _, _ = _save_model(tmp_path)
    pool = serving.ReplicaPool(_factory(d), n_replicas=1).start()
    try:
        assert pool._health_interval == 0.02
        time.sleep(0.25)
        st = pool.stats()
        assert st["probes"] >= 3              # probing at the env rate
        rep = st["replicas"][0]
        assert rep["alive"] and rep["last_health_age_s"] < 1.0
        assert "breaker" in rep               # breaker state visible
    finally:
        pool.stop()


# ---------------------------------------------------------------------------
# NamedSharding replication (multi-device serving shape, CPU mesh)
# ---------------------------------------------------------------------------

def test_replicate_predictor_params_namedsharding(tmp_path):
    """replicate_predictor_params places the weights replicated over
    the (virtual 8-device) mesh — the SNIPPETS [2]/[3] replicate idiom
    — and the predictor still answers bit-consistently."""
    import jax

    d, probe, expect = _save_model(tmp_path)
    p = inference.create_predictor(inference.Config(d))
    mesh = serving.replicate_predictor_params(p)
    assert mesh is not None
    n_dev = len(jax.devices())
    replicated = [v.get() for v in p._scope.vars.values()
                  if v.get() is not None and
                  hasattr(v.get(), "sharding")]
    assert replicated
    assert all(len(a.sharding.device_set) == n_dev
               for a in replicated)
    out, = p.run([probe])
    np.testing.assert_allclose(out, expect, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# acceptance (slow lane): 2x overload — shedding keeps p99 within the
# deadline while goodput stays >= 80% of single-replica capacity
# ---------------------------------------------------------------------------

def test_overload2x_goodput_and_p99_acceptance(tmp_path):
    """ISSUE 6 acceptance, off-chip on CPU via the load generator: at
    2x the measured single-replica capacity, typed load shedding keeps
    admitted-request p99 within the configured deadline and goodput
    >= 80% of capacity."""
    sl = _tools_mod("serving_load")
    deadline_ms = 500.0
    # compute-bound model so the (single-thread) generator is not the
    # bottleneck being measured
    mdir = sl.build_model(str(tmp_path), in_dim=512, hidden=1024,
                          depth=6)
    srv = sl.make_server(mdir, replicas=1, max_batch=16,
                         deadline_ms=deadline_ms)
    try:
        cap = sl.measure_capacity(srv, seconds=1.0)
        assert cap > 0
        rec = sl.run_open_loop(srv, qps=2.0 * cap, seconds=2.5,
                               seed=7, deadline_s=deadline_ms / 1000.0)
    finally:
        srv.stop()
    assert rec["accounted"], rec
    assert rec["shed"] > 0, rec               # overload really shed
    # every admitted request was answered within its deadline window
    assert rec["p99_ms"] is not None and rec["p99_ms"] <= deadline_ms, \
        rec
    assert rec["expired"] <= 0.05 * rec["admitted"], rec
    assert rec["goodput_qps"] >= 0.8 * cap, (rec, cap)

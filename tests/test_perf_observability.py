"""Performance-observability suite (ISSUE 10): device-time
attribution, head-based sampled tracing, and the SLO burn-rate engine.

Contracts pinned here:

  - sampling is decided ONCE per trace id (deterministic hash),
    inherited by every child — in-process and across the RPC envelope
    — so no partial traces exist at any rate; sample=0.0 installs
    nothing (wire- and cost-identical to flag-off); sample=1.0 is
    today's behavior; a seeded tracer samples the same ids run to run;
  - the CPU-backend DeviceTraceSession joins >= 1 annotated device
    slice to a host span by the annotation-embedded trace id, feeds
    per-kernel device-seconds and the step breakdown into the
    registry, and merges device tracks into the chrome trace;
  - the SLO engine fires AND clears a multi-window burn-rate alert,
    records both transitions in the flight recorder, degrades
    /healthz while firing, and serves /sloz.
"""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.observability import (device_trace, flight_recorder,
                                      metrics, slo, tracing)
from paddle_tpu.observability.export import MetricsHTTPServer


def _tools_mod(name):
    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def tracer():
    t = tracing.start_tracing()
    t.clear()
    t.sample_rate = 1.0
    try:
        yield t
    finally:
        tracing.stop_tracing()


# ---------------------------------------------------------------------------
# head-based sampling
# ---------------------------------------------------------------------------

def test_sample_zero_installs_nothing_wire_identical_to_off():
    """Rate 0.0 leaves the module global None — every span site stays
    at the one-conditional disabled cost (the bench-loop assertion in
    test_observability covers that exact state) and the RPC payload
    carries no trace envelope."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    assert tracing.start_tracing(sample=0.0) is None
    assert tracing.maybe_tracer() is None
    assert tracing.sample_rate() == 0.0
    seen = []
    srv = RPCServer("127.0.0.1:0").start()
    srv.register_handler("probe", lambda p: seen.append(p) or "ok")
    client = RPCClient()
    try:
        client.call(srv.endpoint, "probe", ("a", 1), retries=0)
    finally:
        client.close()
        srv.stop()
    assert seen == [("a", 1)]     # the exact legacy payload shape


def test_sample_one_is_todays_behavior(tracer):
    """Rate 1.0: every root sampled, envelope sent, server joined —
    bit-identical to the pre-sampling tracer."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    assert tracer.sample_rate == 1.0
    srv = RPCServer("127.0.0.1:0").start()
    srv.register_handler("echo", lambda p: p)
    client = RPCClient()
    try:
        assert client.call(srv.endpoint, "echo", 7, retries=0) == 7
    finally:
        client.close()
        srv.stop()
    cl = [s for s in tracer.spans() if s.name == "rpc.client:echo"][0]
    sv = [s for s in tracer.spans() if s.name == "rpc.server:echo"][0]
    assert cl.sampled and sv.trace_id == cl.trace_id
    assert sv.parent_id == cl.span_id
    assert tracer.dropped_roots == 0


def test_sampling_deterministic_and_seed_replayable():
    """The verdict is a pure function of the trace id; a seeded
    tracer re-generates the same id stream, so two runs with the same
    seed sample the same ids."""
    ids = {}
    for run in range(2):
        t = tracing.Tracer(capacity=64, sample=0.5, seed=1234)
        ids[run] = [t.start_span("root%d" % i).end().trace_id
                    for i in range(32)]
    assert ids[0] == ids[1]
    t = tracing.Tracer(capacity=64, sample=0.5)
    verdicts = [t._verdict(tid) for tid in ids[0]]
    assert verdicts == [t._verdict(tid) for tid in ids[0]]
    assert any(verdicts) and not all(verdicts)   # both sides at 0.5
    # different seed -> different stream (the seed is load-bearing)
    t2 = tracing.Tracer(capacity=64, sample=0.5, seed=99)
    assert [t2.start_span("r").end().trace_id
            for _ in range(32)] != ids[0]


def test_sampling_inherited_no_partial_traces(tracer):
    """At rate 0.5: every recorded trace is COMPLETE (root + children
    + envelope-joined server span), dropped roots leave nothing, and
    the per-path counters sum to offered."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    tracer.sample_rate = 0.5
    reg = metrics.registry().get("paddle_tpu_trace_traces_total")

    def counts():
        if reg is None:
            return 0.0, 0.0
        return (reg.value(path="work", verdict="sampled"),
                reg.value(path="work", verdict="dropped"))

    s0, d0 = counts()
    srv = RPCServer("127.0.0.1:0").start()
    srv.register_handler("step", lambda p: p)
    client = RPCClient()
    offered = 40
    root_verdicts = []
    try:
        for i in range(offered):
            with tracer.span("work", i=i) as root:
                with tracer.span("child"):
                    # the mid-trace SERVER-side child: must inherit
                    # the parent's verdict through the envelope
                    client.call(srv.endpoint, "step", i, retries=0)
            root_verdicts.append((root.trace_id, root.sampled))
    finally:
        client.close()
        srv.stop()
    reg = metrics.registry().get("paddle_tpu_trace_traces_total")
    s1, d1 = counts()
    n_sampled = sum(1 for _, v in root_verdicts if v)
    assert int(s1 - s0) == n_sampled
    assert int(s1 - s0) + int(d1 - d0) == offered
    assert 0 < n_sampled < offered
    by_trace = {}
    for s in tracer.spans():
        by_trace.setdefault(s.trace_id, set()).add(s.name)
    for tid, sampled in root_verdicts:
        if sampled:
            assert by_trace.get(tid) == {
                "work", "child", "rpc.client:step",
                "rpc.server:step"}, by_trace.get(tid)
        else:
            assert tid not in by_trace    # NOTHING from dropped traces


def test_unsampled_trace_sends_no_envelope(tracer):
    """A dropped trace's RPC leaves the wire byte-identical to
    flag-off: the handler sees the bare payload and the server records
    no span for it."""
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    tracer.sample_rate = 0.5
    srv = RPCServer("127.0.0.1:0").start()
    seen = []
    srv.register_handler("probe", lambda p: seen.append(p) or "ok")
    client = RPCClient()
    try:
        # hunt a dropped root (P(miss in 64) = 2^-64)
        for i in range(64):
            with tracer.span("hunt") as root:
                if not root.sampled:
                    client.call(srv.endpoint, "probe", ("raw", i),
                                retries=0)
                    dropped_tid = root.trace_id
                    break
        else:
            pytest.fail("no dropped root in 64 draws at rate 0.5")
    finally:
        client.close()
        srv.stop()
    assert seen == [("raw", i)]          # bare payload, no envelope
    assert all(s.trace_id != dropped_tid for s in tracer.spans())


def test_serving_config_trace_sample_applies_at_start(tmp_path):
    from paddle_tpu import inference, serving

    with pytest.raises(ValueError):
        serving.ServingConfig(trace_sample=1.5)
    t = tracing.start_tracing()
    try:
        x = layers.data("x", shape=[4], dtype="float32")
        pred = layers.fc(x, size=1)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        d = str(tmp_path / "m")
        fluid.io.save_inference_model(d, ["x"], [pred], exe)
        srv = serving.InferenceServer(
            lambda i: inference.create_predictor(inference.Config(d)),
            serving.ServingConfig(n_replicas=1, max_batch=2,
                                  trace_sample=0.25)).start()
        try:
            assert tracing.sample_rate() == 0.25
        finally:
            srv.stop()
        # trace_sample=0.0 uninstalls — back to the flag-off state
        srv0 = serving.InferenceServer(
            lambda i: inference.create_predictor(inference.Config(d)),
            serving.ServingConfig(n_replicas=1, max_batch=2,
                                  trace_sample=0.0)).start()
        try:
            assert tracing.maybe_tracer() is None
        finally:
            srv0.stop()
    finally:
        tracing.stop_tracing()


# ---------------------------------------------------------------------------
# device-time attribution
# ---------------------------------------------------------------------------

def test_annotation_name_grammar_roundtrip():
    name = device_trace.annotation_name("flash_attention", "abc123")
    assert ":" not in name                # the truncation hazard
    assert device_trace.parse_annotation(name) == ("flash_attention",
                                                   "abc123")
    assert device_trace.parse_annotation(
        device_trace.annotation_name("k")) == ("k", None)
    assert device_trace.parse_annotation("not_ours") is None
    assert device_trace.parse_annotation("pt#") is None
    # tracing off -> the null context (one module-global check)
    assert tracing.maybe_tracer() is None
    assert device_trace.annotate("flash_attention") is \
        device_trace._NULL


def test_device_trace_session_joins_host_span(tracer, tmp_path):
    """THE acceptance leg, chip-free: an executor step inside a
    capture window yields >= 1 device slice joined to the host span's
    trace id; per-kernel seconds and the step breakdown land in the
    registry; the merged chrome trace carries the id on a device
    lane."""
    reg = metrics.registry()
    k0 = reg.get("paddle_tpu_device_kernel_seconds_total")
    k0 = k0.total() if k0 else 0.0
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = layers.data("x", shape=[8], dtype="float32")
        out = layers.mean(layers.fc(x, size=8))
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        prog = fluid.CompiledProgram(fluid.default_main_program())
        sess = device_trace.DeviceTraceSession(
            str(tmp_path / "devtrace"))
        sess.start()
        with tracer.span("request") as root:
            for _ in range(2):
                exe.run(prog,
                        feed={"x": np.ones((2, 8), np.float32)},
                        fetch_list=[out])
        sess.stop()
    assert any(a["kernel"] == "executor.step"
               and a["trace_id"] == root.trace_id
               for a in sess.annotations)
    joined = [j for j in sess.joined
              if j["trace_id"] == root.trace_id]
    assert joined, "no device slice joined the host trace id"
    ksec = sess.kernel_seconds()
    assert ksec.get("executor.step", 0.0) > 0.0
    bd = sess.step_breakdown()
    assert bd["total"] > 0.0 and bd["compute"] > 0.0
    assert bd["total"] >= bd["compute"] + bd["transfer"] - 1e-9
    kreg = reg.get("paddle_tpu_device_kernel_seconds_total")
    assert kreg is not None and kreg.total() > k0
    sreg = reg.get("paddle_tpu_device_step_seconds_total")
    assert sreg.value(component="compute") > 0.0
    # merged chrome trace: a device slice carries the host trace id
    p = str(tmp_path / "merged.json")
    sess.export_merged(p, tracer=tracer)
    doc = json.load(open(p))
    host = [e for e in doc["traceEvents"]
            if e.get("name") == "request"]
    assert host and host[0]["args"]["trace_id"] == root.trace_id
    dev = [e for e in doc["traceEvents"]
           if e.get("pid", 0) >= device_trace.DeviceTraceSession.
           _PID_OFFSET
           and e.get("args", {}).get("trace_id") == root.trace_id
           and e.get("ph") == "X"]
    assert dev, "merged trace has no device slice under the trace id"


def test_kernel_entry_annotations_unsampled_and_off_paths(tracer):
    """Kernel entries run unchanged with tracing off, and an UNSAMPLED
    trace emits no runtime annotation (head sampling reaches the
    device plane); inside a jit trace the annotate site returns the
    null context (the pallas_call's own name= names the kernel), never
    a TraceAnnotation with a frozen id."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas_kernels import flash_attention

    q = jnp.ones((1, 2, 8, 4), jnp.float32)
    with tracer.span("req"):
        out = flash_attention(q, q, q, impl="xla")
    tracing.stop_tracing()
    out_off = flash_attention(q, q, q, impl="xla")   # tracer None path
    assert np.array_equal(np.asarray(out), np.asarray(out_off))
    t = tracing.start_tracing()
    t.sample_rate = 0.0   # every trace dropped (rate kept on tracer to
    #                       exercise the annotate gate, not the None path)
    with t.span("req2"):
        assert device_trace.annotate("flash_attention") is \
            device_trace._NULL

    t.sample_rate = 1.0
    inside = {}

    def f(a):
        inside["ctx"] = device_trace.annotate("flash_attention")
        return a * 2

    jax.jit(f)(jnp.ones((2,)))
    assert not isinstance(inside["ctx"],
                          jax.profiler.TraceAnnotation)


# ---------------------------------------------------------------------------
# SLO engine
# ---------------------------------------------------------------------------

def _counter_slo(reg_name="paddle_tpu_t_slo_reqs_total", **kw):
    return slo.SLO("t_availability", 0.9, 60.0, source={
        "kind": "counter_ratio", "metric": reg_name,
        "good": [{"outcome": "ok"}],
        "total": [{"outcome": "ok"}, {"outcome": "shed"}]}, **kw)


def test_slo_validation_and_histogram_source():
    with pytest.raises(ValueError):
        slo.SLO("bad", 1.5, 60.0, source={"kind": "counter_ratio",
                                          "metric": "m", "good": [],
                                          "total": []})
    with pytest.raises(ValueError):
        slo.SLO("bad", 0.9, 60.0, source={"kind": "nope"})
    r = metrics.MetricsRegistry()
    h = r.histogram("paddle_tpu_t_lat_seconds")
    for v in (0.01, 0.02, 0.05, 1.0):
        h.observe(v)
    s = slo.SLO("lat", 0.9, 60.0, source={
        "kind": "histogram_under",
        "metric": "paddle_tpu_t_lat_seconds", "threshold_s": 0.25})
    good, total = s.sample(r)
    assert total == 4 and good == 3      # the 1.0s observation is bad


def test_slo_burn_rate_fires_and_clears_with_flight_events():
    """Seeded overload shape, synthetic: a shed-heavy phase fires the
    multi-window alert, a recovery phase clears it; both transitions
    land in the flight recorder; gauges track."""
    r = metrics.MetricsRegistry()
    c = r.counter("paddle_tpu_t_slo_reqs_total")
    s = _counter_slo(fast_fraction=0.25, burn_alert=2.0)
    mon = slo.SLOMonitor(slos=[s], registry=r)
    fr = flight_recorder.recorder()
    fr.clear()
    t = 1000.0
    ev = mon.observe(now=t)["t_availability"]
    assert ev["burn_rate_slow"] is None and not ev["firing"]
    # healthy phase: 100 ok over 60s
    for _ in range(6):
        t += 10.0
        c.inc(20, outcome="ok")
        ev = mon.observe(now=t)["t_availability"]
    assert ev["attained"] == 1.0 and not ev["firing"]
    # overload2x phase: half of everything shed -> error 0.5, budget
    # 0.1 -> burn 5 >= 2 in BOTH windows
    for _ in range(8):
        t += 10.0
        c.inc(10, outcome="ok")
        c.inc(10, outcome="shed")
        ev = mon.observe(now=t)["t_availability"]
    assert ev["firing"], ev
    assert ev["burn_rate_fast"] >= 2.0 and ev["burn_rate_slow"] >= 2.0
    reg = metrics.registry()
    assert reg.get("paddle_tpu_slo_alert_firing").value(
        slo="t_availability") == 1.0
    # recovery: the fast window clears first (the multi-window point:
    # either window under threshold un-pages)
    for _ in range(12):
        t += 10.0
        c.inc(20, outcome="ok")
        ev = mon.observe(now=t)["t_availability"]
    assert not ev["firing"], ev
    chain = [(e["category"], e["event"]) for e in fr.events()]
    i_fire = chain.index(("slo", "alert_firing"))
    i_clear = chain.index(("slo", "alert_cleared"))
    assert i_fire < i_clear
    assert reg.get("paddle_tpu_slo_alert_firing").value(
        slo="t_availability") == 0.0
    # the transitions round-trip through a dump — the post-mortem a
    # pager page points at shows WHY it fired
    path = fr.dump(reason="slo_test", announce=False)
    assert path is not None
    dumped = [(e["category"], e["event"])
              for e in flight_recorder.load_dump(path)["events"]]
    assert ("slo", "alert_firing") in dumped
    assert ("slo", "alert_cleared") in dumped


def test_sloz_endpoint_and_healthz_degrades():
    """/sloz parses; /healthz flips to degraded while an alert fires
    and back to the EXACT legacy ok shape when it clears."""
    import urllib.request

    r = metrics.MetricsRegistry()
    c = r.counter("paddle_tpu_t_slo_reqs_total")
    mon = slo.SLOMonitor(slos=[_counter_slo(fast_fraction=0.25,
                                            burn_alert=2.0)],
                         registry=r)
    prev = slo._monitor
    slo.install(mon)
    try:
        with MetricsHTTPServer(port=0, registry=r) as srv:
            doc = json.loads(urllib.request.urlopen(
                srv.url + "/sloz", timeout=5).read())
            assert doc["firing"] == []
            (spec,) = doc["slos"]
            assert spec["name"] == "t_availability"
            assert spec["objective"] == 0.9
            health = json.loads(urllib.request.urlopen(
                srv.url + "/healthz", timeout=5).read())
            assert health == {"status": "ok"}
            # burn the budget hard and re-probe
            c.inc(5, outcome="ok")
            mon.observe()
            time.sleep(0.02)
            c.inc(100, outcome="shed")
            mon.observe()
            health = json.loads(urllib.request.urlopen(
                srv.url + "/healthz", timeout=5).read())
            assert health["status"] == "degraded"
            assert health["alerts"] == ["t_availability"]
    finally:
        slo.install(prev)


def test_serving_request_latency_histogram_feeds_slo(tmp_path):
    """The admission layer observes per-request latency — the
    p99-vs-deadline SLO's source — including typed-error outcomes."""
    from paddle_tpu import inference, serving

    reg = metrics.registry()
    h0 = reg.get("paddle_tpu_serving_request_seconds")
    n0 = 0 if h0 is None else sum(summ["count"]
                                  for _, summ in h0.items())
    x = layers.data("x", shape=[4], dtype="float32")
    pred = layers.fc(x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "m")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    srv = serving.InferenceServer(
        lambda i: inference.create_predictor(inference.Config(d)),
        serving.ServingConfig(n_replicas=1, max_batch=2)).start()
    try:
        srv.infer({"x": np.zeros((1, 4), np.float32)},
                  deadline_s=30.0, timeout=30.0)
    finally:
        srv.stop()
    h = reg.get("paddle_tpu_serving_request_seconds")
    assert h is not None
    n1 = sum(summ["count"] for _, summ in h.items())
    assert n1 > n0
    good, total = slo.serving_latency(deadline_s=30.0).sample(reg)
    assert total >= 1 and good >= 1


def test_slo_report_tool_one_line(tmp_path, capsys):
    sr = _tools_mod("slo_report")
    line = {"metric": "serving_goodput", "mode": "overload2x",
            "offered_qps": 200.0, "goodput_qps": 90.0,
            "capacity_qps": 100.0, "p50_ms": 3.0, "p99_ms": 40.0,
            "deadline_ms": 250.0, "seed": 7,
            "slo": {"serving_availability": {
                "attained": 0.5, "target": 0.99, "burn_rate": 50.0,
                "firing": True}}}
    p = str(tmp_path / "load.json")
    with open(p, "w") as f:
        f.write(json.dumps(line) + "\n")
    rc = sr.main(["--inputs", p])
    out = capsys.readouterr().out.splitlines()
    assert rc == 0 and len(out) == 1
    rec = json.loads(out[0])
    assert rec["metric"] == "serving_qps_slo"
    assert rec["value"] == 90.0 and rec["ok"] is True
    assert rec["rows"][0]["slo"]["serving_availability"][
        "burn_rate"] == 50.0
    # a row missing the availability objective fails the gate
    with open(p, "w") as f:
        f.write(json.dumps(dict(line, slo={})) + "\n")
    assert sr.main(["--inputs", p]) == 1
    capsys.readouterr()


# ---------------------------------------------------------------------------
# profiler device path (satellite)
# ---------------------------------------------------------------------------

def test_profiler_tracer_option_device_path(tmp_path):
    """start_profiler(tracer_option=...) opens the device session
    bound to the active span ctx; stop_profiler routes through
    DeviceTraceSession so the Fluid surface gets attribution for
    free, and the chrome export carries the device tracks."""
    import jax.numpy as jnp

    from paddle_tpu import profiler

    reg = metrics.registry()
    k0 = reg.get("paddle_tpu_device_kernel_seconds_total")
    k0 = k0.value(kernel="profiler") if k0 else 0.0
    t = tracing.start_tracing()
    t.clear()
    try:
        with t.span("request") as root:
            profiler.start_profiler(tracer_option="Default")
            with profiler.RecordEvent("matmul"):
                a = jnp.ones((128, 128))
                (a @ a).block_until_ready()
            p = str(tmp_path / "prof.json")
            sess = profiler.stop_profiler(profile_path=p)
    finally:
        tracing.stop_tracing()
    assert sess is not None
    assert any(a["kernel"] == "profiler"
               and a["trace_id"] == root.trace_id
               for a in sess.annotations)
    joined = [j for j in sess.joined
              if j["trace_id"] == root.trace_id]
    assert joined, "no device slice joined the bound span ctx"
    k1 = reg.get("paddle_tpu_device_kernel_seconds_total").value(
        kernel="profiler")
    assert k1 > k0
    doc = json.load(open(p))
    names = {e["name"] for e in doc["traceEvents"]}
    assert "matmul" in names             # host span survived the merge
    assert any(e.get("pid", 0) >= device_trace.DeviceTraceSession.
               _PID_OFFSET for e in doc["traceEvents"])


def test_profiler_without_tracer_option_unchanged(tmp_path):
    """The legacy no-device path: exact prior behavior (no session,
    plain host chrome export)."""
    from paddle_tpu import profiler

    profiler.start_profiler()
    with profiler.RecordEvent("opA"):
        pass
    p = str(tmp_path / "p.json")
    assert profiler.stop_profiler(profile_path=p) is None
    names = [e["name"] for e in json.load(open(p))["traceEvents"]]
    assert names.count("opA") == 1

"""Fleet-observability suite (ISSUE 12): cross-process collection,
metric exemplars, and tail-latency forensics.

Contracts pinned here:

  - exemplars exist exactly when head sampling does: a sampled active
    trace stamps the histogram bucket, an unsampled/absent one leaves
    the exposition BYTE-identical to PR 10; per-bucket reservoirs are
    bounded, including on the cardinality-overflow series; presence is
    deterministic under PADDLE_TPU_TRACE_SEED;
  - the exposition grammar checker accepts OpenMetrics exemplar syntax
    and rejects malformed exemplars (bad label pair, missing value,
    exemplar on a gauge sample);
  - the collector ingests pushes exactly once under a seeded
    faultinject plan dropping/closing them (frozen-seq retry +
    server-side dedup), marks silent processes stale instead of
    wedging, dedups dump references by path, and assembles
    cross-process traces in one store;
  - THE acceptance leg: a seeded 2x-overload serving run at sample
    0.5 leaves a p99-bucket exemplar whose trace id resolves in the
    collector to a COMPLETE cross-process trace (submit -> ... ->
    delivery incl. the envelope-joined server span from a subprocess),
    and tail_forensics --slowest attributes the aggregate dominantly
    to admission-queue wait with closing segment sums;
  - collector off + sample 0.0 sends zero new wire bytes (the server
    sees the exact legacy payload; no pusher exists).
"""

import importlib.util
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import inference, layers, serving
from paddle_tpu.distributed import faultinject
from paddle_tpu.distributed.faultinject import FaultPlan
from paddle_tpu.distributed.rpc import RPCClient, RPCServer
from paddle_tpu.observability import collector as obs_collector
from paddle_tpu.observability import metrics, slo, tracing
from paddle_tpu.observability.export import parse_prometheus_text


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


@pytest.fixture
def collector_server():
    c = obs_collector.CollectorServer("127.0.0.1:0")
    c.start()
    try:
        yield c
    finally:
        c.stop()


def _save_model(tmp_path, in_dim=8):
    x = layers.data("x", shape=[in_dim], dtype="float32")
    pred = layers.fc(x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [pred], exe)
    return d


# ---------------------------------------------------------------------------
# exemplars
# ---------------------------------------------------------------------------

def test_exemplar_only_with_sampled_trace_and_byte_identity():
    """No tracer / no active span / dropped trace => no exemplar, and
    the exposition + snapshot stay byte-identical to the pre-exemplar
    format.  A sampled active trace stamps the bucket."""
    assert tracing.maybe_tracer() is None
    r = metrics.MetricsRegistry()
    h = r.histogram("t_ex_seconds", "h", buckets=[0.1, 1.0, 10.0])
    h.observe(0.5)
    text_off = r.prometheus_text()
    snap_off = r.snapshot_line()
    assert "#" not in text_off.replace("# HELP", "").replace(
        "# TYPE", "")
    assert "exemplars" not in snap_off

    t = tracing.start_tracing(sample=1.0)
    try:
        # active span but a DIFFERENT registry instrument: ambient
        # pickup stamps the exemplar with the active trace id
        with t.span("req") as sp:
            h.observe(0.5)
        ex = h.exemplars()
        assert len(ex) == 1
        assert ex[0]["trace_id"] == sp.trace_id
        assert ex[0]["le"] == 1.0 and ex[0]["value"] == 0.5
        text_on = r.prometheus_text()
        assert ' # {trace_id="%s"} 0.5 ' % sp.trace_id in text_on
        # the grammar checker accepts its own exemplar output
        samples, exemplars = parse_prometheus_text(
            text_on, with_exemplars=True)
        assert len(exemplars) == 1
        assert exemplars[0]["exemplar_labels"]["trace_id"] == \
            sp.trace_id

        # an observation with NO active span records no new exemplar
        h.observe(5.0)
        assert len(h.exemplars()) == 1

        # a DROPPED trace records nothing (no partial observability)
        t.sample_rate = 0.0
        with t.span("dropped"):
            h.observe(0.05)
        assert len(h.exemplars()) == 1
    finally:
        tracing.stop_tracing()


def test_exemplar_reservoir_bounded_per_bucket():
    r = metrics.MetricsRegistry()
    h = r.histogram("t_ring_seconds", buckets=[1.0],
                    exemplar_capacity=2)
    t = tracing.start_tracing(sample=1.0)
    try:
        tids = []
        for i in range(8):
            with t.span("r%d" % i) as sp:
                h.observe(0.5)
                tids.append(sp.trace_id)
        ex = h.exemplars()
        assert len(ex) == 2                   # bounded
        assert [e["trace_id"] for e in ex] == tids[-2:]   # newest win
    finally:
        tracing.stop_tracing()


def test_exemplar_determinism_under_trace_seed():
    """Same seed => same trace-id stream => same sampling verdicts =>
    the SAME exemplar trace ids, run to run."""
    runs = []
    for _ in range(2):
        tracing.stop_tracing()
        t = tracing.start_tracing(sample=0.5, seed=424242)
        r = metrics.MetricsRegistry()
        h = r.histogram("t_det_seconds", buckets=[1.0],
                        exemplar_capacity=64)
        for i in range(24):
            with t.span("root"):
                h.observe(0.5)
        runs.append([e["trace_id"] for e in h.exemplars()])
        tracing.stop_tracing()
    assert runs[0] == runs[1]
    assert 0 < len(runs[0]) < 24      # both verdicts exercised


def test_exemplar_bounds_under_cardinality_overflow():
    """Past max_series the overflow series absorbs new label sets —
    its exemplar reservoir obeys the same per-bucket bound."""
    r = metrics.MetricsRegistry()
    h = r.histogram("t_ovf_seconds", buckets=[1.0], max_series=2,
                    exemplar_capacity=2)
    t = tracing.start_tracing(sample=1.0)
    try:
        for i in range(10):
            with t.span("r"):
                h.observe(0.5, shard=str(i))
        assert h.overflow_dropped > 0
        ovf = h.exemplars(overflow="true")
        assert 1 <= len(ovf) <= 2             # bounded reservoir
        for lbl, summ in h.items():
            assert len(summ.get("exemplars", [])) <= 2
    finally:
        tracing.stop_tracing()


def test_parse_prometheus_exemplar_accept_and_reject():
    base = ("# TYPE m histogram\n"
            'm_bucket{le="1"} 2%s\n'
            'm_bucket{le="+Inf"} 2\n'
            "m_sum 1.0\nm_count 2\n")
    # accepted: with and without timestamp
    for suffix in (' # {trace_id="abc"} 0.5 1700000000.5',
                   ' # {trace_id="abc"} 0.5'):
        samples, ex = parse_prometheus_text(base % suffix,
                                            with_exemplars=True)
        assert ex and ex[0]["value"] == 0.5
    # counters may carry exemplars too
    parse_prometheus_text(
        "# TYPE c counter\nc 3 # {trace_id=\"t\"} 1\n")
    # rejected: malformed label pair / missing value / unterminated /
    # exemplar on a gauge sample
    for bad in (' # {trace_id=} 0.5',
                ' # {trace_id="abc"}',
                ' # {trace_id="abc" 0.5',
                ' # 0.5'):
        with pytest.raises(ValueError):
            parse_prometheus_text(base % bad)
    with pytest.raises(ValueError, match="non-bucket"):
        parse_prometheus_text(
            '# TYPE g gauge\ng 1 # {trace_id="t"} 1\n')


def test_serving_request_histogram_carries_p99_exemplar(tracer,
                                                       tmp_path):
    """The admission latency histogram stamps the request's OWN trace
    id (the delivery thread has no ambient ctx — the explicit-exemplar
    path)."""
    d = _save_model(tmp_path)
    srv = serving.InferenceServer(
        lambda i: inference.create_predictor(inference.Config(d)),
        serving.ServingConfig(n_replicas=1, max_batch=4)).start()
    try:
        srv.infer({"x": np.zeros((1, 8), np.float32)},
                  deadline_s=30.0, timeout=30.0)
    finally:
        srv.stop()
    roots = [s for s in tracer.spans() if s.name == "serving.submit"]
    tid = roots[-1].trace_id
    h = metrics.registry().get("paddle_tpu_serving_request_seconds")
    ex = h.exemplars(outcome="ok")
    assert any(e["trace_id"] == tid for e in ex), (tid, ex)


# ---------------------------------------------------------------------------
# collector: ingest, loss, staleness, assembly
# ---------------------------------------------------------------------------

def _push(client, endpoint, process, seq, spans=(), metrics_snap=None,
          slo_evals=None, dumps=(), role="test"):
    return client.call(endpoint, obs_collector.MSG_PUSH, {
        "process": process, "role": role, "seq": seq,
        "spans": list(spans), "metrics": metrics_snap,
        "slo": slo_evals, "dumps": list(dumps), "ts": time.time()},
        retries=0)


def _span(tid, sid, parent=None, name="s", t0=0.0, t1=1.0):
    return {"name": name, "trace_id": tid, "span_id": sid,
            "parent_id": parent, "t0_us": t0, "t1_us": t1,
            "attrs": {}}


def test_collector_fleet_series_and_process_bound(collector_server):
    c = collector_server
    client = RPCClient()
    try:
        snap = {"m_total": {"type": "counter",
                            "series": [{"labels": {"k": "v"},
                                        "value": 2.0}]}}
        _push(client, c.endpoint, "p1", 1, metrics_snap=snap,
              role="serving")
        _push(client, c.endpoint, "p2", 1, metrics_snap=snap,
              role="pserver")
        fm = c.fleet_metrics()
        series = fm["m_total"]["series"]
        assert {(s["labels"]["process"], s["labels"]["role"])
                for s in series} == {("p1", "serving"),
                                     ("p2", "pserver")}
        assert all(s["labels"]["k"] == "v" for s in series)

        # bounded process cardinality: past max_processes new names
        # collapse into one overflow entry
        small = obs_collector.CollectorServer(
            "127.0.0.1:0", max_processes=2).start()
        try:
            for i in range(6):
                _push(client, small.endpoint, "proc%d" % i, 1)
            procs = small.snapshot()["processes"]
            assert len(procs) == 3            # 2 + overflow
            assert "overflow" in procs
        finally:
            small.stop()
    finally:
        client.close()


def test_collector_push_loss_exactly_once_and_stale(tmp_path):
    """Seeded faultinject plan over collector_push: drop (ingested,
    reply lost) then close (never ingested).  The pusher's frozen-seq
    retry + the collector's seq dedup land the span batch and the dump
    reference EXACTLY once; a silent process reads as stale; the
    collector never wedges."""
    c = obs_collector.CollectorServer("127.0.0.1:0",
                                      stale_after=0.3).start()
    tracing.stop_tracing()
    t = tracing.start_tracing(sample=1.0)
    dump = tmp_path / "flight_1_1_test.json"
    dump.write_text("{}")
    try:
        with t.span("only-trace"):
            pass
        plan = FaultPlan().on(obs_collector.MSG_PUSH, 0, "drop") \
                          .on(obs_collector.MSG_PUSH, 1, "close")
        with faultinject.installed(plan) as inj:
            p = obs_collector.CollectorPusher(
                c.endpoint, role="t", process="victim",
                interval_s=30.0, deadline=2.0)
            p.start()
            # patch the dump list through the payload: use the real
            # flight recorder announce path instead
            from paddle_tpu.observability import flight_recorder

            flight_recorder.recorder()._dump_paths.append(str(dump))
            assert not p.push_now()     # drop: landed, reply lost
            assert not p.push_now()     # close: never arrived
            assert p.push_now()         # same seq -> deduped ack
            assert p.push_now()         # next seq: no further spans
            assert len(inj.log) == 2
        snap = c.snapshot()
        victim = snap["processes"]["victim"]
        assert victim["span_count"] == 1      # exactly once
        assert [d["path"] for d in snap["dumps"]].count(str(dump)) \
            == 1                              # dump ref exactly once
        tid = c.trace_ids()[0]
        assert len(c.trace(tid)) == 1
        assert not victim["stale"]
        time.sleep(0.4)                       # past stale_after
        assert c.snapshot()["processes"]["victim"]["stale"]
        p.stop(final_push=False)
    finally:
        tracing.stop_tracing()
        c.stop()


def test_collector_trace_assembly_and_completeness(collector_server):
    """Spans of one trace arriving from two processes join in ONE
    store; completeness = every parent resolves (a missing batch keeps
    the trace incomplete until its retry lands)."""
    c = collector_server
    client = RPCClient()
    tid = "deadbeef00000001"
    try:
        _push(client, c.endpoint, "client-proc", 1,
              spans=[_span(tid, "1", None, "rpc.client:echo")])
        assert not c.trace_complete(tid) or \
            len(c.trace(tid)) == 1            # root only: complete
        _push(client, c.endpoint, "server-proc", 1,
              spans=[_span(tid, "s1", "1", "rpc.server:echo")])
        spans = c.trace(tid)
        assert len(spans) == 2
        assert {s["process"] for s in spans} == {"client-proc",
                                                 "server-proc"}
        assert c.trace_complete(tid)
        # an orphan child (its parent's push never landed) keeps the
        # trace INCOMPLETE — no partial trace passes for whole
        _push(client, c.endpoint, "server-proc", 2,
              spans=[_span(tid, "s2", "missing", "child")])
        assert not c.trace_complete(tid)
    finally:
        client.close()


def test_collector_varz_poll(collector_server):
    """Pservers stay collector-agnostic: the collector PULLS their
    registry snapshot over the existing varz RPC."""
    c = collector_server
    srv = RPCServer("127.0.0.1:0").start()
    srv.register_handler(
        "varz", lambda _=None: {"m_total": {
            "type": "counter",
            "series": [{"labels": {}, "value": 1.0}]}})
    try:
        name = c.poll_varz(srv.endpoint)
        assert name == "pserver@" + srv.endpoint
        snap = c.snapshot()
        assert snap["processes"][name]["role"] == "pserver"
        assert "m_total" in c.fleet_metrics()
        # a dead endpoint: None, no crash, nothing ingested
        assert c.poll_varz("127.0.0.1:1", deadline=0.3) is None
    finally:
        srv.stop()


def test_fleet_slo_rollup(collector_server):
    c = collector_server
    client = RPCClient()
    evals_a = {"serving_availability": {
        "objective": 0.99, "good": 90.0, "total": 100.0,
        "burn_rate_slow": 10.0, "firing": True}}
    evals_b = {"serving_availability": {
        "objective": 0.99, "good": 300.0, "total": 300.0,
        "burn_rate_slow": 0.0, "firing": False}}
    try:
        _push(client, c.endpoint, "a", 1, slo_evals=evals_a)
        _push(client, c.endpoint, "b", 1, slo_evals=evals_b)
        fleet = c.fleet_slo()["serving_availability"]
        assert fleet["attained"] == pytest.approx(390.0 / 400.0)
        assert fleet["burn_rate"] == pytest.approx(
            (10.0 * 100.0) / 400.0)
        assert fleet["firing"] is True
        assert fleet["processes"] == 2
    finally:
        client.close()


def test_wire_identity_collector_off_sample_zero(tmp_path):
    """Collector off + sampling 0.0: the server sees the exact legacy
    payload (no envelope, no push traffic) and no pusher exists on a
    started serving server."""
    assert tracing.start_tracing(sample=0.0) is None
    assert obs_collector.maybe_collector() is None
    seen = []
    srv = RPCServer("127.0.0.1:0").start()
    srv.register_handler("probe", lambda p: seen.append(p) or "ok")
    client = RPCClient()
    try:
        client.call(srv.endpoint, "probe", ("a", 1), retries=0)
    finally:
        client.close()
        srv.stop()
    assert seen == [("a", 1)]
    assert serving.ServingConfig().collector is None
    d = _save_model(tmp_path)
    isrv = serving.InferenceServer(
        lambda i: inference.create_predictor(inference.Config(d)),
        serving.ServingConfig(n_replicas=1)).start()
    try:
        assert isrv.collector_pusher is None
    finally:
        isrv.stop()


def test_collector_env_knob_reaches_configs(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_COLLECTOR", "127.0.0.1:9")
    assert serving.ServingConfig().collector == "127.0.0.1:9"
    assert serving.DecodeConfig().collector == "127.0.0.1:9"
    monkeypatch.delenv("PADDLE_TPU_COLLECTOR")
    assert serving.ServingConfig().collector is None


def test_trainer_step_boundary_push(collector_server, monkeypatch):
    """The executor step path pushes through the env-derived pusher —
    trainers join the fleet with zero code changes."""
    monkeypatch.setenv("PADDLE_TPU_COLLECTOR",
                       collector_server.endpoint)
    monkeypatch.setenv("PADDLE_TPU_COLLECTOR_PUSH_INTERVAL", "0.01")
    obs_collector.reset_env_pusher()
    try:
        x = layers.data("x", shape=[4], dtype="float32")
        pred = layers.fc(x, size=1)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"x": np.zeros((2, 4), np.float32)}
        deadline = time.monotonic() + 10.0
        found = False
        while time.monotonic() < deadline and not found:
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[pred])
            found = any(
                p["role"] == "trainer" for p in
                collector_server.snapshot()["processes"].values())
            time.sleep(0.02)
        assert found, collector_server.snapshot()["processes"]
    finally:
        obs_collector.reset_env_pusher()


# ---------------------------------------------------------------------------
# tail forensics
# ---------------------------------------------------------------------------

def _serving_trace(tid, adm_end=1000.0, batch_ts=51000.0,
                   formation=2000.0, rep0=53000.0, rep1=58000.0,
                   deliver=58500.0):
    """Synthetic one-request trace with known segment boundaries."""
    return [
        {"name": "serving.submit", "trace_id": tid, "span_id": "1",
         "parent_id": None, "t0_us": 0.0, "t1_us": adm_end + 10,
         "attrs": {}},
        {"name": "serving.admission", "trace_id": tid, "span_id": "2",
         "parent_id": "1", "t0_us": 10.0, "t1_us": adm_end,
         "attrs": {}},
        {"name": "serving.batch", "trace_id": tid, "span_id": "3",
         "parent_id": "2", "t0_us": batch_ts, "t1_us": batch_ts,
         "attrs": {"formation_us": formation}},
        {"name": "serving.replica", "trace_id": tid, "span_id": "4",
         "parent_id": "3", "t0_us": rep0, "t1_us": rep1,
         "attrs": {}},
        {"name": "predictor.run", "trace_id": tid, "span_id": "5",
         "parent_id": "4", "t0_us": rep0 + 100, "t1_us": rep1 - 100,
         "attrs": {}},
        {"name": "serving.deliver", "trace_id": tid, "span_id": "6",
         "parent_id": "4", "t0_us": deliver, "t1_us": deliver,
         "attrs": {"outcome": "ok"}},
    ]


def test_forensics_decompose_known_segments():
    tf = _tools_mod("tail_forensics")
    d = tf.decompose_trace(_serving_trace("t1"))
    seg = d["segments_us"]
    assert seg["admission_wait"] == 48000.0       # 50000 gap - 2000
    assert seg["batch_formation"] == 2000.0
    assert seg["replica_queue"] == 2000.0
    assert seg["device_compute"] == 4800.0        # predictor.run span
    assert seg["device_host_gap"] == 200.0
    assert seg["delivery"] == 500.0
    assert d["wall_us"] == 57500.0
    assert abs(sum(seg.values()) - d["wall_us"]) < 1e-6
    assert d["closure_ok"] and d["dominant"] == "admission_wait"
    assert d["outcome"] == "ok"

    # device breakdown joined by trace id overrides the span estimate
    d2 = tf.decompose_trace(
        _serving_trace("t1"),
        device_index={"t1": {"compute_us": 3000.0,
                             "transfer_us": 1000.0}})
    seg2 = d2["segments_us"]
    assert seg2["device_compute"] == 3000.0
    assert seg2["device_transfer"] == 1000.0
    assert seg2["device_host_gap"] == 1000.0
    assert d2["device_joined"]

    # an incomplete stage chain is skipped, not guessed at
    assert tf.decompose_trace(_serving_trace("t2")[:3]) is None


def test_forensics_aggregate_slowest_and_inputs(tmp_path):
    tf = _tools_mod("tail_forensics")
    traces = {
        "fast": _serving_trace("fast", batch_ts=2000.0,
                               formation=500.0, rep0=2500.0,
                               rep1=7000.0, deliver=7100.0),
        "slow": _serving_trace("slow"),
        "broken": _serving_trace("broken")[:2],
    }
    decomps, skipped = tf.slowest(traces, 1)
    assert skipped == 1
    assert len(decomps) == 1 and decomps[0]["trace_id"] == "slow"
    agg = tf.aggregate(decomps)
    assert agg["dominant"] == "admission_wait"
    assert agg["per_trace_dominant"] == {"admission_wait": 1}

    # input formats: spans file and collector dump round-trip
    spans_file = tmp_path / "spans.json"
    spans_file.write_text(json.dumps(
        {"spans": [s for t in traces.values() for s in t]}))
    assert set(tf.load_traces(str(spans_file))) == set(traces)
    dump_file = tmp_path / "fleet.json"
    dump_file.write_text(json.dumps({"traces": traces}))
    assert set(tf.load_traces(str(dump_file))) == set(traces)


# ---------------------------------------------------------------------------
# THE acceptance leg (slow): overload + exemplar -> collector ->
# forensics
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_overload_exemplar_resolves_in_collector_and_forensics(
        tmp_path, monkeypatch):
    """ISSUE 12 acceptance: under a seeded 2x-overload run with
    tracing sampled at 0.5, the p99 bucket of
    paddle_tpu_serving_request_seconds carries an exemplar whose trace
    id resolves in the collector to a COMPLETE cross-process trace
    (submit -> ... -> delivery including the envelope-joined server
    span from a second process), and tail_forensics --slowest 5
    attributes the aggregate dominantly to admission-queue wait."""
    tf = _tools_mod("tail_forensics")
    coll = obs_collector.CollectorServer("127.0.0.1:0").start()
    # the second PROCESS: an rpc echo server with tracing on and its
    # own pusher — its rpc.server spans reach the collector from a
    # different process than ours
    child_src = (
        "import os, sys\n"
        "os.environ['PADDLE_TPU_TRACING'] = '1'\n"
        "from paddle_tpu.observability import collector, tracing\n"
        "from paddle_tpu.distributed.rpc import RPCServer\n"
        "tracing.start_tracing(sample=1.0)\n"
        "srv = RPCServer('127.0.0.1:0').start()\n"
        "srv.register_handler('echo', lambda p: p)\n"
        "p = collector.CollectorPusher(%r, role='pserver',\n"
        "                              interval_s=0.1).start()\n"
        "print('EP ' + srv.endpoint, flush=True)\n"
        "sys.stdin.read()\n"
        "p.stop(final_push=True)\n"
        "srv.stop()\n" % coll.endpoint)
    child = subprocess.Popen(
        [sys.executable, "-c", child_src],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.DEVNULL,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    tracing.stop_tracing()
    monkeypatch.setenv("PADDLE_TPU_TRACE_SEED", "7")
    tracer = tracing.start_tracing(sample=0.5, seed=7)
    rpc_client = RPCClient()
    try:
        child_ep = child.stdout.readline().decode().strip()[3:]

        d = _save_model(tmp_path)

        class RPCCallingPredictor:
            """Delegating predictor whose run() first calls the
            second process under the ACTIVE (replica) span — the
            request trace therefore includes an envelope-joined
            rpc.server span from another process."""

            def __init__(self, inner):
                self._inner = inner

            def run(self, feeds):
                rpc_client.call(child_ep, "echo", "x", retries=0)
                return self._inner.run(feeds)

            def __getattr__(self, name):
                return getattr(self._inner, name)

        capacity = 24
        srv = serving.InferenceServer(
            lambda i: RPCCallingPredictor(
                inference.create_predictor(inference.Config(d))),
            serving.ServingConfig(
                n_replicas=1, max_batch=1,
                queue_capacity=capacity, default_deadline_s=60.0,
                max_wait_s=0.001)).start()
        feeds = {"x": np.zeros((1, 8), np.float32)}
        try:
            srv.infer(feeds, deadline_s=60.0, timeout=60.0)  # warm
            tracer.clear()
            t_end = time.monotonic() + 1.5
            n_ok = 0
            while time.monotonic() < t_end:
                futures = []
                for _ in range(capacity):    # overload: fill queue
                    try:
                        futures.append(srv.submit(feeds))
                    except serving.ServingError:
                        break
                for f in futures:
                    f.result(timeout=120.0)
                    n_ok += 1
        finally:
            srv.stop()
        assert n_ok >= 3 * capacity

        # (1) the p99 bucket carries >= 1 exemplar
        h = metrics.registry().get(
            "paddle_tpu_serving_request_seconds")
        series = h.labels(outcome="ok")
        p99 = series.percentile(99)
        exemplars = h.exemplars(outcome="ok")
        assert exemplars
        top = max(exemplars,
                  key=lambda e: float("inf")
                  if e["le"] == "+Inf" else e["le"])
        top_le = float("inf") if top["le"] == "+Inf" else top["le"]
        assert top_le >= p99, (top, p99)

        # (2) the exemplar's trace resolves in the collector to a
        # COMPLETE cross-process trace
        child.stdin.close()
        child.wait(timeout=30)
        pusher = obs_collector.CollectorPusher(
            coll.endpoint, role="serving", interval_s=30.0)
        pusher.start()
        assert pusher.push_now()
        pusher.stop(final_push=False)
        tid = top["trace_id"]
        spans = coll.trace(tid)
        names = {s["name"] for s in spans}
        assert {"serving.submit", "serving.admission",
                "serving.batch", "serving.replica",
                "rpc.client:echo", "rpc.server:echo",
                "serving.deliver"} <= names, sorted(names)
        assert len({s["process"] for s in spans}) >= 2
        assert coll.trace_complete(tid)

        # (3) forensics: the aggregate p99 attribution names
        # admission-queue wait, segments close
        traces = tf.traces_from_spans(
            [tracing.span_to_dict(s) for s in tracer.spans()])
        decomps, _skipped = tf.slowest(traces, 5)
        assert len(decomps) == 5
        assert all(dc["closure_ok"] for dc in decomps)
        agg = tf.aggregate(decomps)
        assert agg["dominant"] == "admission_wait", agg
        assert agg["dominant_share_pct"] > 50.0
    finally:
        rpc_client.close()
        if child.poll() is None:
            child.kill()
        tracing.stop_tracing()
        coll.stop()


# ---------------------------------------------------------------------------
# satellites: slo_report fleet row, check_test_hung fleet section
# ---------------------------------------------------------------------------

def _fleet_doc():
    return {
        "processes": {
            "serving@host-1": {"role": "serving", "stale": False,
                               "last_push_age_s": 0.2, "pushes": 5,
                               "span_count": 12},
            "pserver@host-2": {"role": "pserver", "stale": True,
                               "last_push_age_s": 9.0, "pushes": 1,
                               "span_count": 0},
        },
        "slo_fleet": {"serving_availability": {
            "attained": 0.975, "target": 0.99, "burn_rate": 2.5,
            "firing": True, "good": 390.0, "total": 400.0,
            "processes": 2}},
        "n_traces": 3,
    }


def test_slo_report_fleet_row(tmp_path, capsys):
    sr = _tools_mod("slo_report")
    fleet = tmp_path / "fleet.json"
    fleet.write_text(json.dumps(_fleet_doc()))
    line = tmp_path / "load.json"
    line.write_text(json.dumps({
        "mode": "fixed", "offered_qps": 100.0, "goodput_qps": 99.0,
        "p50_ms": 3.0, "p99_ms": 9.0, "deadline_ms": 250.0,
        "seed": 7,
        "slo": {"serving_availability": {
            "attained": 0.99, "target": 0.99, "burn_rate": 0.5,
            "firing": False}}}) + "\n")
    rc = sr.main(["--inputs", str(line), "--fleet", str(fleet)])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and len(out) == 1
    rep = json.loads(out[0])
    assert rep["n_rows"] == 2
    fleet_row = rep["rows"][-1]
    assert fleet_row["mode"] == "fleet"
    assert fleet_row["slo"]["serving_availability"]["firing"] is True
    assert fleet_row["stale_processes"] == ["pserver@host-2"]
    assert rep["value"] == 99.0       # headline skips the fleet row


def test_check_test_hung_renders_fleet_section(tmp_path, capsys):
    cth = _tools_mod("check_test_hung")
    dump = tmp_path / "fleet_1_soak.json"
    dump.write_text(json.dumps(_fleet_doc()))
    log = tmp_path / "run.log"
    log.write_text(
        "tests/test_x.py::test_a PASSED\n"
        "COLLECTOR FLEET SNAPSHOT: %s (reason=chaos_soak, "
        "processes=2, traces=3)\n" % dump)
    recs = cth.scan_fleet_snapshots(log.read_text().splitlines())
    assert recs == [{"path": str(dump), "reason": "chaos_soak",
                     "processes": 2, "traces": 3}]
    lines = cth.render_fleet_snapshot(recs[0])
    text = "\n".join(lines)
    assert "STALE" in text and "pserver@host-2" in text
    assert "serving_availability" in text and "FIRING" in text
    import sys as _sys

    old_argv = _sys.argv
    _sys.argv = ["check_test_hung.py", str(log)]
    try:
        rc = cth.main()
    finally:
        _sys.argv = old_argv
    out = capsys.readouterr().out
    assert rc == 0 and "Fleet snapshot (collector dumps):" in out

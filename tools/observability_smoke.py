"""Seeded serving + decode observability smoke (ISSUE 9 + 10, ci.sh
gate).

With the ``tracing`` flag ON, runs one request through the
InferenceServer and one sequence through the DecodeServer, then
asserts the end-to-end trace contract:

  - serving: ONE trace id covers submit -> admission -> batch ->
    replica -> Predictor.run -> delivery;
  - decode:  ONE trace id covers submit -> join -> step -> retire ->
    delivery;
  - rpc: a pserver-side handler span joins the CLIENT's trace via the
    RPC envelope (socket transport, in-process server);
  - /metrics on the serving server parses under the in-tree prometheus
    grammar check (observability.export.parse_prometheus_text — no
    external dep) and carries the core instruments;
  - an explicit flight-recorder dump round-trips through its JSON file.

ISSUE 10 legs:

  - DEVICE TRACE (CPU-backend DeviceTraceSession smoke — jax.profiler
    works on CPU): a tracing-on serving request inside a capture
    window must yield >= 1 annotated device slice whose embedded trace
    id JOINS the host ``predictor.run`` span's trace, per-kernel
    device-seconds must land in the registry, and the merged chrome
    trace must carry a device slice under that id — host AND device
    under ONE trace id, chip-free;
  - SAMPLED TRACING at rate 0.5: sampled + dropped root counters must
    sum to the offered roots, every sampled trace must be COMPLETE
    (client + envelope-joined server span), and no dropped trace may
    leave any span in the ring;
  - /sloz parses and carries the declarative objectives.

ISSUE 12 legs:

  - EXEMPLARS: the serving request-latency histogram's exposition
    carries an OpenMetrics exemplar (`# {trace_id="..."} v ts`) whose
    trace id IS the request's trace, and the strict grammar checker
    accepts it;
  - COLLECTOR: a second PROCESS (subprocess RPC server + pusher) and
    this process both push span batches to an in-process
    CollectorServer; one trace id (client span here, envelope-joined
    server span there) must assemble COMPLETE in the collector's one
    store, and /fleetz must parse with both processes present.

stdout contract: EXACTLY ONE JSON line (the same driver/gate shape as
serving_load.py); progress goes to stderr.  Exit 0 iff every
assertion held.
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["PADDLE_TPU_TRACING"] = "1"


def _log(msg):
    print("# " + msg, file=sys.stderr)


def trace_names(tracer, root_name):
    """(trace_id, {span names}) for the trace rooted at `root_name`."""
    roots = [s for s in tracer.spans() if s.name == root_name]
    if not roots:
        return None, set()
    tid = roots[0].trace_id
    return tid, {s.name for s in tracer.spans() if s.trace_id == tid}


def main():
    import jax

    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu import inference, layers, serving
    from paddle_tpu.observability import flight_recorder, tracing
    from paddle_tpu.observability.export import parse_prometheus_text

    tracer = tracing.start_tracing()
    verdict = {"metric": "observability_smoke", "value": 1,
               "unit": "ok", "ok": False}
    checks = {}

    # -- serving leg --------------------------------------------------------
    _log("building tiny fc model")
    x = layers.data("x", shape=[8], dtype="float32")
    pred = layers.fc(x, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    mdir = os.path.join(tempfile.mkdtemp(), "model")
    fluid.io.save_inference_model(mdir, ["x"], [pred], exe)

    srv = serving.InferenceServer(
        lambda i: inference.create_predictor(inference.Config(mdir)),
        serving.ServingConfig(n_replicas=1, max_batch=4,
                              metrics_port=0)).start()
    try:
        srv.infer({"x": np.zeros((1, 8), np.float32)},
                  deadline_s=30.0, timeout=30.0)
        tid, names = trace_names(tracer, "serving.submit")
        need = {"serving.submit", "serving.admission", "serving.batch",
                "serving.replica", "predictor.run", "serving.deliver"}
        checks["serving_trace_ok"] = bool(tid) and need <= names
        verdict["serving_trace_id"] = tid
        verdict["serving_trace_spans"] = sorted(names)
        _log("serving trace %s: %s" % (tid, sorted(names)))

        # /metrics exposition parses under the in-tree grammar
        import urllib.request

        body = urllib.request.urlopen(
            srv.metrics_server.url + "/metrics", timeout=10).read()
        text = body.decode("utf-8")
        samples, exemplars = parse_prometheus_text(
            text, with_exemplars=True)
        sample_names = {n for n, _, _ in samples}
        core = {"paddle_tpu_admission_requests_total",
                "paddle_tpu_batcher_batches_total",
                "paddle_tpu_executor_step_seconds_count"}
        checks["prometheus_ok"] = core <= sample_names
        verdict["prom_samples"] = len(samples)
        _log("prometheus: %d samples, core present=%s"
             % (len(samples), core <= sample_names))
        # ISSUE 12: the request-latency histogram carries an
        # OpenMetrics exemplar naming the request's REAL trace id —
        # the strict grammar checker validates exemplar-bearing
        # exposition end to end
        req_ex = [e for e in exemplars
                  if e["name"] ==
                  "paddle_tpu_serving_request_seconds_bucket"]
        checks["exemplar_ok"] = bool(
            req_ex
            and any(e["exemplar_labels"].get("trace_id") == tid
                    for e in req_ex)
            and ' # {trace_id="' in text)
        verdict["exemplars"] = len(exemplars)
        _log("exemplars: %d total, serving-request exemplar joins "
             "trace %s: %s" % (len(exemplars), tid,
                               checks["exemplar_ok"]))
    finally:
        srv.stop()

    # -- decode leg ---------------------------------------------------------
    dsrv = serving.DecodeServer(config=serving.DecodeConfig(
        max_batch=2, max_new_tokens=4, page_size=16, num_pages=16,
        n_replicas=1)).start()
    try:
        dsrv.decode([2, 3, 4], deadline_s=30.0, timeout=30.0)
        dtid, dnames = trace_names(tracer, "decode.submit")
        dneed = {"decode.submit", "decode.join", "decode.step",
                 "decode.retire", "serving.deliver"}
        checks["decode_trace_ok"] = bool(dtid) and dneed <= dnames
        verdict["decode_trace_id"] = dtid
        verdict["decode_trace_spans"] = sorted(dnames)
        _log("decode trace %s: %s" % (dtid, sorted(dnames)))
    finally:
        dsrv.stop()

    # -- rpc envelope leg ---------------------------------------------------
    from paddle_tpu.distributed.rpc import RPCClient, RPCServer

    rsrv = RPCServer("127.0.0.1:0").start()
    rsrv.register_handler("ping", lambda p: p)
    client = RPCClient()
    try:
        client.call(rsrv.endpoint, "ping", "x", retries=0)
        cspans = [s for s in tracer.spans()
                  if s.name == "rpc.client:ping"]
        sspans = [s for s in tracer.spans()
                  if s.name == "rpc.server:ping"]
        checks["rpc_trace_joined"] = bool(
            cspans and sspans
            and sspans[-1].trace_id == cspans[-1].trace_id
            and sspans[-1].parent_id == cspans[-1].span_id)
        _log("rpc envelope joined=%s" % checks["rpc_trace_joined"])
    finally:
        client.close()
        rsrv.stop()

    # -- flight recorder round-trip ----------------------------------------
    flight_recorder.record("smoke", "probe", n=1)
    path = flight_recorder.dump(reason="smoke", announce=False)
    doc = flight_recorder.load_dump(path) if path else {}
    checks["flight_ok"] = bool(path) and any(
        ev.get("category") == "smoke" for ev in doc.get("events", []))
    verdict["flight_dump"] = path

    # -- ISSUE 10: device-trace leg (CPU-backend DeviceTraceSession) --------
    from paddle_tpu.observability import metrics as obs_metrics
    from paddle_tpu.observability.device_trace import \
        DeviceTraceSession

    _log("device-trace leg: serving request inside a capture window")
    tracer.clear()
    dsess = DeviceTraceSession(
        os.path.join(tempfile.mkdtemp(), "devtrace"))
    srv = serving.InferenceServer(
        lambda i: inference.create_predictor(inference.Config(mdir)),
        serving.ServingConfig(n_replicas=1, max_batch=4,
                              metrics_port=0)).start()
    try:
        dsess.start()
        srv.infer({"x": np.zeros((1, 8), np.float32)},
                  deadline_s=30.0, timeout=30.0)
        dsess.stop()

        pruns = [s for s in tracer.spans()
                 if s.name == "predictor.run"]
        ptid = pruns[-1].trace_id if pruns else None
        joined_tids = {j["trace_id"] for j in dsess.joined}
        ksec = dsess.kernel_seconds()
        reg = obs_metrics.registry().get(
            "paddle_tpu_device_kernel_seconds_total")
        merged = dsess.merged_chrome_trace(tracer)
        merged_dev = [
            e for e in merged["traceEvents"]
            if e.get("pid", 0) >= DeviceTraceSession._PID_OFFSET
            and e.get("args", {}).get("trace_id") == ptid]
        checks["device_trace_ok"] = bool(
            ptid and ptid in joined_tids and ksec
            and reg is not None and reg.total() > 0 and merged_dev)
        verdict["device_joined_slices"] = len(dsess.joined)
        verdict["device_kernel_seconds"] = {
            k: round(v, 6) for k, v in ksec.items()}
        verdict["device_step_breakdown"] = {
            k: round(v, 6) for k, v in dsess.step_breakdown().items()}
        _log("device trace: %d joined slices, kernels %s"
             % (len(dsess.joined), sorted(ksec)))

        # /sloz parses and carries the declarative objectives
        import urllib.request

        sloz = json.loads(urllib.request.urlopen(
            srv.metrics_server.url + "/sloz", timeout=10).read())
        names = {s.get("name") for s in sloz.get("slos", [])}
        checks["sloz_ok"] = "serving_availability" in names and \
            "firing" in sloz
        _log("sloz objectives: %s" % sorted(names))
    finally:
        srv.stop()

    # -- ISSUE 10: sampled-tracing leg (rate 0.5) ---------------------------
    _log("sampled-tracing leg: 40 rpc roots at rate 0.5")
    tracing.stop_tracing()
    t2 = tracing.start_tracing(sample=0.5)
    reg_traces = obs_metrics.registry().get(
        "paddle_tpu_trace_traces_total")

    def _counts():
        if reg_traces is None:
            return 0.0, 0.0
        return (reg_traces.value(path="rpc.client:ping",
                                 verdict="sampled"),
                reg_traces.value(path="rpc.client:ping",
                                 verdict="dropped"))

    s0, d0 = _counts()
    rsrv2 = RPCServer("127.0.0.1:0").start()
    rsrv2.register_handler("ping", lambda p: p)
    client2 = RPCClient()
    offered = 40
    try:
        for _ in range(offered):
            client2.call(rsrv2.endpoint, "ping", "x", retries=0)
    finally:
        client2.close()
        rsrv2.stop()
    reg_traces = obs_metrics.registry().get(
        "paddle_tpu_trace_traces_total")
    s1, d1 = _counts()
    n_sampled, n_dropped = int(s1 - s0), int(d1 - d0)
    roots = [s for s in t2.spans() if s.name == "rpc.client:ping"]
    complete = all(
        any(sv.name == "rpc.server:ping" and sv.trace_id == r.trace_id
            for sv in t2.spans())
        for r in roots)
    checks["sampling_ok"] = (
        n_sampled + n_dropped == offered
        and len(roots) == n_sampled
        and 0 < n_sampled < offered      # both verdicts exercised
        and complete)
    verdict["sampling"] = {"offered": offered, "sampled": n_sampled,
                           "dropped": n_dropped,
                           "complete_traces": complete}
    _log("sampling: %d sampled + %d dropped of %d, complete=%s"
         % (n_sampled, n_dropped, offered, complete))

    tracing.stop_tracing()

    # -- ISSUE 12: fleet-collector leg (two processes, one trace) -----------
    _log("collector leg: cross-process trace assembly + /fleetz")
    import subprocess
    import time as _time

    from paddle_tpu.observability import collector as obs_collector

    t3 = tracing.start_tracing(sample=1.0)
    t3.clear()
    coll = obs_collector.CollectorServer("127.0.0.1:0",
                                         http_port=0).start()
    child_src = (
        "import os, sys, time\n"
        "os.environ['PADDLE_TPU_TRACING'] = '1'\n"
        "from paddle_tpu.observability import collector, tracing\n"
        "from paddle_tpu.distributed.rpc import RPCServer\n"
        "tracing.start_tracing(sample=1.0)\n"
        "srv = RPCServer('127.0.0.1:0').start()\n"
        "srv.register_handler('echo', lambda p: p)\n"
        "p = collector.CollectorPusher(%r, role='pserver',\n"
        "                              interval_s=0.1).start()\n"
        "print('EP ' + srv.endpoint, flush=True)\n"
        "sys.stdin.read()\n"          # EOF = shut down
        "p.stop(final_push=True)\n"
        "srv.stop()\n" % coll.endpoint)
    child = subprocess.Popen(
        [sys.executable, "-c", child_src],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    try:
        ep_line = child.stdout.readline().decode().strip()
        assert ep_line.startswith("EP "), ep_line
        child_ep = ep_line[3:]
        from paddle_tpu.distributed.rpc import RPCClient

        client3 = RPCClient()
        try:
            with t3.span("fleet.probe") as root:
                client3.call(child_ep, "echo", "x", retries=0)
            ftid = root.trace_id
        finally:
            client3.close()
        child.stdin.close()         # child: final push + exit
        child.wait(timeout=30)
        pusher = obs_collector.CollectorPusher(
            coll.endpoint, role="serving", interval_s=0.1)
        pusher.start()
        deadline = _time.monotonic() + 10.0
        assembled = False
        while _time.monotonic() < deadline and not assembled:
            pusher.push_now()
            spans = coll.trace(ftid)
            names = {s["name"] for s in spans}
            procs = {s["process"] for s in spans}
            assembled = ({"fleet.probe", "rpc.client:echo",
                          "rpc.server:echo"} <= names
                         and len(procs) >= 2
                         and coll.trace_complete(ftid))
            _time.sleep(0.05)
        pusher.stop(final_push=False)
        # /fleetz parses and names both processes
        import urllib.request

        fleetz = json.loads(urllib.request.urlopen(
            coll.http_server.url + "/fleetz", timeout=10).read())
        roles = {p.get("role")
                 for p in fleetz.get("processes", {}).values()}
        checks["collector_ok"] = bool(
            assembled and {"pserver", "serving"} <= roles
            and fleetz.get("n_traces", 0) >= 1)
        verdict["fleet_trace_id"] = ftid
        verdict["fleet_processes"] = sorted(
            fleetz.get("processes", {}))
        _log("collector: trace %s assembled=%s from %s"
             % (ftid, assembled, sorted(procs) if spans else []))
    finally:
        if child.poll() is None:
            child.kill()
        coll.stop()
        tracing.stop_tracing()

    verdict.update(checks)
    verdict["ok"] = all(checks.values())
    verdict["value"] = int(verdict["ok"])
    print(json.dumps(verdict))
    return 0 if verdict["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())

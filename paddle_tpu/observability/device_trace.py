"""Device-time attribution: span the Pallas kernels and the compiled
step into the SAME trace ids the host spans carry (ISSUE 10; closes
ROADMAP observability item (b)).

Two halves:

**Annotation emission** — every Pallas kernel entry point
(``flash_attention``, ``flash_decode``, ``conv2d_epilogue``,
``conv2d_bn_act``, paged-KV ``append``) and every ``CompiledProgram``
step/compile wraps its work in ``annotate(kernel)``:

  - tracing flag OFF: the site is ONE module-global None check and
    the null context (the PR-9 disabled-cost contract);
  - at RUNTIME (``jax.core.trace_ctx.is_top_level()``): a
    ``jax.profiler.TraceAnnotation`` whose name carries the kernel and
    the ACTIVE trace id under the grammar ``pt#<kernel>#<trace_id>``
    (``pt#<kernel>#-`` when no trace is active; an UNSAMPLED trace
    emits nothing — head sampling reaches the device plane too).  The
    annotation name grammar deliberately avoids ``:`` — the profiler's
    chrome export truncates event names at the last colon and would
    eat the id;
  - while TRACING INTO a jit (kernel called from a larger compiled
    graph): nothing.  Every ``pl.pallas_call`` in ``ops/`` carries a
    fixed ``name="pt_<kernel>"`` that rides the HLO metadata into the
    compiled program whatever the flag says, so the device op is
    named always and the compiled module does not depend on
    ``tracing``; the per-request id comes from the surrounding
    runtime ``executor.step`` annotation (a trace id frozen at trace
    time would be a lie: the compile is cached across requests).

**DeviceTraceSession** — wraps ``jax.profiler.start_trace`` /
``stop_trace``, parses the emitted trace-event JSON
(``plugins/profile/<run>/*.trace.json.gz``), and joins device slices
back to host spans:

  join algorithm (docs/OBSERVABILITY.md): an event is an ANNOTATION
  when its name (or ``args.long_name``) parses under the ``pt#``
  grammar; an event is a DEVICE slice when it carries HLO metadata
  (``args.hlo_op`` / ``hlo_module``) or lives on a ``/device:*``
  process.  A device slice joins the INNERMOST annotation (same trace
  file) whose [ts, ts+dur] window contains the slice midpoint — on
  TPU the device lanes run on the device clock but xprof aligns them
  to the host timeline in the export; on CPU the XLA runtime threads
  share the host clock outright, which is what makes the CI smoke
  chip-free.

On ``stop()`` the session feeds the metrics registry:

  paddle_tpu_device_kernel_seconds_total{kernel=...}   joined device
      seconds per kernel (the per-kernel device-time attribution)
  paddle_tpu_device_step_seconds_total{component=...}  step-time
      breakdown over the ``executor.step`` windows: compute (joined
      HLO slices), transfer (copy/infeed/outfeed/h2d/d2h slices),
      host_gap (window minus both — dispatch, python, queueing)
  paddle_tpu_device_trace_slices_total{kind=...}       annotation /
      device / joined event counts (the join's own health)

and ``merged_chrome_trace(tracer)`` merges the device tracks into the
host tracer's chrome-trace events (the tools/timeline.py shape):
device processes land on offset pids with ``process_name`` metadata,
and every joined slice carries ``args.trace_id`` — one file shows the
request's host spans AND its device slices under one id.

Env knobs: ``PADDLE_TPU_DEVICE_TRACE_DIR`` (session log directory;
default a fresh tempdir per session).
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import tempfile

from paddle_tpu.observability import metrics as _metrics
from paddle_tpu.observability import tracing as _tracing

__all__ = ["annotate", "annotation_name", "parse_annotation",
           "DeviceTraceSession"]

ANNOTATION_PREFIX = "pt#"

_M_KERNEL_SECONDS = _metrics.counter(
    "paddle_tpu_device_kernel_seconds_total",
    "joined device seconds per annotated kernel/step", max_series=64)
_M_STEP_SECONDS = _metrics.counter(
    "paddle_tpu_device_step_seconds_total",
    "executor.step wall decomposition: compute / transfer / host_gap",
    max_series=8)
_M_SLICES = _metrics.counter(
    "paddle_tpu_device_trace_slices_total",
    "DeviceTraceSession parse/join counts, by kind", max_series=8)

_TRANSFER_MARKERS = ("copy", "transfer", "infeed", "outfeed",
                     "h2d", "d2h", "reshard", "memset")


def annotation_name(kernel, trace_id=None):
    """``pt#<kernel>#<trace_id>`` (grammar: no colons — the profiler
    export truncates names at the last ':')."""
    return "%s%s#%s" % (ANNOTATION_PREFIX, kernel, trace_id or "-")


def parse_annotation(name):
    """(kernel, trace_id | None) for a grammar-conformant name, else
    None."""
    if not name or not name.startswith(ANNOTATION_PREFIX):
        return None
    parts = name[len(ANNOTATION_PREFIX):].rsplit("#", 1)
    if len(parts) != 2 or not parts[0]:
        return None
    kernel, tid = parts
    return kernel, (None if tid in ("", "-") else tid)


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


def annotate(kernel):
    """The kernel-entry annotation site::

        with device_trace.annotate("flash_attention"):
            return _flash_lse(...)

    With tracing off this is one module-global check and the null
    context.  The Pallas kernel entries call it unguarded, on ONE call
    line: source locations ride the Mosaic payload, and a second call
    line under ``if tracing._tracer is not None`` would make the
    compiled module and its cache key depend on the flag.  Sites that
    trace nothing into a jit (executor.step, paged_kv_append) may keep
    the guard."""
    t = _tracing._tracer
    if t is None:
        return _NULL
    import jax

    if not jax.core.trace_ctx.is_top_level():
        # tracing INTO a jit: the pallas_call's own name= carries the
        # kernel identity into the HLO, flag or no flag; never bake a
        # trace id (or the flag) into a cached compile
        return _NULL
    ctx = _tracing.current()
    tid = ctx[0] if ctx is not None else None
    if tid is not None and not t._verdict(tid):
        return _NULL            # head sampling reaches the device plane
    return jax.profiler.TraceAnnotation(annotation_name(kernel, tid))


def session_annotation(kernel, trace_id=None):
    """An UNGATED runtime annotation (profiler.py's device session
    binds the active span ctx with this even when the ``tracing`` flag
    is off — the explicit start_profiler(tracer_option=...) request is
    its own opt-in)."""
    import jax

    return jax.profiler.TraceAnnotation(annotation_name(kernel,
                                                        trace_id))


def _union_us(intervals):
    """Total microseconds covered by a list of (start, end)."""
    if not intervals:
        return 0.0
    total = 0.0
    start = end = None
    for s, e in sorted(intervals):
        if start is None:
            start, end = s, e
        elif s > end:
            total += end - start
            start, end = s, e
        else:
            end = max(end, e)
    total += end - start
    return total


class DeviceTraceSession:
    """One jax.profiler capture window + the parse/join/attribute
    pass (module docstring).  Use as a context manager or
    start()/stop().  After stop():

      .annotations    [{kernel, trace_id, ts, dur, file}]
      .device_slices  [{name, ts, dur, pid, tid, file, transfer}]
      .joined         device slices + {kernel, trace_id} from the join
      .kernel_seconds()   {kernel: joined device seconds}
      .step_breakdown()   {total, compute, transfer, host_gap} seconds
      .merged_chrome_trace(tracer) / .export_merged(path, tracer)
    """

    def __init__(self, logdir=None, registry=None):
        self.logdir = logdir or \
            os.environ.get("PADDLE_TPU_DEVICE_TRACE_DIR") or \
            tempfile.mkdtemp(prefix="paddle_tpu_devtrace_")
        self._registry = registry   # None -> module instruments
        self.annotations = []
        self.device_slices = []
        self.joined = []
        self._meta = []             # raw metadata events for the merge
        self._started = False

    # -- lifecycle ----------------------------------------------------------
    def start(self):
        if not self._started:
            import jax

            os.makedirs(self.logdir, exist_ok=True)
            jax.profiler.start_trace(self.logdir)
            self._started = True
        return self

    def stop(self):
        """Stop the capture, parse the emitted trace, run the join,
        feed the registry.  Returns self (inspect the attributes)."""
        if self._started:
            import jax

            jax.profiler.stop_trace()
            self._started = False
        self._parse()
        self._join()
        self._feed_registry()
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # -- parse --------------------------------------------------------------
    def _trace_files(self):
        runs = sorted(glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*")))
        if not runs:
            return []
        # newest run dir only: a reused logdir keeps old sessions
        return sorted(glob.glob(os.path.join(runs[-1],
                                             "*.trace.json.gz")))

    def _parse(self):
        self.annotations, self.device_slices, self._meta = [], [], []
        device_pids = set()
        for path in self._trace_files():
            try:
                with gzip.open(path, "rt") as f:
                    doc = json.load(f)
            except (OSError, ValueError):
                continue
            events = doc.get("traceEvents", [])
            for ev in events:   # first pass: device-plane pids
                if ev.get("ph") == "M" and \
                        ev.get("name") == "process_name":
                    self._meta.append((path, ev))
                    pname = str(ev.get("args", {}).get("name", ""))
                    if pname.startswith("/device"):
                        device_pids.add((path, ev.get("pid")))
            for ev in events:
                if ev.get("ph") != "X":
                    continue
                args = ev.get("args") or {}
                name = str(args.get("long_name") or ev.get("name", ""))
                parsed = parse_annotation(name)
                if parsed is not None:
                    kernel, tid = parsed
                    self.annotations.append({
                        "kernel": kernel, "trace_id": tid,
                        "ts": float(ev.get("ts", 0.0)),
                        "dur": float(ev.get("dur", 0.0)),
                        "file": path})
                    continue
                if "hlo_op" in args or "hlo_module" in args or \
                        (path, ev.get("pid")) in device_pids:
                    lname = str(ev.get("name", "")).lower()
                    self.device_slices.append({
                        "name": ev.get("name", ""),
                        "ts": float(ev.get("ts", 0.0)),
                        "dur": float(ev.get("dur", 0.0)),
                        "pid": ev.get("pid"), "tid": ev.get("tid"),
                        "file": path,
                        "transfer": any(m in lname for m in
                                        _TRANSFER_MARKERS)})

    # -- join ---------------------------------------------------------------
    def _join(self):
        self.joined = []
        by_file: dict = {}
        for a in self.annotations:
            by_file.setdefault(a["file"], []).append(a)
        for s in self.device_slices:
            anns = by_file.get(s["file"])
            if not anns:
                continue
            mid = s["ts"] + s["dur"] / 2.0
            best = None
            for a in anns:
                if a["ts"] <= mid <= a["ts"] + a["dur"]:
                    if best is None or a["dur"] < best["dur"]:
                        best = a        # innermost enclosing window
            if best is not None:
                j = dict(s)
                j["kernel"] = best["kernel"]
                j["trace_id"] = best["trace_id"]
                self.joined.append(j)

    # -- attribution --------------------------------------------------------
    def kernel_seconds(self):
        """{kernel: joined device seconds} — the per-kernel
        device-time attribution (µs resolution from the trace)."""
        out: dict = {}
        for j in self.joined:
            out[j["kernel"]] = out.get(j["kernel"], 0.0) \
                + j["dur"] / 1e6
        return out

    def step_breakdown(self):
        """Step-time decomposition over the ``executor.step``
        annotation windows: compute (joined HLO slices), transfer
        (copy/infeed/... slices), host_gap (the rest of the window —
        python, dispatch, queueing).  All in seconds."""
        steps = [a for a in self.annotations
                 if a["kernel"] == "executor.step"]
        total = sum(a["dur"] for a in steps) / 1e6
        compute_iv, transfer_iv = [], []
        for j in self.joined:
            for a in steps:
                if a["file"] != j["file"]:
                    continue
                mid = j["ts"] + j["dur"] / 2.0
                if a["ts"] <= mid <= a["ts"] + a["dur"]:
                    iv = (j["ts"], j["ts"] + j["dur"])
                    (transfer_iv if j["transfer"]
                     else compute_iv).append(iv)
                    break
        compute = _union_us(compute_iv) / 1e6
        transfer = _union_us(transfer_iv) / 1e6
        return {"total": total, "compute": compute,
                "transfer": transfer,
                "host_gap": max(0.0, total - compute - transfer)}

    def _feed_registry(self):
        if self._registry is None:
            m_kernel, m_step, m_slices = (_M_KERNEL_SECONDS,
                                          _M_STEP_SECONDS, _M_SLICES)
        else:
            m_kernel = self._registry.counter(
                _M_KERNEL_SECONDS.name, _M_KERNEL_SECONDS.help)
            m_step = self._registry.counter(
                _M_STEP_SECONDS.name, _M_STEP_SECONDS.help)
            m_slices = self._registry.counter(
                _M_SLICES.name, _M_SLICES.help)
        for kernel, secs in self.kernel_seconds().items():
            m_kernel.inc(secs, kernel=kernel)
        bd = self.step_breakdown()
        for component in ("compute", "transfer", "host_gap"):
            if bd[component] > 0.0:
                m_step.inc(bd[component], component=component)
        m_slices.inc(len(self.annotations), kind="annotation")
        m_slices.inc(len(self.device_slices), kind="device")
        m_slices.inc(len(self.joined), kind="joined")

    # -- merge --------------------------------------------------------------
    _PID_OFFSET = 100000   # device lanes land past any real host pid

    def merged_chrome_trace(self, tracer=None):
        """One chrome-trace dict: the host tracer's span events (when
        given) + this session's annotation and device slices, device
        processes re-based onto offset pids with process_name
        metadata, joined slices carrying ``args.trace_id``/``kernel``.
        NOTE the two clock domains: host spans use perf_counter, the
        profiler its own epoch — lanes are per-process tracks, not a
        cross-domain alignment (same as tools/timeline.py's
        per-worker re-basing)."""
        events = list(tracer.chrome_events()) if tracer is not None \
            else []
        pid_map: dict = {}

        def mapped(path, pid):
            key = (path, pid)
            if key not in pid_map:
                pid_map[key] = self._PID_OFFSET + len(pid_map)
            return pid_map[key]

        join_key = {(j["file"], j["pid"], j["tid"], j["ts"]): j
                    for j in self.joined}
        for a in self.annotations:
            events.append({
                "name": annotation_name(a["kernel"], a["trace_id"]),
                "ph": "X", "ts": a["ts"], "dur": a["dur"],
                "pid": mapped(a["file"], "host_annotations"),
                "tid": 0,
                "args": {"kernel": a["kernel"],
                         "trace_id": a["trace_id"]}})
        for s in self.device_slices:
            args = {}
            j = join_key.get((s["file"], s["pid"], s["tid"], s["ts"]))
            if j is not None:
                args = {"trace_id": j["trace_id"],
                        "kernel": j["kernel"]}
            events.append({
                "name": s["name"], "ph": "X", "ts": s["ts"],
                "dur": s["dur"], "pid": mapped(s["file"], s["pid"]),
                "tid": s["tid"], "args": args})
        for (path, pid), new_pid in sorted(pid_map.items(),
                                           key=lambda kv: kv[1]):
            label = "device_annotations" if pid == "host_annotations" \
                else None
            if label is None:
                label = "device:%s" % pid
                for mpath, mev in self._meta:
                    if mpath == path and mev.get("pid") == pid:
                        label = "device:%s" % mev.get(
                            "args", {}).get("name", pid)
                        break
            events.append({"name": "process_name", "ph": "M",
                           "pid": new_pid, "tid": 0,
                           "args": {"name": label}})
        return {"traceEvents": events}

    def export_merged(self, path, tracer=None):
        with open(path, "w") as f:
            json.dump(self.merged_chrome_trace(tracer=tracer), f)
        return path

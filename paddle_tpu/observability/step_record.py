"""The step record: where a step's host time went, always on.

A bounded in-memory ring of small dicts, written where the work
happens and read by whoever runs in the same process (the benchmark's
``layer_metrics`` readers, a test, a debugger).  No flag, no
environment variable, no exporter, no registry instrument: a record
costs a handful of ``time.perf_counter_ns()`` calls and one
``deque.append`` (atomic under the interpreter lock), and the ring
drops its oldest entry when full.

Timestamps are ``time.perf_counter_ns()`` — the clock
``time.perf_counter()`` reads, so they join a caller's own per-step
times.  Three kinds of record (``"kind"``), every one with ``thread``
(``threading.get_ident()``), ``seq`` (process-wide order of creation)
and ``done`` (when it was closed and appended):

``run``  one per ``CompiledProgram._run`` call, appended when the call
    ends (also when it raises: the record then holds the stamps it
    reached).  ``program`` (``id`` of the CompiledProgram),
    ``first_call`` (the call missed the program's jit cache),
    ``fetched`` (``return_numpy``) and the stamps, in order:

      enter       _run entered
      feeds       feeds coerced to arrays of the declared dtype
      state       persistables read from the scope (multi-process:
                  the globalize pass follows, before ``key``)
      key         cache key with the program fingerprint computed and
                  the cache looked up
      built       ``_build_fn`` returned; equals ``key`` on a hit
      conformed   mesh pass done (state ``device_put`` to the declared
                  shardings); equals ``built`` without a mesh
      dispatched  ``fn(state, feeds)`` returned: the step is enqueued,
                  not finished.  ``dispatched - conformed`` is what
                  ``paddle_tpu_executor_step_seconds`` observes
      committed   collector push and scope write-back done
      returned    fetches are numpy, i.e. the device finished; equals
                  ``committed`` when ``fetched`` is false
      done        the record was closed: ``_run``'s inner frame is gone,
                  and with it the previous step's state arrays (their
                  buffers were donated to the step)

    A record whose ``first_call`` is true also holds what that call's
    ``dispatched - conformed`` was spent on (``first_call`` below), ns:

      trace_ns    the Python time of running every op's compute
                  symbolically (``_build_fn``'s ``step`` under jit)
      lower_ns    jax's ``jaxpr_to_mlir_module_duration`` events
      compile_ns  jax's ``backend_compile_duration`` events: the XLA
                  compile, or the load from the persistent cache
      cache_hit   a ``/jax/compilation_cache/cache_hits`` event fired

    The three are disjoint and sum to less than ``dispatched -
    conformed``; the rest is jit's own tracing machinery, the cache key
    and the first launch.  A call that hits the jit cache holds none.

``put``  one per batch in ``DeviceFeeder``'s transfer thread:
    ``host_wait`` (ns blocked waiting for the producer), ``start`` /
    ``end`` around the ``jax.device_put`` calls (host issue time: the
    copy itself is asynchronous and shows on the device's line),
    ``dev_wait`` (ns blocked because the consumer is behind),
    ``bytes``.

``next``  one per ``DeviceFeeder.__next__``: ``start`` / ``done`` around
    the wait for a device-resident batch.

The same phases are on the profiler's clock: ``Record.stamp(...,
phase=)`` opens a ``jax.profiler.TraceAnnotation`` named under
device_trace's grammar (``pt#executor.prepare#-``, ``.dispatch``,
``.commit``, ``.fetch``; ``pt#feeder.put#-``).  Outside a profiler
session a TraceAnnotation is a flag test.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time

from paddle_tpu.observability import device_trace as _device

__all__ = ["MAXLEN", "Record", "records", "clear", "first_call",
           "first_call_trace"]

MAXLEN = 4096
_now = time.perf_counter_ns

_ring = collections.deque(maxlen=MAXLEN)
_seq = itertools.count()
_KEEP = object()


class Record:
    """One record while it is written.  ``stamp(name)`` puts the time
    under ``name``; ``stamp(name, phase=p)`` also ends the open phase's
    profiler annotation and, unless ``p`` is None, begins ``p``'s, so a
    caller cuts its work into phases once for both clocks.  ``done()``
    ends what is open and appends the record to the ring; put it in a
    ``finally``."""

    __slots__ = ("fields", "_open")

    def __init__(self, kind, **fields):
        fields["kind"] = kind
        fields["seq"] = next(_seq)
        fields["thread"] = threading.get_ident()
        self.fields = fields
        self._open = None

    def stamp(self, name, phase=_KEEP):
        if phase is _KEEP:
            self.fields[name] = _now()
            return
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self.fields[name] = _now()
        if phase is not None:
            self._open = _device.session_annotation(phase)
            self._open.__enter__()

    def done(self):
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        self.fields["done"] = _now()
        _ring.append(self.fields)


# -- the first call's parts ---------------------------------------------------

_DURATIONS = {
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_ns",
    "/jax/core/compile/backend_compile_duration": "compile_ns",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# .fields: the record of the first call that is under way on this
# thread, outside its trace; jax fires its events on the calling thread
_first = threading.local()
_listen_lock = threading.Lock()
_listening = False


def _on_duration(event, secs, **_):
    fields = getattr(_first, "fields", None)
    if fields is not None and event in _DURATIONS:
        fields[_DURATIONS[event]] += int(secs * 1e9)


def _on_event(event, **_):
    fields = getattr(_first, "fields", None)
    if fields is not None and event == _CACHE_HIT:
        fields["cache_hit"] = True


def _listen():
    """Registers the two listeners on jax's monitoring events, once a
    process and not before a step is first called."""
    global _listening
    with _listen_lock:
        if not _listening:
            import jax.monitoring as mon

            mon.register_event_duration_secs_listener(_on_duration)
            mon.register_event_listener(_on_event)
            _listening = True


def first_call(rec, fn):
    """`fn`, a jitted step that has not run yet, wrapped for the ONE
    call that traces, lowers and compiles it: the call's `run` record
    gains `trace_ns`, `lower_ns`, `compile_ns` and `cache_hit`.  The
    caller keeps `fn` itself for every later call."""
    def call(*args):
        _listen()
        rec.fields.update(trace_ns=0, lower_ns=0, compile_ns=0,
                          cache_hit=False)
        _first.fields = rec.fields
        try:
            return fn(*args)
        finally:
            _first.fields = None

    return call


@contextlib.contextmanager
def first_call_trace():
    """Around the part of a jitted step's Python body that runs the
    ops' compute: adds its time to `trace_ns` of the first call under
    way on this thread.  Outside one (a `.lower()` from elsewhere, a
    retrace) it writes nowhere.  What jax lowers or compiles while
    the body runs is the trace's time, not counted again."""
    fields = getattr(_first, "fields", None)
    if fields is None:
        yield
        return
    _first.fields = None
    start = _now()
    try:
        yield
    finally:
        fields["trace_ns"] += _now() - start
        _first.fields = fields


def records(kind=None):
    """The ring's records as dicts (copies), oldest first; only those
    of ``kind`` when given."""
    return [dict(r) for r in list(_ring)
            if kind is None or r["kind"] == kind]


def clear():
    _ring.clear()

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
import itertools
import threading
import time

from paddle_tpu.observability import device_trace as _device

__all__ = ["MAXLEN", "Record", "records", "clear"]

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


def records(kind=None):
    """The ring's records as dicts (copies), oldest first; only those
    of ``kind`` when given."""
    return [dict(r) for r in list(_ring)
            if kind is None or r["kind"] == kind]


def clear():
    _ring.clear()

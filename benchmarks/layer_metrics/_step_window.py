"""Not a metric: what the readers of the program's step record share
(run_prepare_ms, run_fetch_ms, feed_put_ms, feed_put_in_run_ms,
first_call_s).  A reader runs in the process that ran the cell, so it
reads the ring itself (paddle_tpu/observability/step_record.py:
`run` records of CompiledProgram._run, `put` records of DeviceFeeder's
transfer thread, all in time.perf_counter_ns()).  A program from
before the record has no such module: everything here is then empty,
the reader gives None and the metric is left out of the line.

The measured window's steps, without the harness's help: of the step
program's `run` records (the program with the most records) that did
not miss the jit cache, drop those that fetched nothing (the traced
stretch calls exe.run(..., return_numpy=False) and comes after the
window) and take the last m["attempted"] (the warm-up steps come
before).  Load with runpy.run_path, as the readers do.
"""

import collections


def _records(kind):
    try:
        from paddle_tpu.observability import step_record
    except ImportError:
        return []
    return step_record.records(kind)


def _step_runs():
    runs = _records("run")
    if not runs:
        return []
    program = collections.Counter(
        r["program"] for r in runs).most_common(1)[0][0]
    return [r for r in runs if r["program"] == program]


def window(m):
    """The window's `run` records, oldest first."""
    steps = [r for r in _step_runs()
             if not r["first_call"] and r["fetched"]]
    return steps[-m["attempted"]:] if m.get("attempted") else []


def first_call():
    """The step program's record of the call that built its step."""
    return next((r for r in _step_runs() if r["first_call"]), None)


def puts(m):
    """(the window's run records, the `put` records that began inside
    the window)."""
    steps = window(m)
    if not steps:
        return [], []
    t0 = steps[0]["enter"]
    t1 = max(r.get("returned", r["enter"]) for r in steps)
    return steps, [p for p in _records("put")
                   if "end" in p and t0 <= p["start"] <= t1]


def median_ms(records, later, earlier):
    """Median over the records that hold both stamps of
    later - earlier, ms; None when none does."""
    d = sorted(r[later] - r[earlier] for r in records
               if later in r and earlier in r)
    if not d:
        return None
    mid = len(d) // 2
    return (d[mid] if len(d) % 2 else (d[mid - 1] + d[mid]) / 2) / 1e6

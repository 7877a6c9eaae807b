"""Not a metric: what the readers of the program's step record share
(run_prepare_ms, run_fetch_ms, feed_put_ms, feed_put_in_run_ms,
first_call_s).  A reader runs in the process that ran the cell, so it
reads the ring itself (paddle_tpu/observability/step_record.py:
`run` records of CompiledProgram._run, `put` records of DeviceFeeder's
transfer thread, all in time.perf_counter_ns()).  A program from
before the record has no such module: everything here then gives None,
and the metric is left out of the line.

The measured window's steps, without the harness's help: of the step
program's `run` records (the program with the most records) that did
not miss the jit cache, drop those that fetched nothing (the traced
stretch calls exe.run(..., return_numpy=False) and comes after the
window) and take the last m["attempted"] (the warm-up steps come
before).  Load with runpy.run_path, as the readers do.
"""

import collections


def _step_runs():
    try:
        from paddle_tpu.observability import step_record
    except ImportError:
        return None, None
    runs = step_record.records("run")
    if not runs:
        return None, None
    program = collections.Counter(
        r["program"] for r in runs).most_common(1)[0][0]
    return step_record, [r for r in runs if r["program"] == program]


def window(m):
    """The window's `run` records, oldest first, or None."""
    _, runs = _step_runs()
    if not runs or not m.get("attempted"):
        return None
    steps = [r for r in runs if not r["first_call"] and r["fetched"]]
    return steps[-m["attempted"]:] or None


def first_call():
    """The step program's record of the call that built its step."""
    _, runs = _step_runs()
    return next((r for r in runs or [] if r["first_call"]), None)


def puts(m):
    """(window's run records, the `put` records that began inside the
    window), or (None, None)."""
    steps = window(m)
    if not steps:
        return None, None
    step_record, _ = _step_runs()
    t0 = steps[0]["enter"]
    t1 = max(r.get("returned", r["enter"]) for r in steps)
    return steps, [p for p in step_record.records("put")
                   if "end" in p and t0 <= p["start"] <= t1]


def median_ms(steps, later, earlier):
    """Median over the records that hold both stamps of
    later - earlier, ms; None when none does."""
    d = sorted(r[later] - r[earlier] for r in steps or []
               if later in r and earlier in r)
    if not d:
        return None
    mid = len(d) // 2
    return (d[mid] if len(d) % 2 else (d[mid - 1] + d[mid]) / 2) / 1e6

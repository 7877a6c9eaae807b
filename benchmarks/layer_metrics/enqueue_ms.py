"""Layer: entry (core/executor.py, core/compiler.py CompiledProgram._run).
Mean of the program's paddle_tpu_executor_step_seconds over the
measured window, ms, read as what it is: the time fn(state, feeds) took
to return, i.e. host enqueue time, not a step time (the clock stops
before the device finishes).  Source: the program's registry.
"""


def read(m):
    c = m["counters"]
    if not c["enqueue_count"]:
        return None
    return c["enqueue_sum_s"] / c["enqueue_count"] * 1e3

"""Shared by the readers of kernels the program names: device time of
the Mosaic calls of the given names, and their share of a roofline.
trace_reduce.py groups a step's device operations as
`mosaic:<kernel name>` (op_ns, first device).  Load with
runpy.run_path, as the readers of _step_window.py do."""


def per_step_ms(m, names):
    """ms a step of the calls named `names`, or None where the trace
    holds none of them (no trace, a CPU trace, a program from before
    the kernels)."""
    if m["trace"] is None:
        return None
    r = m["trace"]["devices"][m["trace"]["first"]]
    found = [r["op_ns"][k] for k in ("mosaic:" + n for n in names)
             if k in r["op_ns"]]
    if not found:
        return None
    return sum(found) / r["steps"] / 1e6


def roofline_pct(m, names, work_key):
    """Least time one chip could take for the work `work_key` names
    (the builder's kernel_work: operations and bytes the algorithm
    needs, from shapes) over the measured time of the calls, %."""
    work = m["work"]["kernel_work"].get(work_key)
    ms = per_step_ms(m, names)
    if work is None or not ms:
        return None
    least_s, _ = m["flops"].roofline_seconds(
        work["flops"] / m["chips"], work["bytes"] / m["chips"],
        m["peaks"])
    return least_s * 1e3 / ms * 100

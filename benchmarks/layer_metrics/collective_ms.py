"""Layer: sharding (transpiler/sharding_transpiler.py, parallel/gspmd.py).
Device time of all-reduce, all-gather, reduce-scatter, all-to-all and
collective-permute per step on the first device, ms.  Source: the
device trace (trace_reduce.py).
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "collective_ns")

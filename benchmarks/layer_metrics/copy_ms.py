"""Layer: kernels (layout copies).  Device time per step on the first
device of what trace_reduce.py classes `copy` (copy, transpose,
slice, concatenate, pad, broadcast and dynamic-update-slice
instructions outside fusions), ms: head split and merge round the
flash kernels, the gathers' operands, the streams' relayouts.  Source:
the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "category_ns", "copy")

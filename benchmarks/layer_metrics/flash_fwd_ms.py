"""Layer: kernels (ops/pallas_kernels.py).  Device time per step on
the first device of the flash-attention forward kernel, the Mosaic
call the program names pt_flash_fwd, ms.  None where the trace holds
no call of that name (a program from before the kernels were named).
Source: the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    r = m["trace"]["devices"][m["trace"]["first"]]
    ns = r["op_ns"].get("mosaic:pt_flash_fwd")
    if ns is None:
        return None
    return ns / r["steps"] / 1e6

"""Layer: kernels (ops/pallas_kernels.py).  Device time per step on
the first device of the two flash-attention backward kernels, the
Mosaic calls the program names pt_flash_bwd_dq and pt_flash_bwd_dkv,
ms.  None where the trace holds neither name.  Source: the device
trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    r = m["trace"]["devices"][m["trace"]["first"]]
    ns = [r["op_ns"].get("mosaic:pt_flash_bwd_" + k)
          for k in ("dq", "dkv")]
    if ns == [None, None]:
        return None
    return sum(n or 0 for n in ns) / r["steps"] / 1e6

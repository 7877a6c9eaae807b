"""Layer: kernels (ops/pallas_kernels.py flash forward and backward).
Device time of Mosaic custom calls per step on the first device, ms;
the only ones in a Transformer step are the flash-attention calls.
Source: the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "category_ns", "mosaic")

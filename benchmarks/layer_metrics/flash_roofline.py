"""Layer: kernels.  Share of its roofline the flash-attention kernels
reach, %: the least time one chip could take for the operations and
bytes the algorithm needs (flops.py: causal half of the square,
backward twice the forward, recomputed scores not counted; the larger
of FLOPs over peak FLOP/s and bytes over peak bytes/s, which for these
shapes is compute) over flash_ms.  Source: the device trace.
"""


def read(m):
    work = m["work"]["kernel_work"].get("flash")
    if m["trace"] is None or work is None:
        return None
    ms = m["tr"].per_step_ms(m["trace"], "category_ns", "mosaic")
    if not ms:
        return None
    least_s, _ = m["flops"].roofline_seconds(
        work["flops"] / m["chips"], work["bytes"] / m["chips"],
        m["peaks"])
    return least_s * 1e3 / ms * 100

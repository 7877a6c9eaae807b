"""Layer: kernels (XLA matmul fusions: projections, feed-forward, logits).
Device time per step on the first device of convolution instructions
and of fusions whose computation holds one, ms: to the TPU compiler a
matmul is a convolution, and a Transformer step holds no other.  In a
sharded step this includes the fusions that hide an all-gather or a
reduce-scatter around a matmul (async_collective_fusion).  Source: the
device trace, with the compiled step's HLO text to say which fusions
hold a convolution.
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "category_ns", "convolution")

"""Layer: kernels (XLA fusions that hold no matmul).  Device time per
step on the first device of the fusions trace_reduce.py classes
`fusion`, ms: norms, rotary, SwiGLU, the router, the residual-stream
mixes and their Sinkhorn, the loss, casts and Adam, until fusions carry
names a reduction can split (the program's ops run under
jax.named_scope pt_mla, pt_moe_route, pt_moe_experts, pt_mhc: in the
HLO metadata, not in the event names).  Source: the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "category_ns", "fusion")

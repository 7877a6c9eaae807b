"""Layer: kernels (ops/pallas_eva.py, the chunk summariser).  Device
time per step on the first device of the Mosaic calls named
pt_eva_pool_fwd and pt_eva_pool_bwd, ms: every layer's pooling of K and
V into one summary a chunk, and its backward (which forms the chunks'
softmaxes again).  None where the trace holds neither (no trace, a
parent without the kernels, a cell without EVA).  Source: the device
trace.
"""

import os
import runpy

EVA_POOL = ("pt_eva_pool_fwd", "pt_eva_pool_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, EVA_POOL)

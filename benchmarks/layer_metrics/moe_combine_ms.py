"""Layer: kernels (ops/pallas_moe_combine.py: the routed experts' sum
by token over the pairs that are held).  Device time per step on the
first device of the Mosaic calls named pt_moe_combine, ms: the
forward's combine, its replay in the recompute segment and d x of
every expert layer.  None where the trace holds none of them (a parent
whose combine is XLA's gathers, a cell without experts).  Source: the
device trace.
"""

import os
import runpy

MOE_COMBINE = ("pt_moe_combine",)

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, MOE_COMBINE)

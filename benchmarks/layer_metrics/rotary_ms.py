"""Layer: kernels (ops/pallas_rotary.py: the rotary position embedding
of q and k as one pass over the projection where it lies).  Device
time per step on the first device of the Mosaic calls named pt_rotary,
ms: the forward pass's, the recompute segments' replays and the
backward's (the same kernel at the negative angle) of every rotary op.
None where the trace holds none (a parent whose rotation is XLA's
fusions and relayouts, a cell without a rotary embedding).  Source: the
device trace.
"""

import os
import runpy

ROTARY = ("pt_rotary",)

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, ROTARY)

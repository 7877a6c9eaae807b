"""Layer: kernels (ops/pallas_conv1d.py: the short causal convolution
of the Mamba-2 and KDA mixers).  Device time per step on the first
device of the Mosaic calls named pt_conv1d_fwd and pt_conv1d_bwd, ms.
None where the trace holds none of them (a parent whose convolution is
XLA's graph, a cell without one).  Source: the device trace.
"""

import os
import runpy

CONV1D = ("pt_conv1d_fwd", "pt_conv1d_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, CONV1D)

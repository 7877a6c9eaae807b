"""Layer: kernels (ops/pallas_ssd.py: the chunked state-space scan of
the Mamba-2 mixers).  Device time per step on the first device of the
Mosaic calls named pt_ssd_fwd and pt_ssd_bwd, ms.  None where the trace
holds none of them.  Source: the device trace.
"""

import os
import runpy

SSD = ("pt_ssd_fwd", "pt_ssd_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, SSD)

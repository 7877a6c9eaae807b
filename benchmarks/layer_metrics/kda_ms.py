"""Layer: kernels (ops/pallas_kda.py: the chunked delta-rule scan of
the KDA mixers).  Device time per step on the first device of the
Mosaic calls named pt_kda_fwd and pt_kda_bwd, ms.  None where the trace
holds none of them.  Source: the device trace.
"""

import os
import runpy

KDA = ("pt_kda_fwd", "pt_kda_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, KDA)

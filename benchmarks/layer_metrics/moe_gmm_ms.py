"""Layer: kernels (ops/pallas_gmm.py: the grouped matmuls of the routed
experts held here).  Device time per step on the first device of the
Mosaic calls named pt_gmm_fwd, pt_gmm_bwd_dx and pt_gmm_bwd_dw, ms.
None where the trace holds none of them.  Source: the device trace.
"""

import os
import runpy

GMM = ("pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, GMM)

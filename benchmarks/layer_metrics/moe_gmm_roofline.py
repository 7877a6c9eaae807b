"""Layer: kernels (ops/pallas_gmm.py).  Share of their roofline the
grouped-matmul kernels reach, %: the least time for the rows a uniform
router sends to the experts held here (builders/xing4_flops.py
gmm_step: tokens x experts per token x held / all experts rows, three
products forward and six backward, each operand moved once; rows padded
to a tile and a forward run again by a recompute segment are not
counted) over moe_gmm_ms.  At 256 rows an expert the bound is close to
the ridge: weights' bytes and rows' operations take about as long.
Source: the device trace.
"""

import os
import runpy

GMM = ("pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, GMM, "moe_gmm")

"""Layer: kernels (ops/pallas_ssd.py).  Share of their roofline the
scan kernels reach, %: the least time for the operations and bytes the
chunked algorithm needs (builders/granite_hybrid_flops.py ssd_step: per
chunk C B^T once a group, per head the masked product, the chunk's
state and the state's output, the backward counted as its own products;
X, B, C, dt, Y and the chunk-start states moved once; the other head's
zeroed lanes of a 128-lane block, the decay matrices the backward forms
again and a recompute segment's replay are not counted) over ssd_ms.
At 64-deep contractions on a 128-deep array the bound is out of reach
by construction; the share says how far.  Source: the device trace.
"""

import os
import runpy

SSD = ("pt_ssd_fwd", "pt_ssd_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, SSD, "ssd")

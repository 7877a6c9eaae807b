"""Layer: kernels (ops/pallas_mhc.py: the two halves of a
manifold-constrained hyper-connection).  Device time per step on the
first device of the Mosaic calls named pt_mhc_pre_fwd, pt_mhc_pre_bwd,
pt_mhc_post_fwd and pt_mhc_post_bwd, ms: the forward pass's, the
recompute segments' replays and the backward's of every
hyper-connection.  None where the trace holds none of them (a parent
whose stream mixes are XLA's fusions, a cell without hyper-connections).
Source: the device trace.
"""

import os
import runpy

MHC = ("pt_mhc_pre_fwd", "pt_mhc_pre_bwd", "pt_mhc_post_fwd",
       "pt_mhc_post_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, MHC)

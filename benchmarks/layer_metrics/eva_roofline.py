"""Layer: kernels (ops/pallas_eva.py, ops/pallas_kernels.py).  Share
of its roofline EVA attention's aggregation reaches, %: the least time
for attention over the pairs the windows ALLOW (a query's own window up
to itself, sum_i min(i mod W + 1, W), plus W/c chunk keys of each
earlier window, at every head; backward twice the forward; q, k, v, o,
the summaries and their gradients moved once:
builders/evabyte_flops.py eva_step; neither the masked half of a
window's diagonal blocks nor the scores the backward forms again is
counted, so they show as lost share) over the device time eva_ms reads.
None where the builder counts no such work or the trace holds no
staircase call (a parent without the kernels).  Source: the device
trace.
"""

import os
import runpy

_here = os.path.dirname(__file__)
_nk = runpy.run_path(os.path.join(_here, "_named_kernels.py"))
_ms = runpy.run_path(os.path.join(_here, "eva_ms.py"))


def read(m):
    if _nk["per_step_ms"](m, _ms["STAIRCASE"]) is None:
        return None
    return _nk["roofline_pct"](m, _ms["STAIRCASE"] + _ms["WINDOW"], "eva")

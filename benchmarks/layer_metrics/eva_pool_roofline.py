"""Layer: kernels (ops/pallas_eva.py, the chunk summariser).  Share of
its roofline the summariser reaches, %: the least time to read K and V
once and write 1/chunk of them, and backward to read K, V and the
summaries' gradients and write dK and dV (builders/evabyte_flops.py
eva_pool_step: bound by the bytes; the softmaxes the backward forms
again are not counted) over the device time of pt_eva_pool_fwd and
pt_eva_pool_bwd.  None where the builder counts no such work or the
trace holds neither call.  Source: the device trace.
"""

import os
import runpy

_here = os.path.dirname(__file__)
_nk = runpy.run_path(os.path.join(_here, "_named_kernels.py"))
_ms = runpy.run_path(os.path.join(_here, "eva_pool_ms.py"))


def read(m):
    return _nk["roofline_pct"](m, _ms["EVA_POOL"], "eva_pool")

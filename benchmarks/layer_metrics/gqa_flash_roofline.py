"""Layer: kernels (ops/pallas_kernels.py flash forward and backward
with fewer KV heads than query heads: K and V read in place by
q_head // group).  Share of their roofline the flash kernels reach, %:
the least time for causal attention at the QUERY heads' count with K
and V read once a KV head (builders/granite_hybrid_flops.py
gqa_flash_step: half the square, backward twice the forward; neither
the scores the backward forms again nor the per-query-head dk and dv
the entry sums over a group is counted) over the device time of the
calls named pt_flash_fwd, pt_flash_bwd_dq and pt_flash_bwd_dkv.
Source: the device trace.
"""

import os
import runpy

FLASH = ("pt_flash_fwd", "pt_flash_bwd_dq", "pt_flash_bwd_dkv")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, FLASH, "gqa_flash")

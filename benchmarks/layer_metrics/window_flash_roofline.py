"""Layer: kernels (ops/pallas_kernels.py flash forward and backward
with a sliding window).  Share of their roofline the window layers'
flash kernels reach, %: the least time for attention over the pairs the
window ALLOWS, sum_i min(i + 1, W) a sequence at the QUERY heads' count
with K and V read once a KV head (builders/mellum2_flops.py
window_flash_step: backward twice the forward; neither the masked half
of a block pair on the band's two edges, nor the scores the backward
forms again, nor the per-query-head dk and dv the entry sums over a
group is counted, so they show as lost share) over the device time of
the calls named pt_flash_win_fwd, pt_flash_win_bwd_dq and
pt_flash_win_bwd_dkv.  None where the builder counts no such work or
the trace holds none of the calls (a parent without them).  Source: the
device trace.
"""

import os
import runpy

WINDOW_FLASH = ("pt_flash_win_fwd", "pt_flash_win_bwd_dq",
                "pt_flash_win_bwd_dkv")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, WINDOW_FLASH, "window_flash")

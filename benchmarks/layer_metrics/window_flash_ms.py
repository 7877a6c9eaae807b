"""Layer: kernels (ops/pallas_kernels.py flash forward and backward
with a sliding window: the grids walk the band's block pairs only).
Device time per step on the first device of the Mosaic calls named
pt_flash_win_fwd, pt_flash_win_bwd_dq and pt_flash_win_bwd_dkv, ms:
every window layer's attention, apart from the full layers' (whose
calls keep the names flash_fwd_ms and flash_bwd_ms read).  None where
the trace holds none of them (no trace, a parent without the window
kernels, a cell without a window layer).  Source: the device trace.
"""

import os
import runpy

WINDOW_FLASH = ("pt_flash_win_fwd", "pt_flash_win_bwd_dq",
                "pt_flash_win_bwd_dkv")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, WINDOW_FLASH)

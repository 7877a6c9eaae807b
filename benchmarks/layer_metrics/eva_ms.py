"""Layer: kernels (ops/pallas_eva.py and, for a window's own tokens,
the flash kernels of ops/pallas_kernels.py).  Device time per step on
the first device of every Mosaic call of EVA attention's aggregation,
ms: a window's causal flash (pt_flash_fwd, pt_flash_bwd_dq,
pt_flash_bwd_dkv: in a configuration whose every mixer is EVA they are
the window part and nothing else) and the staircase over the earlier
windows' chunk keys (pt_eva_chunk_fwd, pt_eva_chunk_bwd), which
carries the window part's running softmax on: no merge is left outside
the kernels.  None where the trace
holds no staircase call (no trace, a parent without the kernels, a cell
without EVA).  Source: the device trace.
"""

import os
import runpy

STAIRCASE = ("pt_eva_chunk_fwd", "pt_eva_chunk_bwd")
WINDOW = ("pt_flash_fwd", "pt_flash_bwd_dq", "pt_flash_bwd_dkv")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    if _nk["per_step_ms"](m, STAIRCASE) is None:
        return None
    return _nk["per_step_ms"](m, STAIRCASE + WINDOW)

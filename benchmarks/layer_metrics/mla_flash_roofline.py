"""Layer: kernels (ops/pallas_kernels.py flash forward and backward at a
q.k size of 192 and a v size of 128: latent attention).  Share of their
roofline the three flash kernels reach, %: the least time for the
operations and bytes causal attention needs at the two head sizes
(builders/xing4_flops.py flash_step: half the square, backward twice
the forward; neither the scores the backward forms again nor a forward
that a recompute segment runs a second time is counted) over the device
time of the calls named pt_flash_fwd, pt_flash_bwd_dq and
pt_flash_bwd_dkv.  By name, not the whole Mosaic category: the step
holds the grouped-matmul kernels too.  Source: the device trace.
"""

import os
import runpy

FLASH = ("pt_flash_fwd", "pt_flash_bwd_dq", "pt_flash_bwd_dkv")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, FLASH, "mla_flash")

"""Layer: kernels (ops/pallas_kda.py).  Share of their roofline the KDA
scan kernels reach, %: the least time for the operations and bytes the
chunked WY form needs (builders/ling3_flops.py kda_step: per chunk and
head the two score matrices, the triangular inverse, W and U, the
state's products and its update, a triangular product as half its
square, the backward counted as its own products; Q, K, V, G, beta, O,
their gradients and the block-start states moved once; the chunk's
forward that the backward kernel runs again from a block's saved state,
the masked half of a product and a recompute segment's replay are not
counted) over kda_ms.  At 64-row products on a 128-deep array and ten
dependent 64 x 64 products an inverse the bound is out of reach by
construction; the share says how far.  Source: the device trace.
"""

import os
import runpy

KDA = ("pt_kda_fwd", "pt_kda_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["roofline_pct"](m, KDA, "kda")

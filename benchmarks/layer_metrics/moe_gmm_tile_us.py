"""Layer: kernels (ops/pallas_gmm.py).  Device time of the grouped
matmuls a live row tile, us: moe_gmm_ms x 1000 over the live tiles of a
step (summed over the expert layers, mean over the steady steps of the
traced stretch, the steps moe_gmm_ms is a mean over).  A layer's calls
run a fixed number of grid steps a live tile, so this is what the
kernels cost whatever the router sent; moe_gmm_ms is this times a count
that drifts.  Source: the device trace over the program's stat rings
(_moe_load.py).
"""

import os
import runpy

_ml = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_moe_load.py"))


def read(m):
    rows, ms = _ml["rows"](m, "traced"), _ml["gmm_ms"](m)
    if rows is None or not ms:
        return None
    tiles_a_step = float(rows[:, :, -1].sum(axis=0).mean())
    return ms * 1e3 / tiles_a_step if tiles_a_step else None

"""Layer: kernels (ops/llm_ops.py moe_experts, ops/pallas_gmm.py).  Row
tiles that hold rows, an expert layer: the bound of the grouped
matmuls' grids (`n_active`), mean over the expert layers and over the
steady steps of the traced stretch: the work moe_gmm_ms was given.
The cell's router drifts to the held experts through a run (PERF.md
section 7), so this differs from run to run and two readings of
moe_gmm_ms compare only at like values of it.  Source: the program's
stat rings (`<layer>.load`, column `live_tiles`; _moe_load.py).
"""

import os
import runpy

_ml = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_moe_load.py"))


def read(m):
    return _ml["live_tiles"](m, "traced")

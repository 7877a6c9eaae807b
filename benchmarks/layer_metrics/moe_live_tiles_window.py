"""Layer: kernels (ops/llm_ops.py moe_experts, ops/pallas_gmm.py).  Row
tiles that hold rows, an expert layer, mean over the expert layers and
over the steps of the MEASURED window: the work the cell's
tokens_per_s was given (the kernels cost time a live tile, and the
cell's router sends more rows here as a run goes on: two rates compare
only at like values of this).  Source: the program's stat rings
(`<layer>.load`, column `live_tiles`; _moe_load.py).
"""

import os
import runpy

_ml = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_moe_load.py"))


def read(m):
    return _ml["live_tiles"](m, "window")

"""Layer: kernels (ops/pallas_gmm.py).  Share of their roofline the
grouped-matmul kernels reach for the rows that really came, %: the
least time for each steady step of the traced stretch, from every
expert layer's routed pairs in the program's stat rings (column
`routed`) through builders/xing4_flops.py gmm_flops / gmm_bytes,
counted as gmm_step counts (three products forward, six backward, each
operand moved once; rows padded to a tile and a forward run again by a
recompute segment are not counted), mean over those steps, over
moe_gmm_ms.  moe_gmm_roofline divides the work of the rows a uniform
router would send by the same time.  Source: the device trace over the
program's stat rings (_moe_load.py).
"""

import os
import runpy

_HERE = os.path.dirname(os.path.abspath(__file__))
_ml = runpy.run_path(os.path.join(_HERE, "_moe_load.py"))
_work = runpy.run_path(os.path.join(_HERE, os.pardir, "builders",
                                    "xing4_flops.py"))


def read(m):
    rows, ms = _ml["rows"](m, "traced"), _ml["gmm_ms"](m)
    if rows is None or not ms:
        return None
    c = m["config"]
    hidden, width = c["hidden_size"], c["moe_intermediate_size"]
    held = rows.shape[2] - 2          # a column an expert held here
    least = []
    for routed in rows[:, :, -2].T:                 # a step's layers
        flops = sum(_work["gmm_flops"](r, hidden, width, backward=b)
                    for r in routed for b in (False, True))
        nbytes = sum(_work["gmm_bytes"](r, held, hidden, width, 2,
                                        backward=b)
                     for r in routed for b in (False, True))
        least.append(m["flops"].roofline_seconds(
            flops / m["chips"], nbytes / m["chips"], m["peaks"])[0])
    return sum(least) / len(least) * 1e3 / ms * 100

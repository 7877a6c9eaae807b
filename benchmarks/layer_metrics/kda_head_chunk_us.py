"""Layer: kernels (ops/pallas_kda.py: the chunked delta-rule scan of
the KDA mixers).  Device time of the Mosaic calls named pt_kda_fwd and
pt_kda_bwd a step (kda_ms) over the chunks of the scan the step walks,
us: the builder's kernel_work["kda"]["head_chunks"], KDA layers x B x
heads held x T / chunk, each walked once forward and once backward.
The kernels' time in the unit that compares 8 heads at 8,192 tokens
with 32 heads at 4,096.  None where the trace holds no such call or the
builder gives no count.  Source: the device trace.
"""

import os
import runpy

KDA = ("pt_kda_fwd", "pt_kda_bwd")

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    chunks = (m["work"]["kernel_work"].get("kda") or {}).get("head_chunks")
    ms = _nk["per_step_ms"](m, KDA)
    if not chunks or ms is None:
        return None
    return ms * 1e3 / chunks

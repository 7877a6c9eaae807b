"""Layer: kernels (ops/pallas_kda.py: the chunked delta-rule scan of
the KDA mixers).  Device time per step on the first device of the
backward kernel, the Mosaic call named pt_kda_bwd, ms: with kda_fwd_ms
it says WHICH of kda_ms's two kernels moved.  None where the trace
holds no call of that name.  Source: the device trace.
"""

import os
import runpy

_nk = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_named_kernels.py"))


def read(m):
    return _nk["per_step_ms"](m, ("pt_kda_bwd",))

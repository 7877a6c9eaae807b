"""Layer: build and compile.  dispatched - conformed of the step
program's first call, s: the jit trace of the whole program, its
lowering, the compile or the persistent-cache load, and the first
launch.  Source: the program's step record.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    r = _sw["first_call"]()
    if r is None or "dispatched" not in r:
        return None
    return (r["dispatched"] - r["conformed"]) / 1e9

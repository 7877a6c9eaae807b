"""Layer: build and compile.  `lower_ns` of the step program's first
call, s: jax's lowering of the traced step to an MLIR module
(its monitoring event jaxpr_to_mlir_module_duration, fired on the
calling thread between `conformed` and `dispatched`).
One of the three parts of first_call_s the program's step record keeps
(`trace_ns`, `lower_ns`, `compile_ns`; the rest of first_call_s is
jit's own tracing machinery, the cache key and the first launch).
Source: the program's step record.  None on a program whose record has
no such field.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    r = _sw["first_call"]()
    if r is None or "lower_ns" not in r:
        return None
    return r["lower_ns"] / 1e9

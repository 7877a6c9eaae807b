"""Layer: build and compile.  `compile_ns` of the step program's first
call, s: the XLA compile of the step, or its load from the
persistent cache (jax's monitoring event backend_compile_duration
between `conformed` and `dispatched`; unlike compile_s not the startup
program's nor the reference's compiles).
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
    if r is None or "compile_ns" not in r:
        return None
    return r["compile_ns"] / 1e9

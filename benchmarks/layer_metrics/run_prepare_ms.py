"""Layer: entry (core/compiler.py CompiledProgram._run).  Median over
the measured window of conformed - enter, ms: the host work in exe.run
before the launch (feed coercion, the scope walk over the
persistables, the cache key with the program fingerprint, on a mesh
the sharding check of every state array), during which the device
idles.  Source: the program's step record.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    return _sw["median_ms"](_sw["window"](m), "conformed", "enter")

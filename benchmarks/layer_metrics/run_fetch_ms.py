"""Layer: entry (core/compiler.py CompiledProgram._run).  Median over
the measured window of returned - committed, ms: the wait for the loss
as numpy, i.e. for the device to finish the step.  step_p50_ms less
this and feed_wait_ms is what the host spends not waiting.  Source:
the program's step record.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    return _sw["median_ms"](_sw["window"](m), "returned", "committed")

"""Layer: input (reader.py DeviceFeeder) against entry.  Mean per step
of the measured window of the time the transfer thread's `put`
intervals [start, end] overlap that step's [enter, dispatched], ms: how
long the transfer thread issued copies while the main thread was on
the launch path, the two contending for the interpreter lock.  Source:
the program's step record.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    steps, puts = _sw["puts"](m)
    steps = [r for r in steps if "dispatched" in r]
    if not steps:
        return None
    overlap = sum(max(0, min(p["end"], r["dispatched"])
                      - max(p["start"], r["enter"]))
                  for r in steps for p in puts)
    return overlap / len(steps) / 1e6

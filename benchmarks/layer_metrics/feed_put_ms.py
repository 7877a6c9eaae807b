"""Layer: input (reader.py DeviceFeeder).  Median end - start of the
transfer thread's `put` records that began inside the measured window,
ms: host time to issue one batch's jax.device_put calls (the copy
itself is asynchronous and shows on the device's line).  Source: the
program's step record.
"""

import os
import runpy

_sw = runpy.run_path(os.path.join(os.path.dirname(__file__),
                                  "_step_window.py"))


def read(m):
    _, puts = _sw["puts"](m)
    return _sw["median_ms"](puts, "end", "start")

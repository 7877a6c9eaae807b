"""Layer: input (reader.py PyReader/DeviceFeeder, data_feeder.py).
Median time a step of the measured window waited in next(loader), ms.
Source: the harness's host clock.  A starved feeder shows here, not as
a slow chip.
"""


def read(m):
    import numpy as np

    return float(np.median(m["clocks"]["feed_wait_s"]) * 1e3)

"""Layer: entry.  Median step of the measured window, ms: from before
next(loader) to the fetched loss.  Source: the harness's host clock.
"""


def read(m):
    import numpy as np

    return float(np.median(m["clocks"]["step_s"]) * 1e3)

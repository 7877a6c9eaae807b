"""Layer: device.  Share of the steady traced window in which no
operation ran on the device, %, averaged over the chips used: 1 - union
of device-operation intervals / window.  Source: the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    return (1 - m["trace"]["busy_s"] / m["trace"]["window_s"]) * 100

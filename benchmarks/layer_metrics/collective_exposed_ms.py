"""Layer: sharding.  The part of collective_ms during which no other
operation ran on that device, per step, ms: the communication not
hidden behind compute.  Source: the device trace.
"""


def read(m):
    if m["trace"] is None:
        return None
    return m["tr"].per_step_ms(m["trace"], "collective_exposed_ns")

"""Layer: device.  Bytes one device holds for the compiled step, GB:
memory_analysis() argument + output + temp - alias.  Says how full the
chip is and guards fit.  Source: the program's compiled step.
"""


def read(m):
    return m["memory"]["step_bytes"] / 1e9

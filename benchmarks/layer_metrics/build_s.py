"""Layer: build and compile (backward.py, optimizer.py,
contrib/mixed_precision, transpiler/).  Seconds to build the model,
minimize, transpile and wrap it as a CompiledProgram.  Source: the
harness's host clock.
"""


def read(m):
    return m["clocks"]["build_s"]

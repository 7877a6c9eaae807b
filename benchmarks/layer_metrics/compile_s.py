"""Layer: build and compile.  Seconds jax spent in backend compiles (or
in loading them from the persistent cache) during set-up.  Source:
jax's monitoring event /jax/core/compile/backend_compile_duration.
"""


def read(m):
    return m["counters"]["compile_s"]

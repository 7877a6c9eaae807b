"""The benchmark's own tests run on the CPU: `pytest benchmarks/tests`.
They are outside the repository's tier-1 suite (tests/)."""

import os
import sys

# four virtual CPU devices for the sharded rehearsal, before jax loads
os.environ.setdefault("JAX_PLATFORMS", "cpu")
_flag = "--xla_force_host_platform_device_count"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " %s=4" % _flag).strip()

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
for p in (CHECKOUT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

"""The stat ring: what a compiled step says of itself, always on.

The step record (step_record.py) says where a step's HOST time went.
What happened inside the compiled step (how many rows a router sent to
each expert this chip holds, how many row tiles a kernel's grid ran) is
known only on the device, and a fetch would change the step's outputs
and so the compiled module.  ``layers.step_stat(name, x)`` keeps such a
value in the program's own state instead:

* a persistable ring ``step_stat.<name>`` ``[K, width]`` float32, one a
  stat, and ONE persistable step counter a program, shared by its
  stats.  Both pass through the step donated, as all state does;
* one ``increment`` of the counter a step and one ``step_stat`` op a
  stat (ops/metrics.py), which writes ``x`` into row ``i mod K`` of the
  ring, ``i`` the index (from 0) of this execution of the program's
  step.  No host sync, no extra fetch, no callback, no flag: the
  compiled module is the same whoever reads the ring, or nobody.

Both ops carry the role ``stat`` (core/program.py): they have no
gradient, a recompute segment does not replay them (the ring advances
one row a step, forward + backward + update), and
``Program.clone(for_test=True)`` drops them, so an evaluation program
that shares the scope leaves the ring and the step index alone.  Under
a mesh the ring and the counter are replicated, and so is the value
written (the out-sharding of state is pinned: the partitioner reduces
a value computed from sharded operands before it writes the row).

``read()`` is the whole reader: ONE device-to-host copy a ring and one
of the counter when called, none before, nothing compiled.  Row ``i``
belongs to the ``i``-th execution of the program's step, so rows join
the step record's ``run`` records of the same CompiledProgram by order
(both keep the last ``K`` = ``step_record.MAXLEN``).
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.observability.step_record import MAXLEN as K

__all__ = ["K", "read"]


def read(program=None, scope=None):
    """``{stat name: {"columns": (str, ...), "steps": int64 [n],
    "rows": float32 [n, width]}}`` for every stat of ``program`` (a
    Program or a CompiledProgram; the default main program) whose ring
    ``scope`` (the global scope) holds: the rows still in the ring,
    oldest first, ``n = min(steps run, K)``, ``steps[j]`` the step
    index of ``rows[j]``.  A program without a stat gives ``{}``."""
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.framework import default_main_program

    if program is None:
        program = default_main_program()
    program = getattr(program, "_program", program)   # a CompiledProgram
    if scope is None:
        scope = global_scope()

    def held(name):
        var = scope.find_var(name)
        return None if var is None or var.get() is None \
            else np.asarray(var.get())

    out, counts = {}, {}
    for op in program.global_block().ops:
        if op.type != "step_stat":
            continue
        step_name = op.inputs["Step"][0]
        if step_name not in counts:
            counts[step_name] = held(step_name)
        ring = held(op.inputs["Ring"][0])
        if ring is None or counts[step_name] is None:
            continue
        done = int(counts[step_name].reshape(-1)[0])
        steps = np.arange(max(done - len(ring), 0), done, dtype=np.int64)
        out[op.attrs["name"]] = {
            "columns": tuple(op.attrs["columns"]), "steps": steps,
            "rows": ring[steps % len(ring)]}
    return out

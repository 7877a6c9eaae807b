"""Not a metric: what the readers of the expert layers' stat rings share
(moe_live_tiles, moe_live_tiles_window, moe_gmm_tile_us,
moe_gmm_roofline_live).  The program keeps, a row a step and an expert
layer, what the step's routing gave this chip: moe_experts' `Load`
(the pairs routed to each held expert, their sum `routed`, and
`live_tiles`, the row tiles the grouped matmuls' grids ran) in the
stat ring `<layer>.load` (paddle_tpu/observability/step_stats.py; row i
is the i-th execution of the program's step).  A reader runs in the
process that ran the cell and reads the rings itself.

Which rows are whose, without the harness's help: of the step
program's `run` records (_step_window.py) those that fetched nothing
are the traced stretch, and the rings' LAST rows are theirs, because
nothing executes the step after the stretch (observe.step_program only
lowers).  The trace's steady window is N - 1 periods of N executions
(trace_reduce.reduce_device): the FIRST `steps` of the stretch; so
unless the traced records number steps + 1 the join is not known and
everything here gives None.  The rows before the stretch, the last
m["attempted"], are the measured window's.  A program without the
module (a parent from before the rings), a cell without an expert
layer, a run without a trace: None, and the metric is left out.
Load with runpy.run_path, as the readers of _step_window.py do.
"""

import os
import runpy

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_sw = runpy.run_path(os.path.join(_HERE, "_step_window.py"))
_nk = runpy.run_path(os.path.join(_HERE, "_named_kernels.py"))

GMM = ("pt_gmm_fwd", "pt_gmm_bwd_dx", "pt_gmm_bwd_dw")


def _loads():
    try:
        from paddle_tpu.observability import step_stats
    except ImportError:
        return []
    return [s for name, s in sorted(step_stats.read().items())
            if name.endswith(".load")]


def rows(m, which):
    """float array [expert layers, steps, columns] of the Load rows of
    `which`: "traced" (the steady steps of the traced stretch) or
    "window" (the measured window's steps); the last two columns are
    `routed` and `live_tiles`.  None where the join is not known."""
    if m.get("trace") is None or m.get("failed"):
        return None
    periods = m["trace"]["devices"][m["trace"]["first"]]["steps"]
    traced = [r for r in _sw["_step_runs"]()
              if not r["first_call"] and not r["fetched"]]
    loads = _loads()
    if not loads or len(traced) != periods + 1:
        return None
    n_t, n_w = len(traced), m["attempted"]
    if min(len(s["rows"]) for s in loads) < n_t + n_w:
        return None
    end = {"traced": (n_t, n_t - periods), "window": (n_t + n_w, n_t)}
    back, stop = end[which]
    return np.stack([s["rows"][len(s["rows"]) - back:
                               len(s["rows"]) - stop] for s in loads])


def live_tiles(m, which):
    """Mean over the steps and the expert layers of the live row tiles
    a layer."""
    r = rows(m, which)
    return None if r is None else float(r[:, :, -1].mean())


def gmm_ms(m):
    """moe_gmm_ms: device time a step of the grouped-matmul kernels."""
    return _nk["per_step_ms"](m, GMM)

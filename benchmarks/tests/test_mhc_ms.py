"""`mhc_ms` (PR 52): the reader on a trace with and without the
kernels' names, and its BENCHMARK.json entry, looked up BY NAME and
held with `<=`: a later PR appends after it and may append cells to it.
"""

import json
import os

import pytest

from conftest import BENCH, CHECKOUT

import harness

CELLS = {"xing4_29b_train_s4k"}


def _read(m):
    return harness._load_file(os.path.join(
        BENCH, "layer_metrics", "mhc_ms.py")).read(m)


def test_nothing_where_the_trace_has_no_such_kernel():
    """No trace (an untraced run, a CPU), and the parent's program,
    whose stream mixes are XLA's fusions: None, and nothing raised."""
    assert _read({"trace": None}) is None
    parent = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_gmm_fwd": 12e6, "fusion:multiply_reduce_fusion": 90e6,
        "copy:copy": 30e6}, "steps": 3}]}
    assert _read({"trace": parent}) is None


def test_a_made_up_trace():
    """4 steps, 10 + 6 + 20 + 12 ms of the four kernels: 12 ms a step;
    the combine and the grouped matmuls beside them do not count."""
    ops = {"mosaic:pt_mhc_pre_fwd": 10e6, "mosaic:pt_mhc_post_fwd": 6e6,
           "mosaic:pt_mhc_pre_bwd": 20e6, "mosaic:pt_mhc_post_bwd": 12e6,
           "mosaic:pt_moe_combine": 9e6, "mosaic:pt_gmm_fwd": 40e6}
    trace = {"first": 0, "devices": [{"op_ns": ops, "steps": 4}]}
    assert _read({"trace": trace}) == pytest.approx(12.0)


def test_benchmark_entry():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    e, = [e for e in spec["per_layer"] if e["name"] == "mhc_ms"]
    assert e == dict(e, unit="ms", better="lower", source="device_trace",
                     layer="kernels", moves="tokens_per_s")
    assert set(e) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    assert CELLS <= set(e["workloads"])
    known = {w["name"] for w in spec["workloads"]}
    rate = next(m for m in spec["end_to_end"]
                if m["name"] == "tokens_per_s")
    for cell in e["workloads"]:
        assert cell in known and cell in rate["workloads"]

"""`conv1d_ms` (PR 42): the reader on a trace with and without the two
kernel names, and its BENCHMARK.json entry, looked up BY NAME and held
with `<=`: a later PR appends after it and may append cells to it.
"""

import json
import os

import pytest

from conftest import BENCH, CHECKOUT

import harness

CELLS = {"granite4_h_micro_train_b1", "ling3_flash_train_s4k"}


def _read(m):
    return harness._load_file(os.path.join(
        BENCH, "layer_metrics", "conv1d_ms.py")).read(m)


def test_nothing_where_the_trace_has_no_such_kernel():
    """No trace (an untraced run, a CPU), and the parent's program,
    whose convolution is XLA's graph: None, and nothing raised."""
    assert _read({"trace": None}) is None
    parent = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_ssd_fwd": 60e6, "fusion:multiply_convert_fusion": 9e6},
        "steps": 3}]}
    assert _read({"trace": parent}) is None


def test_a_made_up_trace():
    """3 steps, 6 ms of pt_conv1d_fwd and 9 ms of pt_conv1d_bwd: 5 ms
    a step; one name alone is read alone; other kernels do not count."""
    ops = {"mosaic:pt_conv1d_fwd": 6e6, "mosaic:pt_conv1d_bwd": 9e6,
           "mosaic:pt_ssd_bwd": 40e6}
    trace = {"first": 0, "devices": [{"op_ns": ops, "steps": 3}]}
    assert _read({"trace": trace}) == pytest.approx(5.0)
    del ops["mosaic:pt_conv1d_bwd"]
    assert _read({"trace": trace}) == pytest.approx(2.0)


def test_benchmark_entry():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    e, = [e for e in spec["per_layer"] if e["name"] == "conv1d_ms"]
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

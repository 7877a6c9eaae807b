"""`rotary_ms` (PR 54): the reader on a trace with and without the
kernel's name, and its BENCHMARK.json entry, looked up BY NAME and held
with `<=`: a later PR appends after it and may append cells to it.
"""

import json
import os

import pytest

from conftest import BENCH, CHECKOUT

import harness

CELLS = {"xing4_29b_train_s4k", "ouro_2_6b_train_s4k",
         "dsv2_lite_train_s4k", "ling3_flash_train_s4k",
         "lfm2_24b_train_s8k", "mellum2_12b_train_s16k"}


def _read(m):
    return harness._load_file(os.path.join(
        BENCH, "layer_metrics", "rotary_ms.py")).read(m)


def test_nothing_where_the_trace_has_no_such_kernel():
    """No trace (an untraced run, a CPU), and the parent's program,
    whose rotation is XLA's fusions and copies: None, and nothing
    raised."""
    assert _read({"trace": None}) is None
    parent = {"first": 0, "devices": [{"op_ns": {
        "mosaic:pt_flash_win_fwd": 12e6, "fusion:fusion": 90e6,
        "copy:reshape": 30e6}, "steps": 3}]}
    assert _read({"trace": parent}) is None


def test_a_made_up_trace():
    """4 steps, 28 ms of the kernel's calls (forward, replay and
    backward carry one name): 7 ms a step; the flash kernels beside
    them do not count."""
    ops = {"mosaic:pt_rotary": 28e6, "mosaic:pt_flash_fwd": 40e6,
           "copy:reshape": 9e6}
    trace = {"first": 0, "devices": [{"op_ns": ops, "steps": 4}]}
    assert _read({"trace": trace}) == pytest.approx(7.0)


def test_benchmark_entry():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    e, = [e for e in spec["per_layer"] if e["name"] == "rotary_ms"]
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

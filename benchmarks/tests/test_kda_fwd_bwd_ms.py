"""`kda_fwd_ms` and `kda_bwd_ms` (PR 46): each reader on a trace with
and without its kernel's name, that the two add up to `kda_ms`, and
their BENCHMARK.json entries, looked up BY NAME and held with `<=`: a
later PR appends after them and may append cells to them.
"""

import json
import os

import pytest

from conftest import BENCH, CHECKOUT

import harness

CELL = "ling3_flash_train_s4k"
READERS = {"kda_fwd_ms": "pt_kda_fwd", "kda_bwd_ms": "pt_kda_bwd"}


def _read(name, m):
    return harness._load_file(os.path.join(
        BENCH, "layer_metrics", name + ".py")).read(m)


@pytest.mark.parametrize("name", sorted(READERS))
def test_nothing_where_the_trace_has_no_such_kernel(name):
    """No trace (an untraced run, a CPU), a cell without a KDA layer,
    and a trace that holds only the OTHER kernel: None, nothing
    raised."""
    assert _read(name, {"trace": None}) is None
    ops = {"mosaic:pt_ssd_fwd": 60e6, "fusion:fusion": 9e6}
    trace = {"first": 0, "devices": [{"op_ns": ops, "steps": 3}]}
    assert _read(name, {"trace": trace}) is None
    other, = set(READERS.values()) - {READERS[name]}
    ops["mosaic:" + other] = 30e6
    assert _read(name, {"trace": trace}) is None


def test_a_made_up_trace():
    """3 steps, 66 ms of pt_kda_fwd and 93 ms of pt_kda_bwd: 22 and 31
    ms a step, and kda_ms their sum; other kernels do not count."""
    ops = {"mosaic:pt_kda_fwd": 66e6, "mosaic:pt_kda_bwd": 93e6,
           "mosaic:pt_conv1d_bwd": 40e6}
    m = {"trace": {"first": 0, "devices": [{"op_ns": ops, "steps": 3}]}}
    assert _read("kda_fwd_ms", m) == pytest.approx(22.0)
    assert _read("kda_bwd_ms", m) == pytest.approx(31.0)
    assert _read("kda_ms", m) == pytest.approx(53.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_benchmark_entry(name):
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    e, = [e for e in spec["per_layer"] if e["name"] == name]
    assert e == dict(e, unit="ms", better="lower", source="device_trace",
                     layer="kernels", moves="tokens_per_s")
    assert set(e) == {"name", "unit", "better", "source", "layer",
                      "moves", "workloads"}
    assert CELL in e["workloads"]
    # where kda_ms is read, so are its two parts
    whole, = [x for x in spec["per_layer"] if x["name"] == "kda_ms"]
    assert set(whole["workloads"]) <= set(e["workloads"])
    known = {w["name"] for w in spec["workloads"]}
    rate = next(m for m in spec["end_to_end"]
                if m["name"] == "tokens_per_s")
    for cell in e["workloads"]:
        assert cell in known and cell in rate["workloads"]

"""`step_trace_s`, `step_lower_s`, `step_compile_s` (PR 51): the three
readers of the parts the program's step record keeps of the step
program's first call, on a ring with and without the fields and after
a real first call, and their BENCHMARK.json entries, looked up BY NAME
and held with `<=`: a later PR appends after them and may append cells.
"""

import json
import os

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT

import harness

PARTS = {"step_trace_s": "trace_ns", "step_lower_s": "lower_ns",
         "step_compile_s": "compile_ns"}
CELLS = {"tfm_base_train_s512", "tfm_base_train_s8k",
         "tfm_base_train_dp2tp2", "rn50_train_b256",
         "xing4_29b_train_s4k", "ouro_2_6b_train_s4k",
         "dsv2_lite_train_s4k", "granite4_h_micro_train_b1",
         "ling3_flash_train_s4k", "lfm2_24b_train_s8k",
         "solar_open2_train_s8k"}


def _read(name, m=None):
    return harness._load_file(os.path.join(
        BENCH, "layer_metrics", name + ".py")).read(m or {})


@pytest.fixture(autouse=True)
def _empty_ring():
    from paddle_tpu.observability import step_record

    step_record.clear()
    yield
    step_record.clear()


def _run_record(**fields):
    from paddle_tpu.observability import step_record

    rec = step_record.Record("run", program=1, fetched=True, **fields)
    for stamp in ("enter", "conformed", "dispatched"):
        rec.stamp(stamp)
    rec.done()


@pytest.mark.parametrize("name", sorted(PARTS))
def test_nothing_on_a_record_without_the_fields(name):
    """An empty ring, a ring without a first call, and the parent's
    first-call record, which holds the stamps and no parts: None, and
    nothing raised."""
    assert _read(name) is None
    _run_record(first_call=False)
    assert _read(name) is None
    _run_record(first_call=True)
    assert _read(name) is None


@pytest.mark.parametrize("name", sorted(PARTS))
def test_a_made_up_record(name):
    """The step program is the one with the most records; its first
    call's field, in seconds."""
    _run_record(first_call=True, trace_ns=1_500_000_000,
                lower_ns=250_000_000, compile_ns=4_000_000_000,
                cache_hit=True)
    for _ in range(3):
        _run_record(first_call=False)
    from paddle_tpu.observability import step_record

    other = step_record.Record("run", program=2, fetched=True,
                               first_call=True, trace_ns=7, lower_ns=7,
                               compile_ns=7)
    other.done()
    want = {"step_trace_s": 1.5, "step_lower_s": 0.25,
            "step_compile_s": 4.0}
    assert _read(name) == pytest.approx(want[name])


def test_a_real_first_call_sums_to_under_first_call_s():
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    # this directory's tests share one process's default programs
    harness._load_file(os.path.join(
        BENCH, "kinds", "train_steps.py"))._fresh_programs()
    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    for _ in range(3):
        exe.run(compiled, feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[loss])
    parts = [_read(name) for name in sorted(PARTS)]
    assert all(p is not None and p > 0 for p in parts)
    assert sum(parts) <= _read("first_call_s")


def test_benchmark_entries():
    spec = json.load(open(os.path.join(CHECKOUT, "BENCHMARK.json")))
    known = {w["name"] for w in spec["workloads"]}
    names = [e["name"] for e in spec["per_layer"]]
    for name in PARTS:
        e, = [e for e in spec["per_layer"] if e["name"] == name]
        assert e == dict(e, unit="s", better="lower",
                         source="program_counter",
                         layer="build and compile", moves="setup_s")
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert CELLS <= set(e["workloads"]) <= known
        # beside the whole they are parts of, which stays
        assert "first_call_s" in names and "compile_s" in names \
            and "build_s" in names
        assert os.path.exists(os.path.join(BENCH, "layer_metrics",
                                           name + ".py"))

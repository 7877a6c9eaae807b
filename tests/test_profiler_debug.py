"""Profiler, flags, NaN debug and graphviz tests (reference §5 aux
subsystems: profiler.py tests, FLAGS_check_nan_inf, debugger)."""

import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import debugger, layers, profiler


def _small_net():
    x = layers.data("x", shape=[4], dtype="float32")
    h = layers.fc(x, size=4, act="relu")
    return x, layers.mean(h)


def test_profiler_records_op_spans(tmp_path):
    x, out = _small_net()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    fluid.set_flags({"profile_ops": True})
    trace_path = str(tmp_path / "trace.json")
    try:
        with profiler.profiler(sorted_key="total",
                               profile_path=trace_path):
            exe.run(feed={"x": np.ones((2, 4), np.float32)},
                    fetch_list=[out])
    finally:
        fluid.set_flags({"profile_ops": False})
    trace = json.load(open(trace_path))
    names = {e["name"] for e in trace["traceEvents"]}
    assert "mul" in names or "matmul" in names, names


def test_check_nan_inf_flag():
    x = layers.data("x", shape=[2], dtype="float32")
    out = layers.mean(layers.log(x))      # log(negative) -> NaN
    exe = fluid.Executor()
    fluid.set_flags({"check_nan_inf": True})
    try:
        with pytest.raises(FloatingPointError) as ei:
            exe.run(feed={"x": np.array([[-1.0, -2.0]], np.float32)},
                    fetch_list=[out])
        assert "log" in str(ei.value)
    finally:
        fluid.set_flags({"check_nan_inf": False})


def test_flags_env_and_types():
    from paddle_tpu import flags

    assert flags.get_flag("check_nan_inf") is False
    fluid.set_flags({"check_nan_inf": True})
    assert flags.get_flag("check_nan_inf") is True
    fluid.set_flags({"check_nan_inf": False})
    with pytest.raises(KeyError):
        fluid.set_flags({"no_such_flag": 1})
    assert "benchmark" in flags.all_flags()


@pytest.mark.parametrize("name", ["flash_packed_stats", "flash_head_pack",
                                  "flash_relayout"])
def test_removed_flash_flags_are_unknown(name):
    """The flash layout flags went with the paths they chose (PR 44):
    an operator's stale setting is told, not swallowed."""
    from paddle_tpu import flags

    assert name not in flags.all_flags()
    with pytest.raises(KeyError, match=name):
        fluid.set_flags({name: "on"})


def test_draw_program_dot(tmp_path):
    x, out = _small_net()
    path = str(tmp_path / "prog.dot")
    dot = debugger.draw_program(fluid.default_main_program(), path)
    assert os.path.exists(path)
    assert dot.startswith("digraph G {")
    assert '"mul"' in dot or '"matmul"' in dot
    assert "->" in dot
    # persistable params highlighted
    assert "lightblue" in dot


def test_device_trace_smoke(tmp_path):
    import jax

    x, out = _small_net()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    logdir = str(tmp_path / "xla_trace")
    with profiler.device_trace(logdir):
        exe.run(feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=[out])
    assert os.path.exists(logdir)

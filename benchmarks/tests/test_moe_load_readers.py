"""The readers PR 36 added (layer_metrics/moe_live_tiles,
moe_live_tiles_window, moe_gmm_tile_us, moe_gmm_roofline_live, and
what they share, _moe_load.py): each on a made-up measurement over a
ring that a real program filled, a row a step, on the CPU: the join to
the traced stretch and to the window, None when the join is not known,
None without the program's module, and the roofline against a hand
count at one shape.  Nothing here is a measurement."""

import json
import os
import sys

import numpy as np
import pytest

from conftest import BENCH, CHECKOUT

NEW = ["moe_live_tiles", "moe_live_tiles_window", "moe_gmm_tile_us",
       "moe_gmm_roofline_live"]
CELLS = ["xing4_29b_train_s4k", "dsv2_lite_train_s4k"]
COLUMNS = ["expert_0", "expert_1", "expert_2", "routed", "live_tiles"]
WARM, WINDOW, TRACED = 5, 4, 3
HIDDEN, WIDTH, HELD = 64, 32, 3
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _reader(name):
    import harness

    return harness._load_file(
        os.path.join(BENCH, "layer_metrics", name + ".py"))


def _row(layer, step):
    """What layer `layer` (0, 1) reports at step `step`: sizes that sum
    to `routed`, and a tile count no other step or layer has."""
    sizes = [100 * (layer + 1) + step, 7 * step, 3]
    return sizes + [sum(sizes), 10 * (layer + 1) + step]


@pytest.fixture
def run():
    """A program with two `<layer>.load` stats, stepped as the
    `train_steps` loop steps its own: a first call and four more
    warm-up steps, a window of four fetched steps, a traced stretch of
    three unfetched ones.  Returns the made-up measurement."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer
    from paddle_tpu.observability import step_record

    sys.path.insert(0, os.path.join(BENCH, "kinds"))
    import train_steps

    train_steps._fresh_programs()
    step_record.clear()
    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    feeds = {"x": np.ones((1, 4), np.float32)}
    for layer in (0, 1):
        v = layers.data("load%d" % layer, shape=[5], dtype="float32")
        layers.step_stat("l%d_experts.load" % layer,
                         layers.reduce_sum(v, dim=0), COLUMNS)
    # a stat that is no expert layer's: not the readers' business
    layers.step_stat("other", layers.reduce_sum(x, dim=0))
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    for step in range(WARM + WINDOW + TRACED):
        for layer in (0, 1):
            feeds["load%d" % layer] = np.asarray(
                [_row(layer, step)], np.float32)
        exe.run(compiled, feed=feeds, fetch_list=[loss],
                return_numpy=step < WARM + WINDOW)
    ms = 3.0
    m = {"attempted": WINDOW, "failed": 0, "chips": 1, "peaks": PEAKS,
         "config": {"hidden_size": HIDDEN, "moe_intermediate_size": WIDTH,
                    "n_routed_experts": HELD},
         "flops": _flops(),
         "trace": {"first": 0, "devices": [{
             "steps": TRACED - 1,
             "op_ns": {"mosaic:pt_gmm_fwd": 1.5e6 * (TRACED - 1),
                       "mosaic:pt_gmm_bwd_dx": 0.5e6 * (TRACED - 1),
                       "mosaic:pt_gmm_bwd_dw": 1.0e6 * (TRACED - 1),
                       "mosaic:pt_flash_fwd": 9e6}}]}}
    yield m, ms
    step_record.clear()


def _flops():
    import harness

    return harness._load_file(os.path.join(BENCH, "flops.py"))


def test_the_rows_join_the_traced_stretch_and_the_window(run):
    m, ms = run
    first_traced = WARM + WINDOW
    steady = range(first_traced, first_traced + TRACED - 1)
    window = range(WARM, WARM + WINDOW)
    want = np.mean([_row(la, s)[-1] for la in (0, 1) for s in steady])
    assert _reader("moe_live_tiles").read(m) == pytest.approx(want)
    want_w = np.mean([_row(la, s)[-1] for la in (0, 1) for s in window])
    assert _reader("moe_live_tiles_window").read(m) == pytest.approx(want_w)
    assert want != want_w
    # the kernels' time over the tiles of a step, all layers
    tiles_a_step = np.mean([_row(0, s)[-1] + _row(1, s)[-1]
                            for s in steady])
    assert _reader("moe_gmm_tile_us").read(m) == pytest.approx(
        ms * 1e3 / tiles_a_step)


def test_roofline_of_the_rows_that_came_against_a_hand_count(run):
    m, ms = run
    first_traced = WARM + WINDOW
    least = []
    for step in (first_traced, first_traced + 1):
        flops = nbytes = 0.0
        for layer in (0, 1):
            rows = _row(layer, step)[-2]
            # gate, up, down: 2 rows hidden width each, forward; twice
            # that backward (the rows' and the weights' gradient)
            flops += 3 * (2 * rows * HIDDEN * WIDTH) * 3
            weights = 3 * HELD * HIDDEN * WIDTH
            # forward: rows in, rows out, weights; backward: rows, their
            # output gradients, input gradients, weights and their
            # gradients; bf16
            nbytes += 2 * ((2 * rows * HIDDEN + weights)
                           + (3 * rows * HIDDEN + 2 * weights))
        least.append(max(flops / PEAKS["bf16_flops_per_s"],
                         nbytes / PEAKS["hbm_bytes_per_s"]))
    want = np.mean(least) * 1e3 / ms * 100
    got = _reader("moe_gmm_roofline_live").read(m)
    assert got == pytest.approx(want, rel=1e-9)
    assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_none_where_the_join_is_not_known(run, name):
    m, _ = run
    reader = _reader(name)
    assert reader.read(m) is not None
    for periods in (TRACED, TRACED - 2):
        # the trace saw another number of executions than the record
        off = dict(m, trace={"first": 0, "devices": [dict(
            m["trace"]["devices"][0], steps=periods)]})
        assert reader.read(off) is None
    assert reader.read(dict(m, trace=None)) is None
    assert reader.read(dict(m, failed=1)) is None
    # more window steps than the ring holds rows before the stretch
    assert reader.read(dict(m, attempted=WARM + WINDOW + 1)) is None
    if name in ("moe_gmm_tile_us", "moe_gmm_roofline_live"):
        bare = dict(m, trace={"first": 0, "devices": [dict(
            m["trace"]["devices"][0], op_ns={"mosaic:pt_flash_fwd": 1})]})
        assert reader.read(bare) is None


@pytest.mark.parametrize("name", NEW)
def test_none_without_the_programs_module(run, name, monkeypatch):
    """The parent's tree has no observability/step_stats.py: the driver
    lays these readers over it, and each leaves its metric out."""
    import paddle_tpu.observability as obs

    m, _ = run
    monkeypatch.delattr(obs, "step_stats")
    monkeypatch.setitem(sys.modules,
                        "paddle_tpu.observability.step_stats", None)
    with pytest.raises(ImportError):
        from paddle_tpu.observability import step_stats  # noqa: F401
    assert _reader(name).read(m) is None


@pytest.mark.parametrize("name", NEW)
def test_none_in_a_program_without_an_expert_layer(name):
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer
    from paddle_tpu.observability import step_record

    sys.path.insert(0, os.path.join(BENCH, "kinds"))
    import train_steps

    train_steps._fresh_programs()
    step_record.clear()
    x = layers.data("x", shape=[4], dtype="float32")
    loss = layers.mean(layers.fc(x, size=3))
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    for step in range(6):
        exe.run(compiled, feed={"x": np.ones((1, 4), np.float32)},
                fetch_list=[loss], return_numpy=step < 3)
    m = {"attempted": 2, "failed": 0, "trace": {"first": 0, "devices": [
        {"steps": 2, "op_ns": {"mosaic:pt_gmm_fwd": 1e6}}]}}
    assert _reader(name).read(m) is None
    step_record.clear()


def test_the_new_entries_resolve_and_name_the_expert_cells_only():
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    by_name = {e["name"]: e for e in spec["per_layer"]}
    assert [e["name"] for e in spec["per_layer"]][-4:] == NEW
    want = {"moe_live_tiles": ("tiles", "lower", "program_counter"),
            "moe_live_tiles_window": ("tiles", "lower", "program_counter"),
            "moe_gmm_tile_us": ("us", "lower", "device_trace"),
            "moe_gmm_roofline_live": ("%", "higher", "device_trace")}
    for name in NEW:
        e = by_name[name]
        assert (e["unit"], e["better"], e["source"]) == want[name]
        assert (e["layer"], e["moves"]) == ("kernels", "tokens_per_s")
        assert e["workloads"] == CELLS
        assert callable(_reader(name).read)
    # what they stand beside stays
    assert by_name["moe_gmm_ms"]["workloads"] == CELLS
    assert by_name["moe_gmm_roofline"]["workloads"] == CELLS

"""The stat ring (layers.step_stat, ops/metrics.py `step_stat`,
observability/step_stats.py) and its first user, moe_experts' `Load`
(ISSUE 36).  What a compiled step says of itself: one row a step in
the program's own state, whoever reads it, or nobody."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as fluid
from paddle_tpu import framework, layers, optimizer, unique_name
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.core.program import STAT, Program
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.core.scope import global_scope
from paddle_tpu.observability import step_stats


def _fresh():
    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _tiny(stat=True):
    """loss = mean(fc(x)); the stat is the batch sum of fc's output."""
    x = layers.data("x", shape=[4], dtype="float32")
    h = layers.fc(x, size=3)
    loss = layers.mean(h)
    if stat:
        layers.step_stat("h.sum", layers.reduce_sum(h, dim=0),
                         ["a", "b", "c"])
    return x, h, loss


def _feed(i, batch=2):
    return {"x": np.full((batch, 4), i + 1, np.float32)}


# -- the mechanism ------------------------------------------------------------

def test_rows_in_step_order_and_the_ring_wraps_at_k(monkeypatch):
    monkeypatch.setattr(step_stats, "K", 8)
    _, h, loss = _tiny()
    optimizer.SGD(0.1).minimize(loss)
    program = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    assert step_stats.read()["h.sum"]["rows"].shape == (0, 3)
    compiled = fluid.CompiledProgram(program)
    sums = []
    for i in range(11):
        hv, = exe.run(compiled, feed=_feed(i), fetch_list=[h])
        sums.append(hv.sum(0))
        if i == 4:
            got = step_stats.read()["h.sum"]
            assert got["columns"] == ("a", "b", "c")
            assert got["steps"].tolist() == [0, 1, 2, 3, 4]
            np.testing.assert_allclose(got["rows"], sums, rtol=1e-6)
    got = step_stats.read(compiled)["h.sum"]
    assert got["steps"].tolist() == list(range(3, 11))
    np.testing.assert_allclose(got["rows"], sums[3:], rtol=1e-6)
    # one ring a stat, one counter a program, no other state
    extra = [v.name for v in program.persistables()
             if v.name.startswith("step_stat")]
    assert sorted(extra) == ["step_stat.h.sum", "step_stat_step_0"]
    assert global_scope().find_var("step_stat.h.sum").get().shape == (8, 3)


def test_read_compiles_nothing_and_reads_the_same_rows_twice():
    import jax.monitoring as mon

    _, _, loss = _tiny()
    optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    for i in range(3):
        exe.run(compiled, feed=_feed(i), fetch_list=[loss])
    compiles = []
    mon.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    first, second = step_stats.read(), step_stats.read()
    assert not compiles
    assert first["h.sum"]["steps"].tolist() == [0, 1, 2]
    np.testing.assert_array_equal(first["h.sum"]["rows"],
                                  second["h.sum"]["rows"])
    # and the step after a read is the step it would have been
    exe.run(compiled, feed=_feed(3), fetch_list=[loss])
    assert step_stats.read()["h.sum"]["steps"].tolist() == [0, 1, 2, 3]
    assert not compiles
    assert step_stats.K == 4096
    assert first["h.sum"]["rows"].dtype == np.float32


def test_two_stats_share_the_programs_counter_and_names_are_unique():
    _, h, loss = _tiny()
    layers.step_stat("h.max", layers.reduce_max(h, dim=0))
    with pytest.raises(ValueError, match="has one already"):
        layers.step_stat("h.max", layers.reduce_max(h, dim=0))
    with pytest.raises(ValueError, match="static width"):
        layers.step_stat("h.whole", h)
    with pytest.raises(ValueError, match="columns"):
        layers.step_stat("h.min", layers.reduce_min(h, dim=0), ["one"])
    ops = fluid.default_main_program().global_block().ops
    stats = [op for op in ops if op.type == "step_stat"]
    assert len(stats) == 2 and stats[1].attrs["columns"] == ["0", "1", "2"]
    assert len({op.inputs["Step"][0] for op in stats}) == 1
    assert [op.type for op in ops if op.op_role == STAT] == [
        "increment", "step_stat", "step_stat"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    exe.run(feed=_feed(0), fetch_list=[loss])        # the interpreter too
    got = step_stats.read()
    assert got["h.sum"]["steps"].tolist() == got["h.max"]["steps"].tolist() \
        == [0]
    # another scope holds none of it
    assert step_stats.read(scope=scope_mod.Scope()) == {}


def test_for_test_clone_drops_the_stat_ops():
    """An evaluation program that shares the scope leaves the ring and
    the step index alone: clone(for_test=True) drops the role `stat`
    as it drops backward and optimize."""
    _, _, loss = _tiny()
    test_program = fluid.default_main_program().clone(for_test=True)
    optimizer.SGD(0.1).minimize(loss)
    assert not [op for op in test_program.global_block().ops
                if op.op_role == STAT or op.type == "step_stat"]
    assert step_stats.read(test_program) == {}
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    train = fluid.CompiledProgram(fluid.default_main_program())
    evaluate = fluid.CompiledProgram(test_program)
    exe.run(train, feed=_feed(0), fetch_list=[loss])
    exe.run(evaluate, feed=_feed(5), fetch_list=[loss.name])
    exe.run(evaluate, feed=_feed(6), fetch_list=[loss.name])
    exe.run(train, feed=_feed(1), fetch_list=[loss])
    assert step_stats.read()["h.sum"]["steps"].tolist() == [0, 1]
    # a full clone keeps them
    kept = fluid.default_main_program().clone()
    assert [op.op_role for op in kept.global_block().ops
            if op.type == "step_stat"] == [STAT]


def test_on_a_2x2_mesh_the_ring_is_replicated_and_so_is_the_row():
    from paddle_tpu.flags import set_flags
    from paddle_tpu.parallel import env as penv
    from paddle_tpu.parallel.gspmd import MeshPlan
    from paddle_tpu.transpiler import shard_program

    rows = {}
    try:
        for sharded in (False, True):
            _fresh()
            penv.reset()
            set_flags({"gspmd": sharded})
            np.random.seed(3)
            x = layers.data("x", shape=[64], dtype="float32")
            h = layers.fc(layers.fc(x, size=128, act="relu"), size=6)
            loss = layers.mean(h)
            layers.step_stat("h.sum", layers.reduce_sum(h, dim=0))
            optimizer.Adam(1e-3).minimize(loss)
            compiled = fluid.CompiledProgram(fluid.default_main_program())
            if sharded:
                compiled = shard_program(
                    compiled, MeshPlan(dp=2, tp=2), loss_name=loss.name,
                    min_size=256, devices=jax.devices()[:4])
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            rng = np.random.RandomState(0)
            for _ in range(3):
                exe.run(compiled, feed={"x": rng.randn(8, 64).astype(
                    np.float32)}, fetch_list=[loss])
            rows[sharded] = step_stats.read()["h.sum"]
            if sharded:
                # the batch was split over dp and the weights over tp
                # and dp; the ring and the counter are whole everywhere
                w = global_scope().find_var("fc_0.w_0").get()
                assert not w.sharding.is_fully_replicated
                for name in ("step_stat.h.sum", "step_stat_step_0"):
                    v = global_scope().find_var(name).get()
                    assert len(v.sharding.device_set) == 4
                    assert v.sharding.is_fully_replicated, name
    finally:
        set_flags({"gspmd": False})
        penv.reset()
    assert rows[True]["steps"].tolist() == [0, 1, 2]
    np.testing.assert_allclose(rows[True]["rows"], rows[False]["rows"],
                               rtol=1e-4, atol=1e-5)


# -- moe_experts' Load --------------------------------------------------------

@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_load_is_a_count_of_the_ids_and_its_last_entry_n_active(impl):
    from paddle_tpu.ops.llm_ops import _group_layout

    rng = np.random.default_rng(5)
    n, k, c, w, tm = 40, 3, 32, 16, 16
    held = [6, 1, 3]
    # top-k ids are distinct a token
    idx = np.stack([rng.permutation(8)[:k] for _ in range(n)]).astype(
        np.int32)
    experts = get_op_def("moe_experts")
    outs = experts.compute(
        {"X": jnp.asarray(rng.normal(0, 1, (n, c)), jnp.float32),
         "TopkIdx": jnp.asarray(idx),
         "TopkWeight": jnp.asarray(rng.random((n, k)), jnp.float32),
         "WGate": jnp.asarray(rng.normal(0, .1, (3, c, w)), jnp.float32),
         "WUp": jnp.asarray(rng.normal(0, .1, (3, c, w)), jnp.float32),
         "WDown": jnp.asarray(rng.normal(0, .1, (3, w, c)), jnp.float32)},
        experts.canonical_attrs({"held": held, "block_m": tm,
                                 "impl": impl}))
    load = np.asarray(outs["Load"])
    assert load.dtype == np.float32 and load.shape == (len(held) + 2,)
    sizes = np.array([(idx == e).sum() for e in held])
    assert load[:3].tolist() == sizes.tolist()
    assert load[3] == sizes.sum()
    assert load[4] == np.maximum(-(-sizes // tm), 1).sum()
    lay = _group_layout(jnp.asarray(idx), tuple(held), tm)
    assert load[4] == int(lay["n_active"][0])
    assert load[:3].tolist() == np.asarray(lay["sizes"]).tolist()
    # nobody routed here: one (empty) tile a group still
    nobody = experts.compute(
        {"X": jnp.zeros((4, c)), "TopkIdx": jnp.full((4, 1), 7, jnp.int32),
         "TopkWeight": jnp.ones((4, 1)),
         "WGate": jnp.zeros((3, c, w)), "WUp": jnp.zeros((3, c, w)),
         "WDown": jnp.zeros((3, w, c))},
        experts.canonical_attrs({"held": held, "block_m": tm,
                                 "impl": impl}))
    assert np.asarray(nobody["Load"]).tolist() == [0, 0, 0, 0, 3]


def _expert_program(amp, recompute, impl, held=(0, 2, 3), k=2, tm=8):
    """x -> fc -> [router -> experts -> + residual] -> loss; the bracket
    is one recompute segment.  Returns (loss, TopkIdx var)."""
    x = layers.data("x", shape=[8, 16], dtype="float32")
    u = layers.fc(x, size=16, num_flatten_dims=2)
    idx, gate = layers.moe_route(u, n_experts=4, k=k, name="l0_router")
    y = layers.moe_experts(u, idx, gate, held=list(held), width=32,
                           block_m=tm, impl=impl, name="l0_experts")
    out = layers.elementwise_add(u, y)
    loss = layers.mean(layers.square(out))
    opt = optimizer.Adam(1e-2)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints([u, out])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(loss)
    return loss, idx


@pytest.mark.parametrize("amp,recompute,impl", [
    (False, False, "xla"), (False, True, "xla"), (True, False, "xla"),
    (True, True, "xla"), (True, True, "interpret")])
def test_one_row_a_step_with_the_backward(amp, recompute, impl):
    """Forward + backward + update, in and out of a recompute segment,
    under AMP: the ring advances one row a step and the row is the
    count of the ids the router gave THAT step."""
    held, tm = (0, 2, 3), 8
    np.random.seed(1)
    loss, idx = _expert_program(amp, recompute, impl, held, tm=tm)
    program = fluid.default_main_program()
    block = program.global_block()
    if recompute:
        # the segment's replay holds the experts and not the stat ops
        replayed = [d["type"] for op in block.ops
                    if op.type == "recompute_segment_grad"
                    for d in op.attrs["ops"]]
        assert "moe_experts" in replayed
        assert not {"step_stat", "increment"} & set(replayed)
    load_var = [op for op in block.ops
                if op.type == "moe_experts"][0].outputs["Load"][0]
    assert block.var(load_var).dtype == "float32"
    assert not [op for op in block.ops if op.type == "cast"
                and load_var in op.input_names()]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(program)
    rng = np.random.default_rng(2)
    want = []
    for _ in range(4):
        _, ids = exe.run(compiled, feed={"x": rng.normal(
            0, 1, (2, 8, 16)).astype(np.float32)}, fetch_list=[loss, idx])
        sizes = np.array([(ids == e).sum() for e in held])
        want.append(sizes.tolist() + [sizes.sum(), np.maximum(
            -(-sizes // tm), 1).sum()])
    got = step_stats.read()
    assert list(got) == ["l0_experts.load"]
    got = got["l0_experts.load"]
    assert got["columns"] == ("expert_0", "expert_2", "expert_3", "routed",
                              "live_tiles")
    assert got["steps"].tolist() == [0, 1, 2, 3]
    assert got["rows"].tolist() == want
    assert len({tuple(r) for r in want}) > 1     # the steps differ


# -- the math of the step does not change -------------------------------------

def _losses(model_fn, config, amp, monkeypatch, stat):
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.layers import nn

    _fresh()
    if not stat:
        monkeypatch.setattr(nn, "step_stat", lambda *a, **kw: None)
    np.random.seed(0)
    model = model_fn(dict(config), seq_len=32)
    opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-3))
    opt._set_checkpoints(model["checkpoints"])
    if amp:
        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    program = fluid.default_main_program()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ids = np.random.default_rng(0).integers(0, 128, (2, 32, 1),
                                            dtype=np.int64)
    compiled = fluid.CompiledProgram(program)
    losses = [float(np.asarray(exe.run(
        compiled, feed={"src_ids": ids, "tgt_label": np.roll(ids, -1, 1)},
        fetch_list=[model["loss"]])[0]).reshape(-1)[0]) for _ in range(3)]
    monkeypatch.undo()
    return losses, [(op.type, op.op_role)
                    for op in program.global_block().ops], step_stats.read()


@pytest.mark.parametrize("model,amp", [
    ("xing4", False), ("xing4", True), ("dsv2", False), ("dsv2", True)])
def test_the_models_losses_are_those_of_the_step_without_the_ring(
        model, amp, monkeypatch):
    """`xing4` and `dsv2` get a stat an expert layer without a line of
    their own; the first three losses (recompute, Adam, with and
    without AMP) are those of the same program built with
    `step_stat` a no-op, TO THE LAST BIT, and the ring holds what each
    layer's router sent."""
    if model == "xing4":
        from test_xing4_model import SMALL
        from paddle_tpu.models.xing4 import xing4_model as fn
    else:
        from test_deepseek_v2_model import SMALL
        from paddle_tpu.models.deepseek_v2 import deepseek_v2_model as fn
    with_ring, ops, stats = _losses(fn, SMALL, amp, monkeypatch, True)
    without, old_ops, none = _losses(fn, SMALL, amp, monkeypatch, False)
    assert [x.hex() for x in with_ring] == [x.hex() for x in without]
    assert with_ring[2] < with_ring[0]
    assert none == {}
    # the same ops, and one increment and a step_stat a layer among them
    n_layers = ops.count(("moe_experts", "forward"))
    assert n_layers >= 2 and len(stats) == n_layers
    assert [o for o in ops if o[1] != STAT] == old_ops
    assert sorted(o[0] for o in ops if o[1] == STAT) == \
        ["increment"] + ["step_stat"] * n_layers
    k = SMALL["num_experts_per_tok"]
    for name, got in stats.items():
        assert name.endswith(".load")
        assert got["steps"].tolist() == [0, 1, 2]
        rows = got["rows"]
        held = len(got["columns"]) - 2
        np.testing.assert_array_equal(rows[:, :held].sum(1), rows[:, held])
        assert (rows[:, held] <= 2 * 32 * k).all()
        assert (rows[:, held + 1] >= held).all()

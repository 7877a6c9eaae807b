"""causal_conv1d's Pallas kernels (ops/pallas_conv1d.py: pt_conv1d_fwd,
pt_conv1d_bwd) in interpret mode against the op's XLA graph: forward
and every gradient at both cells' channel counts (granite-4.0-h-micro
4,352 with a bias, ling-3.0-flash-vl 4,096 without; K 4), cut in T
only.  T spans three row tiles of two chunks, so the halo before a
tile (x) and the halo after it (dz) are crossed between tiles and
between chunks; B is 2, so a row that leaked across a batch start would
show."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import pallas_conv1d
from paddle_tpu.ops import pallas_kernels as pk

K = 4
GEOMETRIES = {"granite4_c4352_bias": (4352, True),
              "ling3_c4096_no_bias": (4096, False)}
T = 384         # three row tiles of 128, two chunks of 64 each
# float32 operands: rounding of the sums only; bfloat16: one rounding
# of y or dx to bfloat16 (2^-8 relative) on values of a few units
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(c, bias, dtype, t=T, b=2, seed=0):
    r = np.random.RandomState(seed)
    ins = {"X": jnp.asarray(r.randn(b, t, c), dtype),
           "W": jnp.asarray(r.uniform(-.5, .5, (c, K)), jnp.float32)}
    if bias:
        ins["Bias"] = jnp.asarray(r.uniform(-.5, .5, (c,)), jnp.float32)
    return ins, jnp.asarray(r.randn(b, t, c), dtype)


def _run(ins, impl, act="silu"):
    return get_op_def("causal_conv1d").compute(
        ins, {"activation": act, "impl": impl})["Y"]


def _grads(ins, gy, impl, act="silu"):
    return get_op_def("causal_conv1d_grad").compute(
        dict(ins, **{"Y@GRAD": gy}), {"activation": act, "impl": impl})


def _close(got, want, tol):
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) <= tol * max(1.0, np.max(np.abs(want)))


def _counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


def test_the_tile_rule():
    # both cells: 2,048 rows of 256 lanes (4,352 = 17 x 256)
    assert pallas_conv1d.tiles(8192, 4352, 4) == (2048, 256)
    assert pallas_conv1d.tiles(4096, 4096, 4) == (2048, 256)
    assert pallas_conv1d.tiles(T, 4352, K) == (128, 256)
    # what the kernels cannot tile: the XLA graph runs
    assert pallas_conv1d.tiles(T, 192, K) is None
    assert pallas_conv1d.tiles(40, 256, K) is None
    assert pallas_conv1d.tiles(T, 256, 9) is None


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_forward_matches_the_xla_graph(geometry, dtype):
    c, bias = GEOMETRIES[geometry]
    ins, _ = _operands(c, bias, dtype)
    before = _counts()
    got = _run(ins, "interpret")
    assert _since(before) == {("causal_conv1d", "interpret"): 1}
    assert got.dtype == jnp.dtype(dtype)
    _close(got, _run(ins, "xla"), TOL[dtype])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_gradients_match_the_xla_graph_s(geometry, dtype):
    """dX, dW and dBias (none without a bias) of the grad op, and dX
    and dW again through jax.vjp of the forward op: what a recompute
    segment's replay differentiates."""
    c, bias = GEOMETRIES[geometry]
    ins, gy = _operands(c, bias, dtype)
    before = _counts()
    got = _grads(ins, gy, "interpret")
    assert _since(before) == {("causal_conv1d_grad", "interpret"): 1}
    want = _grads(ins, gy, "xla")
    assert sorted(got) == sorted(want) == sorted(
        s + "@GRAD" for s in ins)
    assert got["X@GRAD"].dtype == jnp.dtype(dtype)
    for slot in want:
        tol = TOL[dtype] if slot == "X@GRAD" else TOL["float32"]
        _close(got[slot], want[slot], tol)

    def loss(x, w):
        y = _run(dict(ins, X=x, W=w), "interpret")
        return jnp.sum(y.astype(jnp.float32) * gy.astype(jnp.float32))

    dx, dw = jax.grad(loss, (0, 1))(ins["X"], ins["W"])
    _close(dx, want["X@GRAD"], TOL[dtype])
    _close(dw, want["W@GRAD"], TOL["float32"])


@pytest.mark.parametrize("act", ["silu", ""])
def test_no_activation_and_a_narrow_block(act):
    """C 384 takes 128-lane blocks; activation '' skips z's second
    forming in the backward."""
    ins, gy = _operands(384, True, "float32", t=96)
    _close(_run(ins, "interpret", act), _run(ins, "xla", act),
           TOL["float32"])
    got, want = (_grads(ins, gy, impl, act)
                 for impl in ("interpret", "xla"))
    for slot in want:
        _close(got[slot], want[slot], TOL["float32"])


@pytest.mark.parametrize("tile", [0, 1, 2])
def test_a_tile_s_first_rows_reach_only_through_the_halo(tile):
    """X is zero but for the K - 1 rows before row tile `tile` + 1 (the
    last rows of tile `tile`) in batch 0: Y's first K - 1 rows of the
    next tile come through the halo alone; nothing reaches batch 1,
    whose first rows follow batch 0's last in memory."""
    c, tt = 256, 128
    ins, gy = _operands(c, False, "float32")
    r = np.random.RandomState(1)
    x = np.zeros((2, T, c), np.float32)
    x[0, (tile + 1) * tt - (K - 1):(tile + 1) * tt] = r.randn(K - 1, c)
    ins["X"] = jnp.asarray(x)
    ins["W"] = jnp.abs(ins["W"]) + 0.1
    got = np.asarray(_run(ins, "interpret", ""))
    np.testing.assert_allclose(got, _run(ins, "xla", ""), rtol=1e-6,
                               atol=1e-6)
    after = got[0, (tile + 1) * tt:(tile + 1) * tt + K - 1] \
        if tile < 2 else got[1, :K - 1]
    if tile < 2:
        assert np.all(np.abs(after) > 0)
    else:       # the last tile's last rows: the next rows are batch 1's
        assert not after.any()
    assert not got[1].any()
    # and the gradient of those rows comes from the K - 1 rows after
    # them: the halo the backward carries
    g = np.zeros((2, T, c), np.float32)
    if tile < 2:
        g[0, (tile + 1) * tt:(tile + 1) * tt + K - 1] = 1.0
    grads = [_grads(ins, jnp.asarray(g), impl, "")["X@GRAD"]
             for impl in ("interpret", "xla")]
    np.testing.assert_allclose(*grads, rtol=1e-6, atol=1e-6)
    before_tile = np.asarray(grads[0])[0, (tile + 1) * tt - (K - 1):
                                       (tile + 1) * tt]
    assert np.all(before_tile > 0) == (tile < 2)


@pytest.mark.parametrize("case,shape", [("c192", (2, 64, 192)),
                                        ("t40", (2, 40, 256))])
def test_what_the_kernels_cannot_tile_runs_the_xla_graph(case, shape):
    """Asked for by name or not: the op chooses by what it reads, and
    the counter says what ran."""
    b, t, c = shape
    ins, gy = _operands(c, True, "float32", t=t, b=b)
    before = _counts()
    got = _run(ins, "interpret")
    grads = _grads(ins, gy, "interpret")
    assert _since(before) == {("causal_conv1d", "xla"): 1,
                              ("causal_conv1d_grad", "xla"): 1}
    np.testing.assert_array_equal(got, _run(ins, "xla"))
    np.testing.assert_array_equal(grads["X@GRAD"],
                                  _grads(ins, gy, "xla")["X@GRAD"])


def test_off_the_chip_the_default_is_the_xla_graph():
    ins, _ = _operands(256, True, "float32", t=64)
    before = _counts()
    _run(ins, "")
    assert _since(before) == {("causal_conv1d", "xla"): 1}


def test_the_grad_op_reads_no_forward_output():
    """append_backward binds X, W, Bias and Y@GRAD: no Y, so a
    recompute segment has nothing to keep for it."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    grad_def = get_op_def("causal_conv1d_grad")
    assert grad_def.inputs == ("X", "W", "Bias", "Y@GRAD")
    assert grad_def.reads_saved is None
    x = layers.data("x", shape=[32, 128], dtype="float32")
    y = layers.causal_conv1d(layers.fc(x, 128, num_flatten_dims=2), K,
                             name="conv")
    optimizer.SGD(0.1).minimize(layers.mean(y))
    block = fluid.default_main_program().global_block()
    fwd, = [op for op in block.ops if op.type == "causal_conv1d"]
    grad, = [op for op in block.ops if op.type == "causal_conv1d_grad"]
    assert sorted(grad.inputs) == ["Bias", "W", "X", "Y@GRAD"]
    assert fwd.outputs["Y"][0] not in sum(grad.inputs.values(), [])
    assert fwd.attrs.get("impl", "") == ""


@pytest.mark.parametrize("recompute", [False, True])
def test_a_program_trains_through_the_kernels(recompute):
    """fc -> causal_conv1d -> fc -> loss through Executor.run, the
    kernels in interpret mode against the XLA graph: the grad op on the
    plain path, jax.vjp of the op inside a recompute segment."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, layers, optimizer, unique_name
    from paddle_tpu.core.program import Program
    from paddle_tpu.core.scope import Scope, scope_guard

    feed = {"x": np.random.RandomState(3).randn(2, 64, 32).astype(
        np.float32)}
    losses = {}
    for impl in ("xla", "interpret"):
        framework.switch_main_program(Program())
        framework.switch_startup_program(Program())
        unique_name.switch({})
        np.random.seed(7)
        x = layers.data("x", shape=[64, 32], dtype="float32")
        h = layers.fc(x, 128, num_flatten_dims=2, name="in")
        y = layers.causal_conv1d(h, K, name="conv")
        block = fluid.default_main_program().global_block()
        conv, = [op for op in block.ops if op.type == "causal_conv1d"]
        conv.attrs["impl"] = impl
        out = layers.fc(y, 8, num_flatten_dims=2, name="out")
        loss = layers.mean(layers.square(out))
        opt = optimizer.SGD(0.5)
        if recompute:
            opt = optimizer.RecomputeOptimizer(opt)
            opt._set_checkpoints([h, out])
        opt.minimize(loss)
        before = _counts()
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(fluid.default_startup_program())
            prog = fluid.CompiledProgram(fluid.default_main_program())
            losses[impl] = [float(np.asarray(exe.run(
                prog, feed=feed, fetch_list=[loss])[0]).reshape(-1)[0])
                for _ in range(3)]
        assert ("causal_conv1d", impl) in _since(before)
    assert losses["interpret"][2] < losses["interpret"][0]
    np.testing.assert_allclose(losses["interpret"], losses["xla"],
                               rtol=2e-5)

"""causal_conv1d with its two gates inside (ops/pallas_conv1d.py, the
static option `gated` of pt_conv1d_fwd and pt_conv1d_bwd: an LFM2
layer's whole mixer between its projections) in interpret mode against
the op's XLA composition (split the projection, gate, convolve, gate):
forward and all five gradients (the two gates', the signal's, the
filter's, the bias's), at 3 and at 4 taps, with and without gates,
bfloat16 and float32.  T spans three row tiles of two chunks and B is
2, so the halo before a tile and the dz after it are crossed between
tiles and between chunks, and a row that leaked across a batch start
would show.  And head_gated_rms_norm with and without its gate: the
norm a head on an attention layer's q and k is the same op.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import pallas_conv1d
from paddle_tpu.ops import pallas_kernels as pk

T = 384         # three row tiles of 128, two chunks of 64 each
# float32 operands: rounding of the sums only; bfloat16: one rounding
# of y or of a gradient to bfloat16 (2^-8 relative) on values of a few
# units
TOL = {"float32": 2e-5, "bfloat16": 2e-2}


def _operands(c, k, gated, bias, dtype, t=T, b=2, seed=0):
    r = np.random.RandomState(seed)
    ins = {"X": jnp.asarray(r.randn(b, t, 3 * c if gated else c), dtype),
           "W": jnp.asarray(r.uniform(-.5, .5, (c, k)), jnp.float32)}
    if bias:
        ins["Bias"] = jnp.asarray(r.uniform(-.5, .5, (c,)), jnp.float32)
    return ins, jnp.asarray(r.randn(b, t, c), dtype)


def _attrs(impl, act, gated):
    return {"activation": act, "impl": impl, "gated": gated}


def _run(ins, impl, act="", gated=True):
    return get_op_def("causal_conv1d").compute(
        ins, _attrs(impl, act, gated))["Y"]


def _grads(ins, gy, impl, act="", gated=True):
    return get_op_def("causal_conv1d_grad").compute(
        dict(ins, **{"Y@GRAD": gy}), _attrs(impl, act, gated))


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


def test_the_gated_backward_s_row_tile():
    """The gated backward takes blocks at the projection's whole width:
    the largest row tile whose P, dP and dy fit 32 MiB twice over."""
    assert pallas_conv1d.tiles(8192, 2048, 3) == (2048, 256)
    assert pallas_conv1d.gated_bwd_row_tile(8192, 2048, 2) == 512
    assert pallas_conv1d.gated_bwd_row_tile(8192, 2048, 4) == 256
    assert pallas_conv1d.gated_bwd_row_tile(T, 256, 4) == 128
    assert pallas_conv1d.gated_bwd_row_tile(48, 128, 4) == 16


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("gated", [True, False])
@pytest.mark.parametrize("taps,act,bias", [(3, "", False),
                                           (4, "silu", True)])
def test_forward_and_every_gradient_match_the_xla_graph(taps, act, bias,
                                                        gated, dtype):
    """3 taps bare (the LFM2 mixer) and 4 taps with a bias and SiLU
    (the Mamba-2 and KDA side step), each with and without the gates:
    one pair of kernel bodies.  The gated X@GRAD is the whole
    projection's gradient, its thirds dGb, dGc and dx."""
    c = 384                    # three lane blocks of 128
    ins, gy = _operands(c, taps, gated, bias, dtype)
    before = _counts()
    got = _run(ins, "interpret", act, gated)
    want_counts = {("causal_conv1d", "interpret"): 1}
    if gated:
        want_counts[("causal_conv1d_gates", "fused")] = 1
    assert _since(before) == want_counts
    assert got.dtype == jnp.dtype(dtype) and got.shape == (2, T, c)
    _close(got, _run(ins, "xla", act, gated), TOL[dtype])

    before = _counts()
    grads = _grads(ins, gy, "interpret", act, gated)
    assert _since(before) == {("causal_conv1d_grad", "interpret"): 1}
    want = _grads(ins, gy, "xla", act, gated)
    assert sorted(grads) == sorted(want) == sorted(
        s + "@GRAD" for s in ins)
    assert grads["X@GRAD"].dtype == jnp.dtype(dtype)
    assert grads["X@GRAD"].shape == ins["X"].shape
    for third in np.split(np.asarray(want["X@GRAD"], np.float32),
                          3 if gated else 1, axis=-1):
        assert np.abs(third).max() > 0
    for slot in want:
        _close(grads[slot], want[slot],
               TOL[dtype] if slot == "X@GRAD" else TOL["float32"])

    # and through jax.vjp of the forward op: what a recompute
    # segment's replay differentiates
    def loss(x, w):
        y = _run(dict(ins, X=x, W=w), "interpret", act, gated)
        return jnp.sum(y.astype(jnp.float32) * gy.astype(jnp.float32))

    dx, dw = jax.grad(loss, (0, 1))(ins["X"], ins["W"])
    _close(dx, want["X@GRAD"], TOL[dtype])
    _close(dw, want["W@GRAD"], TOL["float32"])


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_cells_width(dtype):
    """C 2,048 and 3 taps as lfm2-24b-a2b has them, cut in T only: the
    forward finds the thirds through three index maps of 8 lane blocks
    each; the backward walks 8 lane blocks of a whole-width tile."""
    ins, gy = _operands(2048, 3, True, False, dtype, t=128, b=1)
    _close(_run(ins, "interpret"), _run(ins, "xla"), TOL[dtype])
    got, want = (_grads(ins, gy, impl) for impl in ("interpret", "xla"))
    _close(got["X@GRAD"], want["X@GRAD"], TOL[dtype])
    _close(got["W@GRAD"], want["W@GRAD"], TOL["float32"])


@pytest.mark.parametrize("tile", [0, 1, 2])
def test_a_tile_s_first_rows_reach_only_through_the_gated_halo(tile):
    """The signal is zero but for the K - 1 rows before row tile `tile`
    + 1 (the last rows of tile `tile`) in batch 0: y's first K - 1 rows
    of the next tile come through the halo alone, and through the
    INPUT gate's halo rows too (a gate of zero there silences them);
    nothing reaches batch 1, whose first rows follow batch 0's last in
    memory."""
    c, k, tt = 256, 3, 128
    ins, _ = _operands(c, k, True, False, "float32")
    r = np.random.RandomState(1)
    p = np.asarray(ins["X"]).copy()
    p[..., 2 * c:] = 0.0
    last = slice((tile + 1) * tt - (k - 1), (tile + 1) * tt)
    p[0, last, 2 * c:] = r.randn(k - 1, c)
    p[..., :2 * c] = np.abs(p[..., :2 * c]) + 0.1       # gates > 0
    ins["X"], ins["W"] = jnp.asarray(p), jnp.abs(ins["W"]) + 0.1
    got = np.asarray(_run(ins, "interpret"))
    np.testing.assert_allclose(got, _run(ins, "xla"), rtol=1e-6,
                               atol=1e-6)
    after = got[0, (tile + 1) * tt:(tile + 1) * tt + k - 1] \
        if tile < 2 else got[1, :k - 1]
    if tile < 2:
        assert np.all(np.abs(after) > 0)
    else:       # the last tile's last rows: the next rows are batch 1's
        assert not after.any()
    assert not got[1].any()
    # the input gate closed on those rows: nothing comes through
    shut = p.copy()
    shut[0, last, :c] = 0.0
    assert not np.asarray(_run(dict(ins, X=jnp.asarray(shut)),
                               "interpret")).any()
    # and the gradient of those rows comes from the K - 1 rows after
    # them: the dz the backward carries from the later tile
    g = np.zeros((2, T, c), np.float32)
    if tile < 2:
        g[0, (tile + 1) * tt:(tile + 1) * tt + k - 1] = 1.0
    grads = [_grads(ins, jnp.asarray(g), impl)["X@GRAD"]
             for impl in ("interpret", "xla")]
    np.testing.assert_allclose(*grads, rtol=1e-6, atol=1e-6)
    d_signal = np.asarray(grads[0])[0, last, 2 * c:]
    assert np.all(d_signal > 0) == (tile < 2)


@pytest.mark.parametrize("case,shape", [("c64", (2, 64, 64)),
                                        ("t40", (2, 40, 128))])
def test_what_the_kernels_cannot_tile_runs_the_xla_graph(case, shape):
    """Asked for by name or not: the op chooses by what it reads, and
    the gates' counter says that XLA's products applied them."""
    b, t, c = shape
    ins, gy = _operands(c, 3, True, False, "float32", t=t, b=b)
    before = _counts()
    got = _run(ins, "interpret")
    grads = _grads(ins, gy, "interpret")
    assert _since(before) == {("causal_conv1d", "xla"): 1,
                              ("causal_conv1d_gates", "xla"): 1,
                              ("causal_conv1d_grad", "xla"): 1}
    np.testing.assert_array_equal(got, _run(ins, "xla"))
    np.testing.assert_array_equal(grads["X@GRAD"],
                                  _grads(ins, gy, "xla")["X@GRAD"])


def test_an_ungated_op_counts_no_gates_and_a_wrong_width_raises():
    ins, _ = _operands(256, 4, False, True, "float32", t=64)
    before = _counts()
    _run(ins, "", "silu", gated=False)
    assert _since(before) == {("causal_conv1d", "xla"): 1}
    with pytest.raises(ValueError, match="3 times"):
        _run(ins, "xla", "", gated=True)
    gated, _ = _operands(256, 3, True, False, "float32", t=64)
    with pytest.raises(ValueError, match="768 channels for a filter"):
        _run(gated, "xla", "", gated=False)


def test_the_layer_appends_one_gated_op_and_its_grad_reads_no_output():
    import paddle_tpu as fluid
    from paddle_tpu import layers, optimizer

    x = layers.data("x", shape=[32, 64], dtype="float32")
    proj = layers.fc(x, 3 * 128, num_flatten_dims=2, bias_attr=False)
    y = layers.gated_short_conv(proj, 3, name="mix")
    assert y.shape[-1] == 128
    optimizer.SGD(0.1).minimize(layers.mean(y))
    block = fluid.default_main_program().global_block()
    fwd, = [op for op in block.ops if op.type == "causal_conv1d"]
    grad, = [op for op in block.ops if op.type == "causal_conv1d_grad"]
    assert fwd.attrs["gated"] and fwd.attrs["activation"] == ""
    assert grad.attrs["gated"] and sorted(grad.inputs) == ["W", "X",
                                                           "Y@GRAD"]
    assert fwd.outputs["Y"][0] not in sum(grad.inputs.values(), [])
    assert block.var("mix.w").shape == (128, 3)
    # the projection's gradient is one array of the projection's shape
    assert block.var(grad.outputs["X@GRAD"][0]).shape[-1] == 3 * 128
    assert not [op for op in block.ops if op.type in ("split", "concat")]
    with pytest.raises(ValueError, match="three thirds"):
        layers.gated_short_conv(layers.fc(x, 128, num_flatten_dims=2), 3)


@pytest.mark.parametrize("recompute", [False, True])
def test_a_program_trains_through_the_gated_kernels(recompute):
    """fc -> gated_short_conv -> fc -> loss through Executor.run, the
    kernels in interpret mode against the XLA composition: the grad op
    on the plain path, jax.vjp of the op inside a recompute segment."""
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
        h = layers.fc(x, 3 * 128, num_flatten_dims=2, name="in")
        y = layers.gated_short_conv(h, 3, name="conv")
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
        used = _since(before)
        assert ("causal_conv1d", impl) in used
        assert ("causal_conv1d_gates",
                "xla" if impl == "xla" else "fused") in used
    assert losses["interpret"][2] < losses["interpret"][0]
    np.testing.assert_allclose(losses["interpret"], losses["xla"],
                               rtol=2e-5)


# -- the head-wise RMS norm, with and without its gate ------------------------

def _head_norm_by_hand(x, scale, eps, gate=None):
    heads = x.shape[-1] // scale.shape[0]
    xh = x.reshape(x.shape[:-1] + (heads, -1)).astype(np.float64)
    y = xh / np.sqrt((xh ** 2).mean(-1, keepdims=True) + eps) * scale
    if gate is not None:
        y = y / (1.0 + np.exp(-gate.astype(np.float64)))[..., None]
    return y.reshape(x.shape)


@pytest.mark.parametrize("gate", [False, True])
def test_head_rms_norm_with_and_without_a_gate(gate):
    """Without a gate: RMSNorm a head times one learned scale of the
    head size (an attention layer's q and k), the head count an
    attribute.  With one: the same, times sigmoid(gate) a head (the
    KDA layer's output norm, as before the gate was optional)."""
    r = np.random.RandomState(0)
    x = r.randn(2, 5, 4 * 16).astype(np.float32)
    scale = (1 + 0.3 * r.randn(16)).astype(np.float32)
    ins = {"X": jnp.asarray(x), "Scale": jnp.asarray(scale)}
    g = r.randn(2, 5, 4).astype(np.float32) if gate else None
    if gate:
        ins["Gate"] = jnp.asarray(g)
    op = get_op_def("head_gated_rms_norm")
    attrs = op.canonical_attrs({"epsilon": 1e-5,
                                "n_head": 0 if gate else 4})
    with jax.default_matmul_precision("highest"):
        got = op.compute(ins, attrs)["Y"]
    np.testing.assert_allclose(got, _head_norm_by_hand(x, scale, 1e-5, g),
                               rtol=2e-5, atol=2e-6)
    # a head's statistic is its own: scaling one head's entries leaves
    # every head's output as it was
    x2 = x.copy()
    x2[..., :16] *= 7.0
    with jax.default_matmul_precision("highest"):
        again = op.compute(dict(ins, X=jnp.asarray(x2)), attrs)["Y"]
    np.testing.assert_allclose(again, got, rtol=1e-4, atol=1e-5)
    # bfloat16 in, bfloat16 out, float32 inside
    low = op.compute(dict(ins, X=jnp.asarray(x, jnp.bfloat16)), attrs)["Y"]
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), got,
                               rtol=2e-2, atol=2e-2)


def test_head_rms_norm_without_gate_needs_heads_and_a_scale():
    op = get_op_def("head_gated_rms_norm")
    x = jnp.ones((2, 3, 64))
    with pytest.raises(ValueError, match="heads"):
        op.compute({"X": x, "Scale": jnp.ones(16)},
                   op.canonical_attrs({"n_head": 0}))
    with pytest.raises(ValueError, match="heads"):
        op.compute({"X": x, "Scale": jnp.ones(16)},
                   op.canonical_attrs({"n_head": 5}))
    with pytest.raises(ValueError, match="no gate and no norm"):
        op.compute({"X": x}, op.canonical_attrs({"n_head": 4}))


def test_the_head_norm_layer_without_a_gate_and_its_gradients():
    """layers.head_gated_rms_norm(x, None, n_head=H): one op, no Gate
    input, one scale of the head size; the program's gradients of x and
    of the scale against jax.grad of the formula by hand."""
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.backward import append_backward

    x = layers.data("x", shape=[6, 64], dtype="float32")
    x.stop_gradient = False
    y = layers.head_gated_rms_norm(x, None, 1e-5, n_head=4, name="qn")
    loss = layers.reduce_sum(layers.square(y))
    append_backward(loss)
    block = fluid.default_main_program().global_block()
    op, = [o for o in block.ops if o.type == "head_gated_rms_norm"]
    assert "Gate" not in op.inputs and op.attrs["n_head"] == 4
    assert block.var("qn.w").shape == (16,)
    with pytest.raises(ValueError, match="one of a gate and n_head"):
        layers.head_gated_rms_norm(x, None)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(2).randn(3, 6, 64).astype(np.float32)
    dx, dscale = exe.run(feed={"x": xv},
                         fetch_list=["x@GRAD", "qn.w@GRAD"])

    def by_hand(xa, scale):
        xh = xa.reshape(3, 6, 4, 16)
        out = xh / jnp.sqrt(jnp.mean(xh * xh, -1, keepdims=True) + 1e-5) \
            * scale
        return jnp.sum(out * out)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(by_hand, (0, 1))(jnp.asarray(xv), jnp.ones(16))
    np.testing.assert_allclose(dx, want[0], rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(dscale, want[1], rtol=1e-4, atol=1e-4)

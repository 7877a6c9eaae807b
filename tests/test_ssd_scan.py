"""ops/ssd_ops.py: the chunked state-space scan (its `xla` and
`interpret` impls) against the recurrence run TOKEN BY TOKEN, forward
and all six input gradients; the registered grad op on the forward's
saved chunk states, alone and inside a recompute segment; the causal
convolution and the gated RMSNorm.

The decay is slow on purpose (dt in [1e-3, 1e-1], A in [0.05, 1]: a
state keeps 20% to 99.99% of itself over a 16-token chunk), so that a
scan that lost the state between chunks would be far outside the
tolerance: `test_the_state_crosses_chunks` holds the reference with the
state zeroed at every chunk start to the same bound and requires that
it FAILS it.

Tolerance: float32 against float32 in another order of summation: 2e-5
of each array's largest entry (1.3e-6 seen).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.ops import pallas_kernels as pk

SLOTS = ("X", "Dt", "A", "B", "C", "D")
TOL = 2e-5


def token_by_token(x, dt, a, bm, cm, d, reset_every=0):
    """x [B, T, H*P], dt [B, T, H], a [H], bm and cm [B, T, N], d [H]
    -> y [B, T, H*P]; reset_every zeroes the state every so many
    tokens (a wrong scan on purpose)."""
    b, t, width = x.shape
    h = dt.shape[-1]
    xh = x.reshape(b, t, h, width // h)

    def step(s, inp):
        i, xt, dtt, bt, ct = inp
        if reset_every:
            s = jnp.where(i % reset_every == 0, 0.0, s)
        s = jnp.exp(dtt * a)[..., None, None] * s \
            + (dtt[..., None] * xt)[..., None] * bt[:, None, None, :]
        return s, jnp.einsum("bhpn,bn->bhp", s, ct) + d[:, None] * xt

    _, ys = lax.scan(
        step, jnp.zeros((b, h, width // h, bm.shape[-1]), x.dtype),
        (jnp.arange(t), xh.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
         bm.transpose(1, 0, 2), cm.transpose(1, 0, 2)))
    return ys.transpose(1, 0, 2, 3).reshape(b, t, width)


def operands(n_chunks, chunk=16, h=4, p=64, n=32, b=2, seed=0):
    r = np.random.RandomState(seed)
    t = n_chunks * chunk
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)  # noqa: E731
    dt = np.exp(r.uniform(np.log(1e-3), np.log(1e-1), (b, t, h)))
    return (f(b, t, h * p), jnp.asarray(dt, jnp.float32),
            -jnp.asarray(r.uniform(0.05, 1.0, h), jnp.float32),
            0.5 * f(b, t, n), 0.5 * f(b, t, n), f(h)), f(b, t, h * p)


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-30))


def reference(args, gy, reset_every=0):
    with jax.default_matmul_precision("highest"):
        y, vjp = jax.vjp(
            lambda *a: token_by_token(*a, reset_every=reset_every), *args)
        return y, vjp(gy)


@pytest.mark.parametrize("n_chunks", [1, 2, 4])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_forward_and_six_gradients_against_the_recurrence(impl, n_chunks):
    args, gy = operands(n_chunks)
    want_y, want_g = reference(args, gy)
    attrs = {"chunk_size": 16, "impl": impl}
    ins = dict(zip(SLOTS, args))
    outs = get_op_def("ssd_scan").compute(ins, attrs)
    assert outs["States"].shape == (2, n_chunks, 4 * 64, 32)
    assert outs["States"].dtype == jnp.float32
    assert rel(outs["Y"], want_y) <= TOL
    grads = get_op_def("ssd_scan_grad").compute(
        dict(ins, Y=outs["Y"], States=outs["States"], **{"Y@GRAD": gy}),
        attrs)
    errors = {s: rel(grads[s + "@GRAD"], g) for s, g in zip(SLOTS, want_g)}
    assert all(e <= TOL for e in errors.values()), errors
    assert all(grads[s + "@GRAD"].dtype == v.dtype
               and grads[s + "@GRAD"].shape == v.shape
               for s, v in ins.items())


def test_head_size_128_one_head_a_lane_block():
    args, gy = operands(2, h=2, p=128, n=16, b=1)
    want_y, want_g = reference(args, gy)
    attrs = {"chunk_size": 16, "impl": "interpret"}
    ins = dict(zip(SLOTS, args))
    outs = get_op_def("ssd_scan").compute(ins, attrs)
    grads = get_op_def("ssd_scan_grad").compute(
        dict(ins, Y=outs["Y"], States=outs["States"], **{"Y@GRAD": gy}),
        attrs)
    assert rel(outs["Y"], want_y) <= TOL
    assert all(rel(grads[s + "@GRAD"], g) <= TOL
               for s, g in zip(SLOTS, want_g))


def test_the_state_crosses_chunks():
    """The recurrence with its state zeroed at every chunk start is
    NOT the scan: it misses the tolerance by orders of magnitude, in
    the output and in every gradient that the state carries."""
    args, gy = operands(4)
    want_y, want_g = reference(args, gy)
    lost_y, lost_g = reference(args, gy, reset_every=16)
    assert rel(lost_y, want_y) > 1000 * TOL
    assert all(rel(lo, w) > 1000 * TOL
               for lo, w in zip(lost_g[:5], want_g[:5]))
    # and the first chunk, which starts from zero either way, agrees
    assert rel(lost_y[:, :16], want_y[:, :16]) <= TOL


def test_states_are_the_state_each_chunk_starts_from():
    args, _ = operands(3)
    outs = get_op_def("ssd_scan").compute(
        dict(zip(SLOTS, args)), {"chunk_size": 16, "impl": "interpret"})
    x, dt, a, bm, _, _ = args
    s = jnp.zeros((2, 4, 64, 32), jnp.float32)
    for t in range(32):
        if t % 16 == 0:
            assert rel(outs["States"][:, t // 16].reshape(s.shape) + 1.0,
                       s + 1.0) <= TOL
        s = jnp.exp(dt[:, t] * a)[..., None, None] * s \
            + (dt[:, t, :, None] * x[:, t].reshape(2, 4, 64))[..., None] \
            * bm[:, t, None, None, :]


def test_a_length_that_is_no_multiple_of_the_chunk_raises():
    args, _ = operands(2)
    ins = {s: (v[:, :24] if v.ndim == 3 else v)
           for s, v in zip(SLOTS, args)}
    for impl in ("xla", "interpret"):
        with pytest.raises(ValueError, match="nothing is padded"):
            get_op_def("ssd_scan").compute(
                ins, {"chunk_size": 16, "impl": impl})


def test_sizes_the_kernels_cannot_tile_run_the_xla_form():
    args, _ = operands(2, h=2, p=32)
    before = _counts()
    get_op_def("ssd_scan").compute(
        dict(zip(SLOTS, args)), {"chunk_size": 16, "impl": "interpret"})
    assert _since(before) == {("ssd_scan", "xla"): 1}


def _counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _counts().items()
            if v - before.get(k, 0)}


# -- through the IR: the grad op reads the saved states ---------------------

T, H, P, N, CHUNK = 64, 4, 64, 32, 16


def _net(recompute, impl):
    """x -> fc -> ssd_scan -> fc -> loss, one recompute segment round
    the scan; returns (loss, [grads of every parameter])."""
    def data(name, width):
        return layers.data(name, shape=[T, width], dtype="float32")

    x, dt, bm, cm = (data("x", H * P), data("dt", H), data("bm", N),
                     data("cm", N))
    u = layers.fc(x, H * P, num_flatten_dims=2, bias_attr=False)
    y = layers.mamba2_scan(u, dt, bm, cm, chunk_size=CHUNK, impl=impl,
                           name="ssm")
    out = layers.fc(y, 8, num_flatten_dims=2, bias_attr=False)
    loss = layers.mean(layers.square(out))
    opt = optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints([u, out])
    return loss, opt.backward(loss)


def _feed(seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.randn(2, T, H * P).astype(np.float32),
            "dt": r.randn(2, T, H).astype(np.float32),
            "bm": 0.5 * r.randn(2, T, N).astype(np.float32),
            "cm": 0.5 * r.randn(2, T, N).astype(np.float32)}


def _run_net(recompute, impl):
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()
    np.random.seed(0)
    loss, pg = _net(recompute, impl)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _counts()
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed=_feed(), fetch_list=[loss] + [g for _, g in pg])
    return ({p.name: np.asarray(o) for (p, _), o in zip(pg, outs[1:])},
            float(np.asarray(outs[0]).reshape(-1)[0]), _since(before),
            fluid.default_main_program())


def test_grad_op_in_a_recompute_segment_reads_the_saved_states():
    want, want_loss, _, _ = _run_net(False, "xla")
    assert {"ssm_A_log.w", "ssm_dt_bias.w", "ssm_D.w"} <= set(want)
    for recompute in (False, True):
        got, loss, used, prog = _run_net(recompute, "interpret")
        # the forward kernel once, the backward on the saved states:
        # never a second forward for the grad op, nor a third for the
        # segment's replay
        assert used[("ssd_scan", "interpret")] == 1, used
        assert used[("ssd_scan_grad", "saved")] == 1, used
        assert ("ssd_scan_grad", "recompute") not in used
        assert abs(loss - want_loss) <= 1e-6 * abs(want_loss)
        errors = {n: float(np.abs(got[n] - w).max() / np.abs(w).max())
                  for n, w in want.items()}
        assert all(e <= 1e-4 for e in errors.values()), errors
        if recompute:
            seg = [op for op in prog.global_block().ops
                   if op.type == "recompute_segment_grad"
                   and op.inputs.get("Saved")]
            assert len(seg) == 1 and len(seg[0].inputs["Saved"]) == 2
        else:
            gop, = [op for op in prog.global_block().ops
                    if op.type == "ssd_scan_grad"]
            assert gop.inputs.get("Y") and gop.inputs.get("States")


def test_unbound_states_differentiate_the_forward_again():
    args, gy = operands(2)
    _, want_g = reference(args, gy)
    before = _counts()
    grads = get_op_def("ssd_scan_grad").compute(
        dict(zip(SLOTS, args), **{"Y@GRAD": gy}),
        {"chunk_size": 16, "impl": "interpret"})
    assert _since(before)[("ssd_scan_grad", "recompute")] == 1
    assert all(rel(grads[s + "@GRAD"], g) <= TOL
               for s, g in zip(SLOTS, want_g))


def test_amp_keeps_the_scan_s_steps_and_states_float32():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    x = layers.data("x", shape=[T, H * P], dtype="float32")
    u = layers.fc(x, H * P + 2 * N, num_flatten_dims=2, bias_attr=False)
    z = layers.fc(x, H * P, num_flatten_dims=2, bias_attr=False)
    dt = layers.fc(x, H, num_flatten_dims=2, bias_attr=False)
    xs, bm, cm = layers.split(
        layers.causal_conv1d(u, 4, name="conv"), [H * P, N, N], dim=-1)
    y = layers.gated_rms_norm(
        layers.mamba2_scan(xs, dt, bm, cm, chunk_size=CHUNK, name="ssm"),
        z, name="gn")
    loss = layers.mean(layers.fc(y, 8, num_flatten_dims=2))
    decorate(optimizer.SGD(0.0), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(loss)
    block = fluid.default_main_program().global_block()
    scan, = [op for op in block.ops if op.type == "ssd_scan"]
    dtype = lambda slot: block.var(scan.inputs[slot][0]).dtype  # noqa
    low = {s for s in SLOTS if scan.inputs[s][0].endswith(".cast_bfloat16")
           or "bfloat16" in str(dtype(s))}
    assert not low & {"Dt", "A", "D"}
    conv, = [op for op in block.ops if op.type == "causal_conv1d"]
    # the filter and the bias are read as they are: float32
    assert conv.inputs["W"] == ["conv.w"]
    assert conv.inputs["Bias"] == ["conv_bias.w"]
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    got_y, got_states = exe.run(
        fluid.CompiledProgram(fluid.default_main_program()),
        feed={"x": _feed()["x"]},
        fetch_list=[scan.outputs["Y"][0], scan.outputs["States"][0]],
        return_numpy=False)
    assert got_y.dtype == jnp.bfloat16
    assert got_states.dtype == jnp.float32


# -- the convolution and the gated norm -------------------------------------

def test_causal_conv1d_never_reads_ahead():
    r = np.random.RandomState(0)
    x = jnp.asarray(r.randn(2, 12, 6), jnp.float32)
    w = jnp.asarray(r.randn(6, 4), jnp.float32)
    bias = jnp.asarray(r.randn(6), jnp.float32)
    conv = get_op_def("causal_conv1d").compute

    def run(x, act="silu"):
        return conv({"X": x, "W": w, "Bias": bias},
                    {"activation": act})["Y"]

    want = np.zeros((2, 12, 6), np.float32)
    for t in range(12):
        for k in range(4):
            if t - 3 + k >= 0:
                want[:, t] += np.asarray(w[:, k]) \
                    * np.asarray(x[:, t - 3 + k])
    want += np.asarray(bias)
    np.testing.assert_allclose(run(x, ""), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(run(x), want / (1 + np.exp(-want)),
                               rtol=1e-5, atol=1e-6)
    # y[:, :t + 1] does not move when x[:, t + 1:] does ...
    for t in range(11):
        moved = x.at[:, t + 1:].set(7.0)
        np.testing.assert_array_equal(run(moved)[:, :t + 1],
                                      run(x)[:, :t + 1])
    # ... and no gradient flows from y_t to x_{t+1}
    jac = jax.jacobian(lambda x: run(x)[0, :, 0])(x)[:, 0, :, 0]
    assert np.all(np.triu(np.asarray(jac), 1) == 0)
    assert np.all(np.diag(np.asarray(jac)) != 0)
    with pytest.raises(ValueError, match="activation"):
        run(x, "relu")


def test_gated_rms_norm_gates_before_the_norm():
    r = np.random.RandomState(0)
    x, z = (jnp.asarray(r.randn(2, 5, 16), jnp.float32) for _ in range(2))
    w = jnp.asarray(r.rand(16) + 0.5, jnp.float32)
    got = get_op_def("gated_rms_norm").compute(
        {"X": x, "Gate": z, "Scale": w}, {"epsilon": 1e-5})["Y"]
    u = np.asarray(x) * np.asarray(z) / (1 + np.exp(-np.asarray(z)))
    want = u / np.sqrt((u * u).mean(-1, keepdims=True) + 1e-5) \
        * np.asarray(w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    low = get_op_def("gated_rms_norm").compute(
        {"X": x.astype(jnp.bfloat16), "Gate": z.astype(jnp.bfloat16),
         "Scale": w}, {"epsilon": 1e-5})["Y"]
    assert low.dtype == jnp.bfloat16

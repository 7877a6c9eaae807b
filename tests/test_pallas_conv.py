"""Fused conv-epilogue Pallas kernel tests (interpret mode on CPU) +
flag-gated dispatch + IR fuse-pass wiring.

Mirrors the flash-attention test idiom (tests/test_pallas_kernels.py):
XLA reference vs kernel output under float32 matmul precision, plus
grad checks through the custom_vjp.  The backward reuses the SAME XLA
conv vjp the unfused graph runs, so gradients compare bit-exact; the
forward compares to float tolerance (the kernel's tap-loop reduction
order differs from XLA's conv reduction — 1x1 convs in one Cout block,
a single contraction of one shape in both, do come out bit-identical
and are asserted so).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.flags import set_flags
from paddle_tpu.ops.pallas_conv import (_DEFAULT_BLOCK_CO, _norm_padding,
                                        _reference, conv2d_epilogue)


def _mk(rng, n, h, w, cin, cout, k, oh, ow, has_bias, has_res,
        dtype=np.float32):
    x = jnp.asarray(rng.randn(n, h, w, cin).astype(dtype))
    wt = jnp.asarray((rng.randn(cout, cin, k, k) * 0.1).astype(dtype))
    b = jnp.asarray(rng.randn(cout).astype(dtype)) if has_bias else None
    r = jnp.asarray(rng.randn(n, oh, ow, cout).astype(dtype)) \
        if has_res else None
    return x, wt, b, r


# (n, h, w, cin, cout, k, stride, pad, bias, residual, act) — covers
# 3x3/1x1, stride 1/2, SAME-style/VALID padding, every epilogue combo
_CASES = [
    (2, 8, 8, 16, 32, 3, 1, 1, True, True, "relu"),     # full chain
    (1, 9, 9, 8, 16, 3, 2, 1, False, True, None),       # stride 2
    (2, 8, 8, 16, 32, 1, 1, 0, True, False, "relu"),    # 1x1 + bias
    (1, 7, 7, 8, 24, 1, 2, 0, False, False, None),      # 1x1 stride 2
    (1, 10, 6, 8, 16, 3, 1, 0, True, True, "relu"),     # VALID, rect
    (1, 8, 8, 8, 300, 1, 1, 0, False, True, None),      # Cout > block
]


@pytest.mark.parametrize("case", _CASES)
def test_fused_matches_unfused(case):
    n, h, w, cin, cout, k, s, p, has_b, has_r, act = case
    rng = np.random.RandomState(0)
    oh = (h + 2 * p - k) // s + 1
    ow = (w + 2 * p - k) // s + 1
    x, wt, b, r = _mk(rng, n, h, w, cin, cout, k, oh, ow, has_b, has_r)
    with jax.default_matmul_precision("float32"):
        fused = conv2d_epilogue(x, wt, b, r, strides=(s, s),
                                paddings=(p, p), act=act,
                                impl="interpret")
        ref = _reference(x, wt, b, r, (s, s), _norm_padding((p, p)),
                         act or "")
    assert fused.shape == (n, oh, ow, cout)
    if k == 1 and cout <= _DEFAULT_BLOCK_CO:
        # a 1x1 conv in one Cout block is ONE contraction of one shape
        # in both paths: bit parity
        np.testing.assert_array_equal(np.asarray(fused),
                                      np.asarray(ref))
    elif k == 1:
        # tiled over Cout the kernel contracts [OH*OW, Cin] x [Cin, 256]
        # and the reference [.., Cin] x [Cin, 300]: the CPU picks its
        # vector width and fused multiply-adds by the shape, so the two
        # round apart by one float32 unit in the last place of an
        # element (seen: 0.47 of the largest element's)
        np.testing.assert_allclose(
            np.asarray(fused), np.asarray(ref), rtol=0,
            atol=2 * np.finfo(np.float32).eps
            * np.abs(np.asarray(ref)).max())
    else:
        np.testing.assert_allclose(np.asarray(fused), np.asarray(ref),
                                   atol=2e-5)


def test_fused_grads_match_unfused():
    """dx/dw reuse the XLA conv vjp and the epilogue backward is
    closed-form — all four grads must match the unfused composite's
    autodiff BIT-EXACTLY (same underlying conv-grad HLO)."""
    rng = np.random.RandomState(1)
    x, wt, b, r = _mk(rng, 2, 8, 8, 8, 16, 3, 8, 8, True, True)
    cot = jnp.asarray(rng.randn(2, 8, 8, 16).astype(np.float32))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * cot)

    with jax.default_matmul_precision("float32"):
        gf = jax.grad(loss(lambda a, ww, bb, rr: conv2d_epilogue(
            a, ww, bb, rr, strides=(1, 1), paddings=(1, 1),
            act="relu", impl="interpret")), argnums=(0, 1, 2, 3))(
                x, wt, b, r)
        gr = jax.grad(loss(lambda a, ww, bb, rr: _reference(
            a, ww, bb, rr, (1, 1), ((1, 1), (1, 1)), "relu")),
            argnums=(0, 1, 2, 3))(x, wt, b, r)
    for name, a, e in zip("x w bias residual".split(), gf, gr):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(e),
                                      err_msg="d" + name)


def test_fused_grads_stride2_no_epilogue():
    rng = np.random.RandomState(2)
    x, wt, _, _ = _mk(rng, 1, 9, 9, 8, 16, 3, 5, 5, False, False)
    with jax.default_matmul_precision("float32"):
        gf = jax.grad(lambda a: jnp.sum(conv2d_epilogue(
            a, wt, strides=(2, 2), paddings=(1, 1),
            impl="interpret")))(x)
        gr = jax.grad(lambda a: jnp.sum(_reference(
            a, wt, None, None, (2, 2), ((1, 1), (1, 1)), "")))(x)
    np.testing.assert_array_equal(np.asarray(gf), np.asarray(gr))


def test_fused_bf16_close_to_f32():
    """The AMP/bf16-infer path feeds bf16 operands: the kernel
    accumulates in f32, so it must stay within bf16 tolerance of the
    f32 reference."""
    rng = np.random.RandomState(3)
    x, wt, b, r = _mk(rng, 1, 8, 8, 16, 16, 3, 8, 8, True, True)
    with jax.default_matmul_precision("float32"):
        ref = _reference(x, wt, b, r, (1, 1), ((1, 1), (1, 1)), "relu")
        got = conv2d_epilogue(
            x.astype(jnp.bfloat16), wt.astype(jnp.bfloat16),
            b.astype(jnp.bfloat16), r.astype(jnp.bfloat16),
            strides=(1, 1), paddings=(1, 1), act="relu",
            impl="interpret")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref),
        atol=0.15, rtol=0.1)


# ---------------------------------------------------------------------------
# flag-gated dispatch + IR wiring
# ---------------------------------------------------------------------------

def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def test_flag_off_is_noop():
    """conv2d with the flag off must run the EXACT original lax path:
    the op compute's output is bit-identical with the flag off vs a
    registry call made before this module ever loaded (zero behavior
    change when off — acceptance criterion)."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.flags import get_flag

    assert get_flag("conv_epilogue") == "off"  # the shipped default
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 6, 6, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 8, 3, 3).astype(np.float32))
    d = get_op_def("conv2d")
    attrs = d.canonical_attrs({"strides": [1, 1], "paddings": [1, 1],
                               "data_format": "NHWC"})
    off = d.compute({"Input": x, "Filter": w}, attrs)["Output"]
    from jax import lax

    dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                    ("NHWC", "OIHW", "NHWC"))
    ref = lax.conv_general_dilated(x, w, (1, 1), [(1, 1), (1, 1)],
                                   rhs_dilation=(1, 1),
                                   dimension_numbers=dn,
                                   feature_group_count=1)
    np.testing.assert_array_equal(np.asarray(off), np.asarray(ref))


def test_flag_dispatch_routes_conv2d():
    """conv_epilogue=interpret reroutes the NHWC conv2d op through the
    Pallas kernel; NCHW convs and grouped convs stay on lax."""
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 6, 6, 8).astype(np.float32))
    w = jnp.asarray(rng.randn(16, 8, 3, 3).astype(np.float32) * 0.1)
    d = get_op_def("conv2d")
    attrs = d.canonical_attrs({"strides": [1, 1], "paddings": [1, 1],
                               "data_format": "NHWC"})
    off = d.compute({"Input": x, "Filter": w}, attrs)["Output"]
    set_flags({"conv_epilogue": "interpret"})
    try:
        with jax.default_matmul_precision("float32"):
            on = d.compute({"Input": x, "Filter": w}, attrs)["Output"]
    finally:
        set_flags({"conv_epilogue": "off"})
    np.testing.assert_allclose(np.asarray(on), np.asarray(off),
                               atol=2e-5)


def test_transpiler_fuses_residual_block():
    """conv2d + bias add + residual add + relu -> ONE conv2d_epilogue
    op; executing the rewritten program (flag-off XLA composite) is
    bit-identical to the unfused graph, and the interpret-mode Pallas
    path matches to float tolerance."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, layers
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.transpiler import fuse_conv_epilogue

    def build():
        _fresh()
        img = layers.data("image", shape=[8, 12, 12], dtype="float32")
        c1 = layers.conv2d(img, 16, 3, stride=1, padding=1,
                           bias_attr=None)
        short = layers.conv2d(img, 16, 1, bias_attr=False)
        out = layers.elementwise_add(short, c1, act="relu")
        return out

    rng = np.random.RandomState(0)
    x = rng.randn(2, 8, 12, 12).astype(np.float32)

    out = build()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    ref = exe.run(framework.default_main_program(),
                  feed={"image": x}, fetch_list=[out])[0]
    params = {p.name: np.asarray(global_scope().find_var(p.name).get())
              for p in framework.default_main_program()
              .all_parameters()}

    out2 = build()
    prog = framework.default_main_program()
    n = fuse_conv_epilogue(prog, protected=[out2.name])
    assert n == 1
    types = [op.type for op in prog.global_block().ops]
    assert "conv2d_epilogue" in types
    assert "relu" not in types
    # the shortcut conv must still run BEFORE the fused op (the
    # residual operand is produced mid-chain)
    assert types.index("conv2d") < types.index("conv2d_epilogue")
    fused_op = [op for op in prog.global_block().ops
                if op.type == "conv2d_epilogue"][0]
    assert "Bias" in fused_op.inputs and "Residual" in fused_op.inputs
    assert fused_op.attrs["act"] == "relu"

    exe2 = fluid.Executor(fluid.TPUPlace())
    exe2.run(framework.default_startup_program())
    for k, v in params.items():
        global_scope().find_var(k).set(jnp.asarray(v))
    got_off = exe2.run(prog, feed={"image": x}, fetch_list=[out2])[0]
    np.testing.assert_array_equal(np.asarray(got_off),
                                  np.asarray(ref))
    set_flags({"conv_epilogue": "interpret"})
    try:
        with jax.default_matmul_precision("float32"):
            got_on = exe2.run(prog, feed={"image": x},
                              fetch_list=[out2])[0]
    finally:
        set_flags({"conv_epilogue": "off"})
    np.testing.assert_allclose(np.asarray(got_on), np.asarray(ref),
                               atol=2e-5)


def test_transpiler_skips_broadcast_and_shared_outputs():
    """A scalar/bias-shaped second operand is NOT a residual, and a
    conv output consumed twice must not be erased."""
    from paddle_tpu import framework, layers
    from paddle_tpu.transpiler import fuse_conv_epilogue

    _fresh()
    img = layers.data("image", shape=[4, 8, 8], dtype="float32")
    c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
    # c1 used twice: by the add AND directly by a second consumer
    add = layers.elementwise_add(c1, c1)
    n = fuse_conv_epilogue(framework.default_main_program(),
                           protected=[add.name])
    assert n == 0
    types = [op.type
             for op in framework.default_main_program()
             .global_block().ops]
    assert "conv2d_epilogue" not in types


def test_grad_flows_through_fused_ir_op():
    """append_backward over a fused program produces finite grads that
    match the unfused program's bit-exactly (generic vjp through the
    custom_vjp backward = the same XLA conv grads)."""
    import paddle_tpu as fluid
    from paddle_tpu import backward, framework, layers
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.transpiler import fuse_conv_epilogue

    def build():
        _fresh()
        img = layers.data("image", shape=[4, 8, 8], dtype="float32")
        c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=None)
        short = layers.conv2d(img, 8, 1, bias_attr=False)
        out = layers.elementwise_add(short, c1, act="relu")
        loss = layers.reduce_sum(out)
        return out, loss

    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 8, 8).astype(np.float32)

    out, loss = build()
    prog = framework.default_main_program()
    backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    params = {p.name: np.asarray(global_scope().find_var(p.name).get())
              for p in prog.all_parameters()}
    ref = exe.run(prog, feed={"image": x},
                  fetch_list=[loss.name, "conv2d_0.w_0@GRAD"])

    out2, loss2 = build()
    prog2 = framework.default_main_program()
    n = fuse_conv_epilogue(prog2, protected=[out2.name, loss2.name])
    assert n == 1
    backward.append_backward(loss2)
    exe2 = fluid.Executor(fluid.TPUPlace())
    exe2.run(framework.default_startup_program())
    for k, v in params.items():
        global_scope().find_var(k).set(jnp.asarray(v))
    got = exe2.run(prog2, feed={"image": x},
                   fetch_list=[loss2.name, "conv2d_0.w_0@GRAD"])
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(ref[0]))
    np.testing.assert_array_equal(np.asarray(got[1]),
                                  np.asarray(ref[1]))


def test_nhwc_transpile_carries_fused_op():
    """The layout pass converts Input AND Residual to NHWC and flips
    the op's data_format."""
    from paddle_tpu import framework, layers
    from paddle_tpu.transpiler import fuse_conv_epilogue, nhwc_transpile

    _fresh()
    img = layers.data("image", shape=[4, 8, 8], dtype="float32")
    c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
    short = layers.conv2d(img, 8, 1, bias_attr=False)
    layers.elementwise_add(short, c1, act="relu")
    prog = framework.default_main_program()
    assert fuse_conv_epilogue(prog) == 1
    nhwc_transpile(prog)
    fused = [op for op in prog.global_block().ops
             if op.type == "conv2d_epilogue"][0]
    assert fused.attrs["data_format"] == "NHWC"
    blk = prog.global_block()
    # channels ride last after the layout pass: Input C=4 (the image),
    # Residual C=8 (the shortcut conv's output)
    assert blk.var(fused.inputs["Input"][0]).shape[-1] == 4
    assert blk.var(fused.inputs["Residual"][0]).shape[-1] == 8


def test_moments_1pass_survives_zero_probe():
    """ADVICE r5: a probe region of exact zeros on a channel whose
    |mean| >> std must not collapse the variance (the old
    single-element probe degraded to the cancellation-prone raw
    form); rsqrt(var+eps) downstream must stay bounded."""
    from paddle_tpu.ops.nn import _moments_1pass

    x = np.full((4, 2, 5, 5), 1000.0, np.float32)
    x += np.random.RandomState(0).randn(4, 2, 5, 5).astype(
        np.float32) * 1e-2
    x[:, :, 0, 0] = 0.0          # the whole probe slice
    xj = jnp.asarray(x)
    mean, var = _moments_1pass(xj, (0, 2, 3))
    ref_var = np.var(x.astype(np.float64), axis=(0, 2, 3))
    ref_mean = np.mean(x.astype(np.float64), axis=(0, 2, 3))
    np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(var), ref_var, rtol=1e-2)
    # and the clean path still agrees with jnp.var exactly enough
    y = jnp.asarray(np.random.RandomState(1).randn(4, 3, 6, 6)
                    .astype(np.float32) * 3 + 2)
    m2, v2 = _moments_1pass(y, (0, 2, 3))
    np.testing.assert_allclose(np.asarray(m2),
                               np.asarray(jnp.mean(y, (0, 2, 3))),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(v2),
                               np.asarray(jnp.var(y, (0, 2, 3))),
                               rtol=1e-4, atol=1e-6)

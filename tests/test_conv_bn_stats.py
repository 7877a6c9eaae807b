"""Conv+BN-stats train-chain fusion tests (interpret mode on CPU):
kernel parity, precomputed-stats batch_norm, the fuse_conv_bn_train IR
pass, NHWC carry, AMP slot pinning, and flag-off no-op (ISSUE 4).

Parity strategy (the pallas_conv idiom): the "xla" impl IS the exact
unfused op sequence, so flag-off executor runs compare bit-exact; the
interpret-mode kernels compare at float tolerance (tap-loop and
normalize FMA contraction differ from XLA's fusion choices by ulps),
except where the construction pins bit equality (1x1 conv stats vs a
same-reduction-order reference; batch_norm fed precomputed stats vs
computing its own from the same values).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from paddle_tpu.flags import get_flag, set_flags
from paddle_tpu.ops.nn import _moments_1pass
from paddle_tpu.ops.pallas_conv import (_conv_core, _conv_stats_pallas,
                                        _norm_padding, bn_normalize_epilogue,
                                        conv2d_bn_act, conv2d_bn_stats)


def _mk(rng, n, h, w, cin, cout, k, dtype=np.float32):
    x = jnp.asarray(rng.randn(n, h, w, cin).astype(dtype))
    wt = jnp.asarray((rng.randn(cout, cin, k, k) * 0.1).astype(dtype))
    scale = jnp.asarray((rng.rand(cout) + 0.5).astype(np.float32))
    shift = jnp.asarray(rng.randn(cout).astype(np.float32))
    return x, wt, scale, shift


# ---------------------------------------------------------------------------
# kernel: Σy/Σy² sibling outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k,s,p", [(3, 1, 1), (1, 1, 0), (3, 2, 1),
                                   (1, 2, 0)])
def test_stats_match_moments_1pass(k, s, p):
    """The conv kernel's sibling Σy/Σy², finalized to mean/var, must
    agree with the unfused graph's `_moments_1pass` over the conv
    output (different algorithm — raw moments vs shifted one-pass — so
    float tolerance, not bit parity)."""
    rng = np.random.RandomState(0)
    x, wt, _, _ = _mk(rng, 2, 9, 9, 8, 16, k)
    with jax.default_matmul_precision("float32"):
        y, mean, var = conv2d_bn_stats(x, wt, strides=(s, s),
                                       paddings=(p, p),
                                       impl="interpret")
        yr = _conv_core(x, wt, (s, s), _norm_padding((p, p)))
        mr, vr = _moments_1pass(yr.astype(jnp.float32), (0, 1, 2))
    assert y.shape == yr.shape
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(mr),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(var), np.asarray(vr),
                               rtol=1e-4, atol=1e-6)


def test_stats_bit_exact_1x1_same_order():
    """A 1x1 conv is ONE contraction in both paths and the kernel's
    per-image stat reduction is the same jnp.sum the host reference
    runs — the partial sums compare BIT-EXACT."""
    rng = np.random.RandomState(1)
    x, wt, _, _ = _mk(rng, 2, 8, 8, 16, 32, 1)
    with jax.default_matmul_precision("float32"):
        y, s1, s2 = _conv_stats_pallas(x, wt, None, (1, 1),
                                       _norm_padding((0, 0)),
                                       interpret=True)
        yr = _conv_core(x, wt, (1, 1), _norm_padding((0, 0)))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    yf = np.asarray(yr, np.float32).reshape(2, 64, 32)
    np.testing.assert_array_equal(
        np.asarray(s1), np.asarray(jnp.sum(jnp.asarray(yf), axis=1)))
    np.testing.assert_array_equal(
        np.asarray(s2),
        np.asarray(jnp.sum(jnp.asarray(yf) * jnp.asarray(yf), axis=1)))


def test_stats_bf16_input():
    """bf16 conv output: stats accumulate in f32 over the ROUNDED
    output (what the unfused BN sees), staying near the f32 moments."""
    rng = np.random.RandomState(2)
    x, wt, _, _ = _mk(rng, 1, 8, 8, 16, 16, 3)
    with jax.default_matmul_precision("float32"):
        y, mean, var = conv2d_bn_stats(
            x.astype(jnp.bfloat16), wt.astype(jnp.bfloat16),
            strides=(1, 1), paddings=(1, 1), impl="interpret")
        yr = _conv_core(x, wt, (1, 1), _norm_padding((1, 1)))
    assert y.dtype == jnp.bfloat16
    assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(mean),
        np.asarray(jnp.mean(yr.astype(jnp.float32), axis=(0, 1, 2))),
        atol=0.05, rtol=0.05)
    assert np.all(np.asarray(var) >= 0)


# ---------------------------------------------------------------------------
# kernel: one-pass normalize + residual + ReLU
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("has_res,act", [(True, "relu"), (True, None),
                                         (False, "relu"),
                                         (False, None)])
def test_fused_normalize_matches_unfused_chain(has_res, act):
    """The one-pass kernel vs the unfused normalize -> cast ->
    residual-add -> relu chain, given the SAME stats: identical op
    order and rounding points, so only FMA-contraction ulps separate
    them."""
    rng = np.random.RandomState(3)
    y = jnp.asarray(rng.randn(2, 8, 8, 32).astype(np.float32))
    mean = jnp.asarray(rng.randn(32).astype(np.float32))
    var = jnp.asarray((rng.rand(32) + 0.1).astype(np.float32))
    scale = jnp.asarray((rng.rand(32) + 0.5).astype(np.float32))
    shift = jnp.asarray(rng.randn(32).astype(np.float32))
    res = jnp.asarray(rng.randn(2, 8, 8, 32).astype(np.float32)) \
        if has_res else None
    got = bn_normalize_epilogue(y, mean, var, scale, shift, res,
                                epsilon=1e-5, act=act,
                                impl="interpret")
    sh = (1, 1, 1, 32)
    ref = (y.astype(jnp.float32) - mean.reshape(sh)) \
        * lax.rsqrt(var.reshape(sh) + 1e-5) * scale.reshape(sh) \
        + shift.reshape(sh)
    ref = ref.astype(y.dtype)
    if has_res:
        ref = ref + res
    if act == "relu":
        ref = jnp.maximum(ref, 0)
    assert got.shape == ref.shape and got.dtype == ref.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=1e-5)


def test_fused_normalize_bf16():
    rng = np.random.RandomState(4)
    y = jnp.asarray(rng.randn(1, 8, 8, 16).astype(np.float32),
                    jnp.bfloat16)
    mean = jnp.asarray(rng.randn(16).astype(np.float32))
    var = jnp.asarray((rng.rand(16) + 0.1).astype(np.float32))
    scale = jnp.asarray((rng.rand(16) + 0.5).astype(np.float32))
    shift = jnp.asarray(rng.randn(16).astype(np.float32))
    res = jnp.asarray(rng.randn(1, 8, 8, 16).astype(np.float32),
                      jnp.bfloat16)
    got = bn_normalize_epilogue(y, mean, var, scale, shift, res,
                                act="relu", impl="interpret")
    ref = bn_normalize_epilogue(y, mean, var, scale, shift, res,
                                act="relu", impl="xla")
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=0.1, rtol=0.1)


# ---------------------------------------------------------------------------
# the differentiable fused entry
# ---------------------------------------------------------------------------

def test_conv_bn_act_interpret_matches_unfused():
    """Forward AND all six gradients of the two-kernel path vs the
    exact unfused composite ("xla" impl — conv, _moments_1pass,
    normalize, residual, relu): float tolerance (kernel stats are raw
    moments; the composite's are shifted one-pass)."""
    rng = np.random.RandomState(5)
    x, wt, scale, shift = _mk(rng, 2, 8, 8, 8, 16, 3)
    res = jnp.asarray(rng.randn(2, 8, 8, 16).astype(np.float32))
    cot = jnp.asarray(rng.randn(2, 8, 8, 16).astype(np.float32))

    def run(impl):
        def loss(a, ww, s, b, r):
            out, _m, _v = conv2d_bn_act(
                a, ww, s, b, None, r, strides=(1, 1), paddings=(1, 1),
                act="relu", epsilon=1e-5, impl=impl)
            return jnp.sum(out * cot)

        with jax.default_matmul_precision("float32"):
            out, m, v = conv2d_bn_act(
                x, wt, scale, shift, None, res, strides=(1, 1),
                paddings=(1, 1), act="relu", epsilon=1e-5, impl=impl)
            grads = jax.grad(loss, argnums=(0, 1, 2, 3, 4))(
                x, wt, scale, shift, res)
        return out, m, v, grads

    out_i, m_i, v_i, g_i = run("interpret")
    out_x, m_x, v_x, g_x = run("xla")
    np.testing.assert_allclose(np.asarray(out_i), np.asarray(out_x),
                               atol=3e-5)
    np.testing.assert_allclose(np.asarray(m_i), np.asarray(m_x),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(v_i), np.asarray(v_x),
                               rtol=1e-4, atol=1e-6)
    for name, a, e in zip("x w scale shift residual".split(), g_i, g_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(e),
                                   atol=5e-4, err_msg="d" + name)


def test_conv_bn_act_dresidual_is_masked_passthrough():
    """The residual gradient is exactly the ReLU-masked cotangent (the
    unfused add's grad), bit-exact by construction."""
    rng = np.random.RandomState(6)
    x, wt, scale, shift = _mk(rng, 1, 6, 6, 4, 8, 1)
    res = jnp.asarray(rng.randn(1, 6, 6, 8).astype(np.float32))
    with jax.default_matmul_precision("float32"):
        out, _m, _v = conv2d_bn_act(x, wt, scale, shift, None, res,
                                    act="relu", impl="xla")
        dres = jax.grad(
            lambda r: jnp.sum(conv2d_bn_act(
                x, wt, scale, shift, None, r, act="relu",
                impl="xla")[0]))(res)
    np.testing.assert_array_equal(
        np.asarray(dres),
        np.where(np.asarray(out) > 0, 1.0, 0.0).astype(np.float32))


# ---------------------------------------------------------------------------
# batch_norm / batch_norm_grad consuming precomputed stats
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batch_norm_precomputed_stats_bit_parity(dtype):
    """Feeding batch_norm the exact stats `_moments_1pass` would
    compute must reproduce the self-computed path BIT-EXACTLY (same
    normalize expression on the same values) — across f32 and bf16
    inputs, NCHW and NHWC."""
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.RandomState(7)
    d = get_op_def("batch_norm")
    for layout, shp, axes in (("NCHW", (2, 8, 5, 5), (0, 2, 3)),
                              ("NHWC", (2, 5, 5, 8), (0, 1, 2))):
        x = jnp.asarray(rng.randn(*shp).astype(np.float32) * 3 + 1,
                        dtype)
        c = 8
        ins = {"X": x,
               "Scale": jnp.asarray((rng.rand(c) + 0.5)
                                    .astype(np.float32)),
               "Bias": jnp.asarray(rng.randn(c).astype(np.float32)),
               "Mean": jnp.zeros(c, jnp.float32),
               "Variance": jnp.ones(c, jnp.float32)}
        attrs = d.canonical_attrs({"data_layout": layout})
        ref = d.compute(dict(ins), attrs)
        mean, var = _moments_1pass(x.astype(jnp.float32), axes)
        got = d.compute({**ins, "BatchMean": mean,
                         "BatchVariance": var}, attrs)
        for k in ("Y", "MeanOut", "VarianceOut", "SavedMean",
                  "SavedVariance"):
            np.testing.assert_array_equal(np.asarray(got[k]),
                                          np.asarray(ref[k]),
                                          err_msg="%s %s" % (layout, k))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batch_norm_grad_precomputed_stats_bit_parity(dtype):
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.RandomState(8)
    d = get_op_def("batch_norm_grad")
    x = jnp.asarray(rng.randn(2, 6, 4, 4).astype(np.float32), dtype)
    dy = jnp.asarray(rng.randn(2, 6, 4, 4).astype(np.float32), dtype)
    ins = {"X": x, "Y@GRAD": dy,
           "Scale": jnp.asarray((rng.rand(6) + 0.5).astype(np.float32))}
    attrs = d.canonical_attrs({})
    ref = d.compute(dict(ins), attrs)
    mean, var = _moments_1pass(x.astype(jnp.float32), (0, 2, 3))
    got = d.compute({**ins, "BatchMean": mean, "BatchVariance": var},
                    attrs)
    for k in ("X@GRAD", "Scale@GRAD", "Bias@GRAD"):
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(ref[k]), err_msg=k)


def test_batch_norm_eval_mode_ignores_precomputed_stats():
    """Eval/global-stats BN normalizes with the RUNNING stats; supplied
    batch stats must not change that."""
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.RandomState(9)
    d = get_op_def("batch_norm")
    x = jnp.asarray(rng.randn(2, 4, 3, 3).astype(np.float32))
    ins = {"X": x,
           "Scale": jnp.ones(4, jnp.float32),
           "Bias": jnp.zeros(4, jnp.float32),
           "Mean": jnp.asarray(rng.randn(4).astype(np.float32)),
           "Variance": jnp.asarray((rng.rand(4) + 0.5)
                                   .astype(np.float32))}
    attrs = d.canonical_attrs({"is_test": True})
    ref = d.compute(dict(ins), attrs)
    got = d.compute({**ins,
                     "BatchMean": jnp.full(4, 100.0, jnp.float32),
                     "BatchVariance": jnp.full(4, 100.0, jnp.float32)},
                    attrs)
    np.testing.assert_array_equal(np.asarray(got["Y"]),
                                  np.asarray(ref["Y"]))


# ---------------------------------------------------------------------------
# IR pass + executor wiring
# ---------------------------------------------------------------------------

def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _build_block(is_test=False, groups=1):
    """A miniature ResNet bottleneck tail: main conv+BN, shortcut
    conv+BN, residual add, relu."""
    from paddle_tpu import layers

    img = layers.data("image", shape=[8, 10, 10], dtype="float32")
    c1 = layers.conv2d(img, 16, 3, padding=1, bias_attr=False,
                       groups=groups)
    b1 = layers.batch_norm(c1, is_test=is_test)
    short = layers.conv2d(img, 16, 1, bias_attr=False)
    b2 = layers.batch_norm(short, is_test=is_test)
    out = layers.elementwise_add(b2, b1, act="relu")
    return out


def test_flag_defaults_off():
    assert get_flag("conv_bn_stats") == "off"


def test_transpiler_fuses_train_block_and_flag_off_is_bit_exact():
    """conv+BN(train)+residual+relu (and the shortcut conv+BN) ->
    conv2d_bn_train ops; executing the rewritten program with the flag
    OFF is bit-identical to the unfused graph (incl. the running-stat
    updates), and the interpret-mode kernel path matches to float
    tolerance."""
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.transpiler import fuse_conv_bn_train

    rng = np.random.RandomState(0)
    x = rng.randn(2, 8, 10, 10).astype(np.float32)

    _fresh()
    out = _build_block()
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    prog = framework.default_main_program()
    params = {p.name: np.asarray(global_scope().find_var(p.name).get())
              for p in prog.all_parameters()}
    mean_vars = [p.name for p in prog.all_parameters()
                 if "batch_norm" in p.name and
                 ("mean" in p.name or "variance" in p.name)]
    ref = exe.run(prog, feed={"image": x}, fetch_list=[out])[0]
    ref_stats = {n: np.asarray(global_scope().find_var(n).get())
                 for n in mean_vars}

    _fresh()
    out2 = _build_block()
    prog2 = framework.default_main_program()
    n = fuse_conv_bn_train(prog2, protected=[out2.name])
    assert n == 2                 # the main chain AND the shortcut
    types = [op.type for op in prog2.global_block().ops]
    assert types.count("conv2d_bn_train") == 2
    assert "batch_norm" not in types and "conv2d" not in types
    assert "relu" not in types and "elementwise_add" not in types
    fused = [op for op in prog2.global_block().ops
             if op.type == "conv2d_bn_train"]
    tail = [op for op in fused if "Residual" in op.inputs]
    assert len(tail) == 1 and tail[0].attrs["act"] == "relu"
    # BN output wiring preserved: running-stat vars still the outputs
    for op in fused:
        assert op.outputs["MeanOut"] == op.inputs["Mean"]
        assert op.outputs["VarianceOut"] == op.inputs["Variance"]

    exe2 = fluid.Executor(fluid.TPUPlace())
    exe2.run(framework.default_startup_program())
    for k, v in params.items():
        global_scope().find_var(k).set(jnp.asarray(v))
    got_off = exe2.run(prog2, feed={"image": x}, fetch_list=[out2])[0]
    np.testing.assert_array_equal(np.asarray(got_off), np.asarray(ref))
    for name, want in ref_stats.items():
        np.testing.assert_array_equal(
            np.asarray(global_scope().find_var(name).get()), want,
            err_msg=name)

    # interpret-mode kernels under the flag: float tolerance
    for k, v in params.items():
        global_scope().find_var(k).set(jnp.asarray(v))
    set_flags({"conv_bn_stats": "interpret"})
    try:
        with jax.default_matmul_precision("float32"):
            got_on = exe2.run(prog2, feed={"image": x},
                              fetch_list=[out2])[0]
    finally:
        set_flags({"conv_bn_stats": "off"})
    np.testing.assert_allclose(np.asarray(got_on), np.asarray(ref),
                               atol=5e-5)


def test_transpiler_rejects_grouped_conv():
    from paddle_tpu import framework
    from paddle_tpu.transpiler import fuse_conv_bn_train

    _fresh()
    out = _build_block(groups=4)
    n = fuse_conv_bn_train(framework.default_main_program(),
                           protected=[out.name])
    # the grouped main conv must NOT fuse; the group-1 shortcut may
    types = [op.type for op in
             framework.default_main_program().global_block().ops]
    assert n == 1
    assert "conv2d" in types      # the grouped conv survives
    assert "batch_norm" in types  # with its BN


def test_transpiler_rejects_eval_mode_bn():
    from paddle_tpu import framework
    from paddle_tpu.transpiler import fuse_conv_bn_train

    _fresh()
    out = _build_block(is_test=True)
    n = fuse_conv_bn_train(framework.default_main_program(),
                           protected=[out.name])
    assert n == 0
    types = [op.type for op in
             framework.default_main_program().global_block().ops]
    assert "conv2d_bn_train" not in types


def test_transpiler_leaves_non_tail_relu():
    """conv -> BN -> sigmoid -> relu: the relu is not the chain tail
    (an alien op sits between), so only conv+BN fuse and both
    activations survive."""
    from paddle_tpu import framework, layers
    from paddle_tpu.transpiler import fuse_conv_bn_train

    _fresh()
    img = layers.data("image", shape=[4, 8, 8], dtype="float32")
    c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
    b1 = layers.batch_norm(c1)
    s = layers.sigmoid(b1)
    out = layers.relu(s)
    n = fuse_conv_bn_train(framework.default_main_program(),
                           protected=[out.name])
    assert n == 1
    types = [op.type for op in
             framework.default_main_program().global_block().ops]
    assert "conv2d_bn_train" in types
    assert "sigmoid" in types and "relu" in types
    fused = [op for op in
             framework.default_main_program().global_block().ops
             if op.type == "conv2d_bn_train"][0]
    assert fused.attrs["act"] == ""


def test_transpiler_skips_shared_conv_output():
    """A conv output consumed twice must not be erased."""
    from paddle_tpu import framework, layers
    from paddle_tpu.transpiler import fuse_conv_bn_train

    _fresh()
    img = layers.data("image", shape=[4, 8, 8], dtype="float32")
    c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
    layers.batch_norm(c1)
    extra = layers.reduce_sum(c1)     # second consumer of the conv
    n = fuse_conv_bn_train(framework.default_main_program(),
                           protected=[extra.name])
    assert n == 0


def test_grad_flows_through_fused_ir_op():
    """append_backward over the fused program (flag off -> the exact
    unfused composite inside the custom_vjp) reproduces the unfused
    program's loss bit-exactly and its gradients to a few units in the
    last place."""
    import paddle_tpu as fluid
    from paddle_tpu import backward, framework, layers
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.transpiler import fuse_conv_bn_train

    def build():
        _fresh()
        img = layers.data("image", shape=[4, 8, 8], dtype="float32")
        c1 = layers.conv2d(img, 8, 3, padding=1, bias_attr=False)
        b1 = layers.batch_norm(c1)
        short = layers.conv2d(img, 8, 1, bias_attr=False)
        out = layers.elementwise_add(short, b1, act="relu")
        loss = layers.reduce_sum(out)
        return out, loss

    rng = np.random.RandomState(0)
    x = rng.randn(2, 4, 8, 8).astype(np.float32)
    fetches = ["conv2d_0.w_0@GRAD", "batch_norm_0.w_0@GRAD",
               "batch_norm_0.b_0@GRAD"]

    out, loss = build()
    prog = framework.default_main_program()
    backward.append_backward(loss)
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    params = {p.name: np.asarray(global_scope().find_var(p.name).get())
              for p in prog.all_parameters()}
    ref = exe.run(prog, feed={"image": x},
                  fetch_list=[loss.name] + fetches)

    out2, loss2 = build()
    prog2 = framework.default_main_program()
    n = fuse_conv_bn_train(prog2, protected=[out2.name, loss2.name])
    assert n == 1
    backward.append_backward(loss2)
    exe2 = fluid.Executor(fluid.TPUPlace())
    exe2.run(framework.default_startup_program())
    for k, v in params.items():
        global_scope().find_var(k).set(jnp.asarray(v))
    got = exe2.run(prog2, feed={"image": x},
                   fetch_list=[loss2.name] + fetches)
    # the forward is the same composite term for term: bit-equal
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]),
                                  err_msg="loss")
    # the backward is not the same program: the unfused graph runs the
    # hand-written batch_norm_grad and a conv2d_grad of its own, the
    # fused op's grad is jax.vjp over the whole composite, and XLA sums
    # the 128 terms of each BN moment and of each filter tap in another
    # order.  Bit-equality cannot be guaranteed across the two; a few
    # float32 units in the last place of the gradient's largest element
    # can (seen: 3.3 for the filter, 2.6 for the BN scale, 0 for the
    # BN bias).
    eps = np.finfo(np.float32).eps
    for name, a, e in zip(fetches, got[1:], ref[1:]):
        e = np.asarray(e)
        np.testing.assert_allclose(np.asarray(a), e, rtol=0,
                                   atol=8 * eps * np.abs(e).max(),
                                   err_msg=name)


def test_nhwc_transpile_carries_fused_op():
    """The layout pass converts Input AND Residual to NHWC, flips
    data_format, and leaves the 1-D BN params alone."""
    from paddle_tpu import framework
    from paddle_tpu.transpiler import fuse_conv_bn_train, nhwc_transpile

    _fresh()
    _build_block()
    prog = framework.default_main_program()
    assert fuse_conv_bn_train(prog) == 2
    nhwc_transpile(prog)
    fused = [op for op in prog.global_block().ops
             if op.type == "conv2d_bn_train"]
    blk = prog.global_block()
    for op in fused:
        assert op.attrs["data_format"] == "NHWC"
        assert blk.var(op.inputs["Input"][0]).shape[-1] == 8
        assert len(blk.var(op.inputs["Scale"][0]).shape) == 1
    tail = [op for op in fused if "Residual" in op.inputs][0]
    assert blk.var(tail.inputs["Residual"][0]).shape[-1] == 16


def test_amp_rewrite_pins_bn_slots_fp32():
    """AMP white-lists conv2d_bn_train for Input/Filter/Residual but
    must NOT cast Scale/BNBias/Mean/Variance (running stats would
    accumulate in bf16), and only the Output rides low-precision."""
    from paddle_tpu import framework
    from paddle_tpu.contrib.mixed_precision.fp16_lists import (
        AutoMixedPrecisionLists)
    from paddle_tpu.contrib.mixed_precision.fp16_utils import (
        rewrite_program)
    from paddle_tpu.transpiler import fuse_conv_bn_train

    _fresh()
    _build_block()
    prog = framework.default_main_program()
    assert fuse_conv_bn_train(prog) == 2
    rewrite_program(prog, AutoMixedPrecisionLists())
    fused = [op for op in prog.global_block().ops
             if op.type == "conv2d_bn_train"]
    assert fused
    for op in fused:
        assert op.inputs["Filter"][0].endswith(".cast_bfloat16")
        for slot in ("Scale", "BNBias", "Mean", "Variance"):
            assert not op.inputs[slot][0].endswith(".cast_bfloat16"), \
                slot

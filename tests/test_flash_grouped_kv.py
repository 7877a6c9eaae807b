"""Fewer KV heads than query heads in the flash kernels (ISSUE 38):
query head h reads KV head h // group, K and V stay where they are
([B, H/group, T, D] or token-major [B, T, (H/group)*D]) and are never
repeated to H heads, dk and dv are summed over a group's query heads.

Forward, dq, dk and dv against plain attention with K and V REPEATED,
at groups 1, 2 and 4, head-major and token-major, head sizes 64 and
128, the backward in one sweep and in two; the op through the IR with
its saved residuals; `ouro_model` with grouped KV heads against its
reference.  All on the CPU, the kernels in interpret mode.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.ops import pallas_kernels as pk

T, BLOCK, SCALE = 32, 16, 0.015625


def _counts(kernel):
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == kernel})


def _operands(b, h, hkv, d, dtype=jnp.float32, seed=0):
    """Head-major q, k, v and the cotangent of out."""
    rng = np.random.RandomState(seed)
    # scores of a few units, so that the softmax is far from uniform
    # at the scale 1 / 64
    return tuple(jnp.asarray(rng.randn(*s) * m, dtype) for s, m in (
        ((b, h, T, d), 4.0), ((b, hkv, T, d), 4.0), ((b, hkv, T, d), 1.0),
        ((b, h, T, d), 1.0)))


def _repeated(q, k, v, g, group, causal=True):
    """Plain attention with K and V repeated to q's heads, and its
    gradients: dk and dv of a KV head are what autodiff sums over the
    group."""
    def f(q, k, v):
        out = pk._plain_attention(
            q, jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1),
            causal, SCALE)
        return (out.astype(jnp.float32) * g.astype(jnp.float32)).sum(), out

    grads, out = jax.grad(f, argnums=(0, 1, 2), has_aux=True)(q, k, v)
    return (out, *grads)


# heads: 8 at group 4 and size 64, so that the query heads' lane blocks
# 2 and 3 read KV lane block 0's SECOND head
@pytest.mark.parametrize("sweeps", ["one_sweep", "two_sweeps"])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("token_major", [False, True],
                         ids=["head_major", "token_major"])
@pytest.mark.parametrize("group", [1, 2, 4])
def test_grouped_kv_against_repeated_k_and_v(group, token_major, d,
                                             sweeps):
    h = 8
    hkv = h // group
    q, k, v, g = _operands(2, h, hkv, d)
    want = _repeated(q, k, v, g, group)
    call = dict(causal=True, scale=SCALE, block_q=BLOCK, block_k=BLOCK,
                impl="interpret")
    ops, heads = (q, k, v, g), None
    if token_major:
        ops, heads = tuple(pk._merge_heads(x) for x in ops), h
    before = _counts("flash_attention_layout"), \
        _counts("flash_attention_kv_heads")
    out, lse = pk._flash_attention_fwd(*ops[:3], heads=heads, **call)
    if sweeps == "one_sweep":
        grads = pk._flash_attention_bwd(*ops[:3], out, lse, ops[3],
                                        heads=heads, **call)
    else:
        grads = pk._flash_bwd_pallas(
            *ops[:3], out, lse.reshape(2 * h, T), ops[3],
            one_sweep_vmem=None,
            **pk._call_args(ops[0], ops[1], heads=heads, **call)[1])
    # in place: no transposition inside, and the grouping counted once
    # a forward (equal counts add no series)
    layouts = _counts("flash_attention_layout") - before[0]
    assert set(layouts) == {"token_major" if token_major
                            else "head_major"}
    assert _counts("flash_attention_kv_heads") - before[1] == (
        {"grouped": 1} if group > 1 else {})
    assert lse.shape == (2, h, T)
    got = (out, *grads)
    if token_major:
        got = tuple(pk._split_heads(x, n)
                    for x, n in zip(got, (h, h, hkv, hkv)))
    for name, a, w in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(w),
            atol=2e-5 * max(1.0, float(jnp.abs(w).max())), err_msg=name)
        assert float(jnp.abs(a).max()) > 0, name


@pytest.mark.parametrize("case", ["odd_group_d64", "one_kv_head_d64",
                                  "d32", "not_a_block_multiple"])
def test_what_the_blocks_cannot_serve_in_place(case):
    """Token-major operands whose KV heads fill no whole lane block, or
    whose lane block's query heads read two KV heads (an odd group at
    two heads a block): the entry transposes inside (`head_major`),
    still reads K and V by index map, and gives the same answer.  A
    length that is no multiple of the blocks is padded as ever."""
    h, hkv, d, t = {"odd_group_d64": (6, 2, 64, T),
                    "one_kv_head_d64": (4, 1, 64, T),
                    "d32": (4, 2, 32, T),
                    "not_a_block_multiple": (8, 2, 64, 40)}[case]
    rng = np.random.RandomState(3)
    q, k, v, g = (jnp.asarray(rng.randn(1, n, t, d), jnp.float32)
                  for n in (h, hkv, hkv, h))
    want = _repeated(q, k, v, g, h // hkv)
    call = dict(causal=True, scale=SCALE, block_q=BLOCK, block_k=BLOCK,
                impl="interpret", heads=h)
    ops = tuple(pk._merge_heads(x) for x in (q, k, v, g))
    before = _counts("flash_attention_layout")
    out, lse = pk._flash_attention_fwd(*ops[:3], **call)
    grads = pk._flash_attention_bwd(*ops[:3], out, lse, ops[3], **call)
    assert _counts("flash_attention_layout") - before == {
        "token_major" if case == "not_a_block_multiple"
        else "head_major": 2}
    for a, w, n in zip((out, *grads), want, (h, h, hkv, hkv)):
        np.testing.assert_allclose(
            np.asarray(pk._split_heads(a, n)), np.asarray(w),
            atol=2e-5 * max(1.0, float(jnp.abs(w).max())))


def test_bf16_sums_a_group_in_float32():
    q, k, v, g = _operands(1, 8, 2, 64, jnp.bfloat16)
    want = _repeated(*(x.astype(jnp.float32) for x in (q, k, v, g)), 4)
    call = dict(causal=True, scale=SCALE, block_q=BLOCK, block_k=BLOCK,
                impl="interpret", heads=8)
    ops = tuple(pk._merge_heads(x) for x in (q, k, v, g))
    out, lse = pk._flash_attention_fwd(*ops[:3], **call)
    grads = pk._flash_attention_bwd(*ops[:3], out, lse, ops[3], **call)
    for a, w, n in zip((out, *grads), want, (8, 8, 2, 2)):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(pk._split_heads(a, n), np.float32), np.asarray(w),
            atol=3e-2 * max(1.0, float(jnp.abs(w).max())))


def test_query_heads_that_are_no_multiple_raise():
    q, k, v, _ = _operands(1, 6, 4, 64)
    with pytest.raises(ValueError, match="no whole multiple"):
        pk._flash_attention_fwd(q, k, v, causal=True, impl="interpret")
    with pytest.raises(ValueError, match="no whole multiple"):
        pk._flash_attention_fwd(
            *(pk._merge_heads(x) for x in (q, k, v)), causal=True,
            impl="interpret", heads=6)


def test_off_the_chip_k_and_v_are_repeated_and_counted_so():
    """The XLA impl is plain attention on K and V repeated in memory:
    the right answer, and a series of its own, so that a cell whose
    configuration names `grouped` is not correct if this ran."""
    q, k, v, g = _operands(1, 4, 2, 64)
    want = _repeated(q, k, v, g, 2)
    before = _counts("flash_attention_kv_heads")
    got = jax.grad(lambda *a: (pk.flash_attention(
        *a, causal=True, scale=SCALE, impl="xla") * g).sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert _counts("flash_attention_kv_heads") - before == {"repeated": 1}
    for a, w in zip(got, want[1:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(w),
                                   atol=2e-5 * float(jnp.abs(w).max()))


# -- through the IR -----------------------------------------------------------

def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


def test_the_op_and_its_grad_on_saved_residuals(interpret):
    """layers.flash_attention(n_head=8, n_kv_head=2) on token-major
    projections: K@GRAD and V@GRAD at the KV heads' width, the grad op
    on the forward's Out and LSE, equal to plain attention's."""
    from paddle_tpu import backward

    _fresh()
    h, hkv, d = 8, 2, 64
    qv = layers.data("q", shape=[T, h * d], dtype="float32")
    kv = layers.data("k", shape=[T, hkv * d], dtype="float32")
    vv = layers.data("v", shape=[T, hkv * d], dtype="float32")
    for var in (qv, kv, vv):
        var.stop_gradient = False
    out = layers.flash_attention(qv, kv, vv, causal=True, scale=SCALE,
                                 n_head=h, n_kv_head=hkv, block_q=BLOCK,
                                 block_k=BLOCK)
    assert tuple(out.shape[1:]) == (T, h * d)
    loss = layers.reduce_sum(layers.square(out))
    grads = backward.gradients([loss], [qv, kv, vv])
    q, k, v, _ = _operands(2, h, hkv, d, seed=5)
    feed = {n: np.asarray(pk._merge_heads(x))
            for n, x in zip("qkv", (q, k, v))}
    before = _counts("flash_attention_grad"), \
        _counts("flash_attention_kv_heads")
    got = fluid.Executor(fluid.CPUPlace()).run(
        feed=feed, fetch_list=[out] + grads)
    assert _counts("flash_attention_grad") - before[0] == {"saved": 1}
    assert _counts("flash_attention_kv_heads") - before[1] == {
        "grouped": 1}
    ref_out = _repeated(q, k, v, jnp.zeros_like(q), 4)[0]
    want = _repeated(q, k, v, 2.0 * ref_out, 4)
    for a, w, n in zip(got, want, (h, h, hkv, hkv)):
        w = np.asarray(pk._merge_heads(w))
        assert a.shape == w.shape
        np.testing.assert_allclose(a, w,
                                   atol=5e-5 * max(1.0, np.abs(w).max()))


def test_n_kv_head_checks_k_against_it():
    _fresh()
    q = layers.data("q", shape=[T, 8 * 64], dtype="float32")
    k = layers.data("k", shape=[T, 2 * 64], dtype="float32")
    with pytest.raises(ValueError, match="n_kv_head says 4"):
        layers.flash_attention(q, k, k, n_head=8, n_kv_head=4)
    with pytest.raises(ValueError, match="n_kv_head says 3"):
        layers.flash_attention(
            q, layers.data("k3", shape=[T, 3 * 64], dtype="float32"),
            k, n_head=8, n_kv_head=3)


# -- ouro_model with grouped KV heads ----------------------------------------

@pytest.mark.parametrize("kv_heads", [1, 2])
def test_ouro_model_with_grouped_kv_heads(kv_heads):
    """`ouro_model` raised NotImplementedError at num_key_value_heads
    != num_attention_heads before the kernels had a head-group map.
    Its reference keeps equal counts, so the comparison is with the
    reference on K and V projections REPEATED to the query heads'
    count: the loss and every gradient, a KV projection's as the sum
    over its group."""
    import test_ouro_model as t
    from conftest import load_reference
    from paddle_tpu.core.scope import global_scope
    from paddle_tpu.models.ouro import ouro_model

    ref = load_reference("ouro")

    config = dict(t.SMALL, num_key_value_heads=kv_heads,
                  total_ut_steps=2)
    heads, d = config["num_attention_heads"], config["head_dim"]
    group = heads // kv_heads
    t._fresh()
    np.random.seed(0)
    model = ouro_model(config, seq_len=t.SEQ)
    from paddle_tpu import optimizer

    params_grads = optimizer.SGD(0.0).backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    block = fluid.default_main_program().global_block()
    assert block.var("ouro_l0_k.w").shape == (config["hidden_size"],
                                              kv_heads * d)
    get = lambda n: jnp.array(  # noqa: E731
        global_scope().find_var(n).get(), copy=True)
    params = ref.read_params(config, get)

    def widen(w):
        c = w.shape[0]
        return jnp.repeat(w.reshape(c, kv_heads, d), group,
                          axis=1).reshape(c, heads * d)

    ids, labels = ref._split(t._batch(config))

    def loss_of(p):
        # the gradient of a KV projection is, through the repeat, the
        # sum over the query heads of its group
        wide = dict(p, layers=[dict(lw, k=widen(lw["k"]), v=widen(lw["v"]))
                               for lw in p["layers"]])
        return ref.batch_loss(wide, ids, labels, config)

    batch = t._batch(config)
    want_loss, want_grads = jax.value_and_grad(loss_of)(params)
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"]] + [g for _, g in params_grads])
    assert float(np.asarray(outs[0]).reshape(-1)[0]) == pytest.approx(
        float(want_loss), rel=1e-5)
    names = jax.tree_util.tree_leaves(ref.param_names(config))
    want = dict(zip(names, jax.tree_util.tree_leaves(want_grads)))
    for (p, _), o in zip(params_grads, outs[1:]):
        w = np.asarray(want[p.name])
        assert o.shape == w.shape, p.name
        assert np.abs(np.asarray(o) - w).max() \
            <= 1e-4 * np.abs(w).max(), p.name

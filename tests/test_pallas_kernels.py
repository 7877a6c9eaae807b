"""Flash-attention Pallas kernel tests (interpret mode on CPU) +
IR-op wiring + transformer fused-attention equivalence.

Mirrors the reference OpTest pattern (op_test.py:134): numpy/XLA
reference vs kernel output, plus grad check through custom_vjp.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas_kernels import _plain_attention, flash_attention


def _rand_qkv(rng, b, h, tq, tk, d):
    q = jnp.asarray(rng.randn(b, h, tq, d).astype(np.float32))
    k = jnp.asarray(rng.randn(b, h, tk, d).astype(np.float32))
    v = jnp.asarray(rng.randn(b, h, tk, d).astype(np.float32))
    return q, k, v


@pytest.mark.parametrize("shape,causal", [
    ((2, 4, 128, 128, 64), False),
    ((2, 4, 128, 128, 64), True),
    ((1, 2, 100, 100, 32), True),     # non-multiple of block -> padding
    ((1, 2, 64, 128, 64), False),     # cross attention Tq != Tk
    ((1, 1, 8, 8, 16), True),         # tiny
    ((1, 2, 16, 5, 16), True),        # tq > tk causal: fully-masked rows
])
def test_flash_matches_reference(shape, causal):
    b, h, tq, tk, d = shape
    rng = np.random.RandomState(0)
    q, k, v = _rand_qkv(rng, b, h, tq, tk, d)
    with jax.default_matmul_precision("float32"):
        out = flash_attention(q, k, v, causal=causal, impl="interpret",
                              block_q=32, block_k=32)
        ref = _plain_attention(q, k, v, causal, 1.0 / np.sqrt(d))
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_flash_grad_matches_reference():
    rng = np.random.RandomState(1)
    q, k, v = _rand_qkv(rng, 1, 2, 32, 32, 16)
    with jax.default_matmul_precision("float32"):
        g1 = jax.grad(lambda a: flash_attention(
            a, k, v, causal=True, impl="interpret", block_q=16,
            block_k=16).sum())(q)
        g2 = jax.grad(lambda a: _plain_attention(
            a, k, v, True, 0.25).sum())(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   atol=2e-5)


@pytest.mark.parametrize("shape,causal", [
    ((2, 2, 64, 64, 32), False),
    ((2, 2, 64, 64, 32), True),
    ((1, 2, 100, 100, 32), True),     # padding (non-multiple blocks)
    ((1, 2, 48, 96, 32), False),      # cross attention Tq != Tk
    ((1, 1, 16, 5, 16), True),        # tq > tk: fully-masked rows
])
def test_flash_pallas_bwd_matches_reference(shape, causal):
    """The dedicated Pallas backward (dq, dk, dv) vs the XLA replay,
    under a NON-uniform cotangent so every term (delta, ds) matters."""
    b, h, tq, tk, d = shape
    rng = np.random.RandomState(2)
    q, k, v = _rand_qkv(rng, b, h, tq, tk, d)
    w = jnp.asarray(rng.randn(b, h, tq, d).astype(np.float32))

    def loss(fn):
        def inner(a, bb, c):
            return (fn(a, bb, c) * w).sum()
        return inner

    with jax.default_matmul_precision("float32"):
        flash = loss(lambda a, bb, c: flash_attention(
            a, bb, c, causal=causal, impl="interpret", block_q=32,
            block_k=32))
        plain = loss(lambda a, bb, c: _plain_attention(
            a, bb, c, causal, 1.0 / np.sqrt(d)))
        g1 = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(plain, argnums=(0, 1, 2))(q, k, v)
    for name, a, bq in zip("q k v".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bq),
                                   atol=3e-5, err_msg=f"d{name}")


def test_amp_rewrite_keeps_flash_inputs_low_precision():
    """flash_attention is AMP-whitelisted: under the bf16 rewrite no
    fp32 back-cast may feed it (an unlisted op gets its low-precision
    inputs cast BACK to fp32 — exactly what would quietly throw away
    the kernel's bf16 bandwidth win on chip)."""
    import paddle_tpu as fluid  # noqa: F401
    from paddle_tpu import framework, optimizer
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.models.transformer import transformer_encoder_model

    np.random.seed(0)
    model = transformer_encoder_model(
        vocab_size=200, max_len=16, d_model=32, n_head=2, d_inner=64,
        n_layer=1, dropout_rate=0.0)
    decorate(optimizer.SGD(0.1), init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    gb = framework.default_main_program().global_block()
    flash_ops = [op for op in gb.ops if op.type == "flash_attention"]
    assert flash_ops
    for op in flash_ops:
        ins = [n for ns in op.inputs.values() for n in ns]
        assert not [n for n in ins if n.endswith(".cast_float32")], ins


def test_flash_bf16_fwd_bwd_close_to_f32():
    """The AMP path feeds bf16 q/k/v into the kernel on TPU: forward
    and backward must stay within bf16 tolerance of the f32 reference
    (accumulation is f32 inside the kernel)."""
    rng = np.random.RandomState(7)
    q32, k32, v32 = _rand_qkv(rng, 1, 2, 64, 64, 32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q32, k32, v32))
    w = jnp.asarray(rng.randn(1, 2, 64, 32).astype(np.float32))
    sc = 1.0 / np.sqrt(32)

    with jax.default_matmul_precision("float32"):
        out_b = flash_attention(qb, kb, vb, causal=True,
                                impl="interpret", block_q=32,
                                block_k=32)
        assert out_b.dtype == jnp.bfloat16
        ref = _plain_attention(q32, k32, v32, True, sc)
        np.testing.assert_allclose(
            np.asarray(out_b.astype(jnp.float32)), np.asarray(ref),
            atol=0.04)  # bf16 has ~2-3 decimal digits

        g_b = jax.grad(lambda a: (flash_attention(
            a, kb, vb, causal=True, impl="interpret", block_q=32,
            block_k=32).astype(jnp.float32) * w).sum())(qb)
        g_r = jax.grad(lambda a: (_plain_attention(
            a, k32, v32, True, sc) * w).sum())(q32)
        assert g_b.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            np.asarray(g_b.astype(jnp.float32)), np.asarray(g_r),
            atol=0.1)


def _merge_lse(o1, l1, o2, l2):
    m = jnp.maximum(l1, l2)
    a1 = jnp.exp(l1 - m)[..., None]
    a2 = jnp.exp(l2 - m)[..., None]
    o = (o1 * a1 + o2 * a2) / (a1 + a2)
    return o, m + jnp.log(a1[..., 0] + a2[..., 0])


def test_flash_lse_split_kv_merge_matches_whole():
    """(out, lse) is a complete mergeable summary: attention over KV
    split in two chunks, merged, equals attention over the whole KV —
    for values AND gradients (grads flow through lse via the merge,
    exercising the dlse term of the Pallas backward)."""
    from paddle_tpu.ops.pallas_kernels import flash_attention_lse

    b, h, t, d = 1, 2, 64, 32
    rng = np.random.RandomState(3)
    q, k, v = _rand_qkv(rng, b, h, t, t, d)
    w = jnp.asarray(rng.randn(b, h, t, d).astype(np.float32))
    sc = 1.0 / np.sqrt(d)

    def split_loss(q, k, v):
        o1, l1 = flash_attention_lse(q, k[:, :, :t // 2],
                                     v[:, :, :t // 2],
                                     impl="interpret", block_q=32,
                                     block_k=32, scale=sc)
        o2, l2 = flash_attention_lse(q, k[:, :, t // 2:],
                                     v[:, :, t // 2:],
                                     impl="interpret", block_q=32,
                                     block_k=32, scale=sc)
        o1 = o1.astype(jnp.float32)
        o2 = o2.astype(jnp.float32)
        # lse is padded to the q block; t==64 is block-aligned here
        o, _ = _merge_lse(o1, l1.reshape(b, h, t),
                          o2, l2.reshape(b, h, t))
        return (o * w).sum()

    def whole_loss(q, k, v):
        return (_plain_attention(q, k, v, False, sc) * w).sum()

    with jax.default_matmul_precision("float32"):
        v1, g1 = jax.value_and_grad(split_loss, argnums=(0, 1, 2))(
            q, k, v)
        v2, g2 = jax.value_and_grad(whole_loss, argnums=(0, 1, 2))(
            q, k, v)
    np.testing.assert_allclose(float(v1), float(v2), rtol=1e-5)
    for name, a, bq in zip("q k v".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bq),
                                   atol=3e-5, err_msg=f"d{name}")


def test_flash_lse_grad_non_block_aligned():
    """Regression: lse (and its cotangent) is q-block padded; the
    backward must slice, not reshape — T=48 with block 32 pads to 64."""
    from paddle_tpu.ops.pallas_kernels import flash_attention_lse

    rng = np.random.RandomState(4)
    q, k, v = _rand_qkv(rng, 1, 2, 48, 48, 16)
    w = jnp.asarray(rng.randn(1, 2, 48, 16).astype(np.float32))
    sc = 0.25

    def loss(a, b, c):
        o, lse = flash_attention_lse(a, b, c, impl="interpret",
                                     block_q=32, block_k=32, scale=sc)
        return (o * w).sum() + (lse[:, :48] * 0.01).sum()

    def ref(a, b, c):
        s = jnp.einsum("bhqd,bhkd->bhqk", a, b) * sc
        o = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), c)
        lse = jax.scipy.special.logsumexp(s, axis=-1)
        return (o * w).sum() + (lse.reshape(2, 48) * 0.01).sum()

    with jax.default_matmul_precision("float32"):
        g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, bq in zip("q k v".split(), g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bq),
                                   atol=3e-5, err_msg=f"d{name}")


def test_flash_attention_ir_op():
    """The flash_attention op runs through Executor + CompiledProgram."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, layers

    rng = np.random.RandomState(0)
    qkv = rng.randn(3, 2, 2, 16, 8).astype(np.float32)
    q = layers.data("q", shape=[2, 16, 8], dtype="float32")
    k = layers.data("k", shape=[2, 16, 8], dtype="float32")
    v = layers.data("v", shape=[2, 16, 8], dtype="float32")
    out = layers.flash_attention(q, k, v, causal=True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = {"q": qkv[0], "k": qkv[1], "v": qkv[2]}
    (o1,) = exe.run(framework.default_main_program(), feed=feed,
                    fetch_list=[out])
    compiled = fluid.CompiledProgram(framework.default_main_program())
    (o2,) = exe.run(compiled, feed=feed, fetch_list=[out])
    ref = _plain_attention(jnp.asarray(qkv[0]), jnp.asarray(qkv[1]),
                           jnp.asarray(qkv[2]), True, 8 ** -0.5)
    np.testing.assert_allclose(o1, np.asarray(ref), atol=1e-3)
    np.testing.assert_allclose(o2, np.asarray(ref), atol=1e-3)


def test_flash_attention_ir_op_block_override(monkeypatch):
    """block_q/block_k attrs thread layer -> op -> kernel entry and
    keep numerics identical to the default tiling (commit 09cb16f).
    The kernel entry is spied on: on CPU the impl auto-resolves to
    plain XLA (which ignores tiles), so only a capture proves the
    op -> kernel half of the plumbing."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, layers
    from paddle_tpu.ops import pallas_kernels

    seen = {}
    real = pallas_kernels._flash_attention_fwd   # the op's kernel entry

    def spy(q, k, v, **kw):
        seen.update(kw)
        return real(q, k, v, **kw)

    monkeypatch.setattr(pallas_kernels, "_flash_attention_fwd", spy)

    rng = np.random.RandomState(1)
    qkv = rng.randn(3, 1, 2, 40, 8).astype(np.float32)
    q = layers.data("q", shape=[2, 40, 8], dtype="float32")
    k = layers.data("k", shape=[2, 40, 8], dtype="float32")
    v = layers.data("v", shape=[2, 40, 8], dtype="float32")
    out = layers.flash_attention(q, k, v, causal=True, block_q=16,
                                 block_k=8)
    op = framework.default_main_program().global_block().ops[-1]
    assert op.attrs["block_q"] == 16 and op.attrs["block_k"] == 8
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(framework.default_startup_program())
    feed = {"q": qkv[0], "k": qkv[1], "v": qkv[2]}
    (o1,) = exe.run(framework.default_main_program(), feed=feed,
                    fetch_list=[out])
    assert seen.get("block_q") == 16 and seen.get("block_k") == 8
    ref = _plain_attention(jnp.asarray(qkv[0]), jnp.asarray(qkv[1]),
                           jnp.asarray(qkv[2]), True, 8 ** -0.5)
    np.testing.assert_allclose(o1, np.asarray(ref), atol=1e-3)
    # unset blocks reach the kernel entry unset (None/0) so the
    # kernel's size-aware default (_default_block) decides
    seen.clear()
    q2 = layers.data("q2", shape=[2, 40, 8], dtype="float32")
    out2 = layers.flash_attention(q2, k, v, causal=True)
    exe.run(framework.default_main_program(),
            feed={**feed, "q2": qkv[0]}, fetch_list=[out2])
    assert not seen.get("block_q") and not seen.get("block_k")
    from paddle_tpu.ops.pallas_kernels import _default_block
    assert _default_block(40) == 512      # short seq keeps 512
    assert _default_block(32768) == 1024  # long seq gets the sweep pick


def test_impl_autodetect_keys_on_device_not_backend(monkeypatch):
    """Auto-detection asks the default DEVICE for its platform: a TPU
    chip picks the Pallas kernel, anything else the XLA form."""
    from paddle_tpu.ops import pallas_kernels as pk

    class _TpuDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(pk.jax, "devices", lambda: [_TpuDev()])
    assert pk._on_tpu() is True

    class _CpuDev:
        platform = "cpu"
        device_kind = "cpu"

    monkeypatch.setattr(pk.jax, "devices", lambda: [_CpuDev()])
    assert pk._on_tpu() is False


def test_transformer_fused_vs_unfused():
    """Fused-attention transformer == unfused composition (is_test mode)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu.core.program import Program
    from paddle_tpu.core.scope import Scope, scope_guard
    from paddle_tpu.models.transformer import transformer_encoder_model

    rng = np.random.RandomState(0)
    src = rng.randint(0, 64, (2, 16, 1)).astype(np.int64)
    outs = {}
    for fused in (True, False):
        framework.switch_main_program(Program())
        framework.switch_startup_program(Program())
        from paddle_tpu import unique_name
        unique_name.switch({})
        np.random.seed(7)  # same param init both times
        import paddle_tpu.models.transformer as tr
        orig = tr.multi_head_attention
        if not fused:
            def unfused(*a, **kw):
                kw["use_flash"] = False
                return orig(*a, **kw)
            tr.multi_head_attention = unfused
        try:
            model = transformer_encoder_model(
                vocab_size=64, max_len=16, d_model=32, n_head=4,
                d_inner=64, n_layer=1, dropout_rate=0.0, is_test=True)
        finally:
            tr.multi_head_attention = orig
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(framework.default_startup_program())
            (loss,) = exe.run(
                framework.default_main_program(),
                feed={"src_ids": src, "tgt_label": src},
                fetch_list=[model["loss"]])
        outs[fused] = float(loss)
    assert np.isfinite(outs[True])
    np.testing.assert_allclose(outs[True], outs[False], rtol=2e-3)


def test_transformer_flash_branch_has_no_head_split_or_merge(monkeypatch):
    """The flash branch hands the op the q, k, v projections as they
    are and the output projection the op's Out: no transpose (and no
    reshape) op in the program, forward or backward.  Three Adam steps
    give the losses of the same weights through the rank-4 op between
    `_split_heads` and the merge, which is what the branch was; both on
    the XLA impl, where the rank-3 op makes those transposes inside."""
    import paddle_tpu as fluid
    import paddle_tpu.models.transformer as tr
    from paddle_tpu import framework, layers, optimizer, unique_name
    from paddle_tpu.core.program import Program
    from paddle_tpu.core.scope import Scope, scope_guard

    def rank4(q, k, v, causal=False, n_head=None):
        tq, tk, width = q.shape[1], k.shape[1], q.shape[2]
        q = tr._split_heads(q, tq, n_head, width // n_head)
        k = tr._split_heads(k, tk, n_head, width // n_head)
        v = tr._split_heads(v, tk, n_head, width // n_head)
        out = layers.transpose(
            flash(q, k, v, causal=causal), [0, 2, 1, 3])
        return layers.reshape(out, [-1, tq, width])

    flash = layers.flash_attention
    ids = np.random.RandomState(0).randint(0, 64, (2, 16, 1)) \
        .astype(np.int64)
    losses = {}
    for which in ("rank3", "rank4"):
        framework.switch_main_program(Program())
        framework.switch_startup_program(Program())
        unique_name.switch({})
        np.random.seed(7)                  # same param init both times
        if which == "rank4":
            monkeypatch.setattr(layers, "flash_attention", rank4)
        model = tr.transformer_encoder_model(
            vocab_size=64, max_len=16, d_model=128, n_head=2, d_inner=64,
            n_layer=2, dropout_rate=0.0)
        optimizer.Adam(1e-2).minimize(model["loss"])
        prog = framework.default_main_program()
        types = [op.type for op in prog.global_block().ops]
        assert types.count("flash_attention") == 2
        assert types.count("flash_attention_grad") == 2
        moved = [t for t in types if t.startswith(("transpose",
                                                   "reshape"))]
        assert bool(moved) == (which == "rank4"), moved
        with scope_guard(Scope()):
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(framework.default_startup_program())
            compiled = fluid.CompiledProgram(prog)
            losses[which] = [
                float(exe.run(compiled,
                              feed={"src_ids": ids, "tgt_label": ids},
                              fetch_list=[model["loss"]])[0])
                for _ in range(3)]
    assert losses["rank3"][2] < losses["rank3"][0]
    assert losses["rank3"] == losses["rank4"]


# ---------------------------------------------------------------------------
# Mosaic TPU lowering legality — interpret mode never enforces the
# (8, 128) last-two-dims block tiling rule, so a kernel can pass every
# CPU test and still be rejected by the real-chip lowering (this
# exact failure shipped in round 4: a [1, bq] lse block spec crashed
# the first on-TPU transformer bench).  jax.export cross-lowers for
# the tpu platform on CPU, running the Mosaic block-mapping checks.
# ---------------------------------------------------------------------------

# q, k and v shapes; the head count of token-major operands; causal
LOWERING = {
    "tfm_base": (((32, 8, 512, 64),) * 3, None, True),
    "bert_base": (((8, 16, 512, 64),) * 3, None, False),
    "padding": (((1, 2, 100, 64),) * 3, None, True),
    "cross_tiny_q": (((1, 1, 8, 64),) + ((1, 1, 136, 64),) * 2, None,
                     False),
    # token-major, as the cells' projections leave q, k and v: two
    # heads a lane block (`_s8k`), one (`ouro`), grouped KV heads read
    # in place (`granite4`)
    "token_major_d64": (((4, 8192, 512),) * 3, 8, True),
    "token_major_d128": (((1, 4096, 2048),) * 3, 16, True),
    "token_major_32q_8kv": (((1, 8192, 2048),) + ((1, 8192, 512),) * 2,
                            32, True),
    # latent attention, head-major as `xing4` and `dsv2` feed it: q.k
    # 192, v 128
    "latent_192_128": (((1, 16, 4096, 192),) * 2 + ((1, 16, 4096, 128),),
                       None, True),
}


@pytest.mark.parametrize("case", sorted(LOWERING))
def test_flash_tpu_lowering_is_legal(case):
    from jax import export

    from paddle_tpu.ops.pallas_kernels import flash_attention_lse

    shapes, heads, causal = LOWERING[case]
    q, k, v = (jnp.zeros(shape, jnp.bfloat16) for shape in shapes)

    def step(q, k, v):
        return jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=causal, impl="pallas", heads=heads)
            .astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    export.export(jax.jit(step), platforms=("tpu",))(q, k, v)
    if heads is not None or v.shape[-1] != q.shape[-1]:
        return    # flash_attention_lse: head-major, one head size

    def step_lse(q, k, v):
        return flash_attention_lse(q, k, v, causal=causal,
                                   impl="pallas")

    export.export(jax.jit(step_lse), platforms=("tpu",))(q, k, v)

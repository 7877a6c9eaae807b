"""models/evabyte.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/evabyte.py on seeded weights: the loss,
ALL num_pred_heads x vocab logits a position and EVERY parameter's
gradient (the pooling vectors mu and phi among them), over four windows
(so that the chunk keys of one, two and three earlier windows are
read); each wrong model of the reference, which the comparison has to
see; the norm's unit offset; the scopes, the counters and the kernels a
step holds.

The reference puts a window's token scores and the earlier chunks'
scores side by side under one softmax, a window of queries at a time;
the program pools with eva_pool and aggregates with eva_attention (off
the chip their XLA forms, in interpret mode four causal flash calls and
the staircase merged by their log-sum-exp).

Tolerances, and why.

* float32: the same mathematics in another order: loss to 1e-5, logits
  to 1e-5 of the largest logit, gradients to 1e-4 of each parameter's
  largest entry.  bf16 anywhere fails this:
  `test_float32_tolerance_excludes_bf16`.
* AMP (bf16 matmul operands, q, k, v and the summaries; the residual
  stream, the norms' statistics, mu, phi and the pooling softmaxes
  float32): logits to 3e-2 of the largest logit, the loss to 1e-3,
  gradients to 0.15 of each parameter's largest entry (`mellum2`'s
  bound).

`WIDE` draws every matrix from N(0, 0.2) in place of N(0, 0.02): at 128
channels the published 0.02 leaves the scores so flat that a softmax
over the wrong keys, or a chunk's mean in place of its softmax, would
hardly show.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.evabyte import evabyte_model
from paddle_tpu.ops import pallas_kernels as pk

from conftest import load_reference

ref = load_reference("evabyte")

SEQ, BATCH = 64, 2

SMALL = {
    "attention_class": "eva", "hidden_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "intermediate_size": 192, "num_hidden_layers": 2,
    "window_size": 16, "chunk_size": 4, "num_pred_heads": 3,
    "vocab_size": 40, "rope_theta": 100000, "rms_norm_eps": 1e-5,
    "norm_add_unit_offset": True, "attention_bias": False,
    "hidden_act": "silu", "tie_word_embeddings": False,
    "initializer_range": 0.02, "param_prefix": "evabyte",
}
WIDE = dict(SMALL, initializer_range=0.2)
# what the kernels tile: heads of 128 token-major, a window of 128
# tokens in chunks of 8, so 16 chunk keys a window
LANE = dict(WIDE, hidden_size=256, num_attention_heads=2,
            num_key_value_heads=2, window_size=128, chunk_size=8,
            initializer_range=0.05)
AMP_SMALL = dict(SMALL, initializer_range=0.05)

F32 = {"loss": 1e-5, "logits": 1e-5, "grad": 1e-4}
AMP = {"loss": 1e-3, "logits": 3e-2, "grad": 0.15}


def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _build(config, amp, recompute, opt=None, seq=SEQ):
    _fresh()
    np.random.seed(0)
    model = evabyte_model(config, seq_len=seq)
    opt = opt or optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    return model, opt


def _batch(config, seed=0, seq=SEQ):
    """As builders/evabyte.py: one stream, label [t, p] = id t + 1 + p."""
    n_pred = config["num_pred_heads"]
    stream = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (BATCH, seq + n_pred), dtype=np.int64)
    ahead = np.arange(seq)[:, None] + 1 + np.arange(n_pred)[None, :]
    return stream[:, :seq, None], stream[:, ahead][..., None]


def _scope_params(config):
    # copies: the step donates the weights
    return jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))


def _impl_counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}


def _spread(config):
    """mu, phi and the norms' offsets as the layer draws them are small
    or zero; spread them, so that a comparison that ignored them would
    show."""
    rng = np.random.default_rng(5)
    names = ref.param_names(config)
    for lw in names["layers"]:
        for key, scale in (("mu", 1.0), ("phi", 1.0), ("attn_norm", 0.3),
                           ("ffn_norm", 0.3)):
            var = global_scope().find_var(lw[key])
            var.set(jnp.asarray(rng.normal(0, scale, np.shape(var.get())),
                                jnp.float32))


def _run(config, amp, recompute, seq=SEQ):
    """{loss, logits, grads} of the program (and `used`, the kernel
    impls its step counted) and of the reference."""
    model, opt = _build(config, amp, recompute, seq=seq)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _spread(config)
    batch = _batch(config, seq=seq)
    params = _scope_params(config)
    ids32, labels32 = ref._split(batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids32, labels32, config))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jnp.stack([
            ref.head_logits(ref.sequence_state(params, i, config),
                            params["head"], config) for i in ids32])
    names = ref.param_names(config)
    want = {"loss": float(want_loss), "logits": np.asarray(want_logits),
            "grads": dict(zip(jax.tree_util.tree_leaves(names),
                              jax.tree_util.tree_leaves(want_grads)))}
    before = _impl_counts()
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"], model["logits"]]
                   + [g for _, g in params_grads])
    got = {"used": _since(before),
           "loss": float(np.asarray(outs[0]).reshape(-1)[0]),
           "logits": np.asarray(outs[1], np.float32),
           "grads": {p.name: np.asarray(o, np.float32)
                     for (p, _), o in zip(params_grads, outs[2:])}}
    return got, want, params


@functools.lru_cache(maxsize=None)
def _wide():
    """`_run(WIDE, False, False)`, once for the tests that read it."""
    return _run(WIDE, False, False)


def _grad_errors(got, want):
    """|got - want| at its largest over the parameter's largest
    |want|, by parameter (a gradient that IS zero: over 1)."""
    return {n: float(np.abs(got[n] - np.asarray(w)).max()
                     / (np.abs(np.asarray(w)).max() or 1.0))
            for n, w in want.items()}


def _logits_error(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _check(got, want, tol, unread=()):
    """unread: the parameters nothing reads in this configuration (mu
    and phi where no chunk key is seen, or a chunk is one token), whose
    gradients are zero on both sides."""
    assert set(got["grads"]) == set(want["grads"])
    assert got["loss"] == pytest.approx(want["loss"], rel=tol["loss"])
    assert got["logits"].shape == want["logits"].shape
    assert _logits_error(got["logits"], want["logits"]) <= tol["logits"]
    errors = _grad_errors(got["grads"], want["grads"])
    assert max(errors.values()) <= tol["grad"], \
        sorted(errors.items(), key=lambda kv: -kv[1])[:5]
    # every parameter has a gradient that is not zero, mu and phi too:
    # none of the comparisons above is of 0 with 0
    assert sorted(n for n, w in want["grads"].items()
                  if not np.abs(np.asarray(w)).max() > 0) == sorted(unread)
    assert all(not np.abs(got["grads"][n]).max() for n in unread)


@pytest.fixture
def interpret(monkeypatch):
    """The kernels' auto-impl resolves to their interpret mode: the
    program then runs the pooling, staircase and flash kernels on the
    CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


CASES = {
    "f32": (SMALL, False, False, F32, SEQ),
    "f32_wide": (WIDE, False, False, F32, SEQ),
    "f32_wide_recompute": (WIDE, False, True, F32, SEQ),
    # one window and less: causal attention, no chunk key is read
    "f32_one_window": (dict(WIDE, window_size=64), False, False, F32, SEQ),
    "f32_less_than_a_window": (dict(WIDE, window_size=64), False, True,
                               F32, 48),
    # chunks of one token: every earlier token is its own summary
    "f32_chunks_of_one": (dict(WIDE, chunk_size=1), False, False, F32,
                          SEQ),
    "f32_no_unit_offset": (dict(WIDE, norm_add_unit_offset=False), False,
                           False, F32, SEQ),
    "amp_recompute": (AMP_SMALL, True, True, AMP, SEQ),
}


# where mu and phi move nothing: no chunk key is read, or a chunk's
# softmax is over its one token
UNREAD = ("f32_one_window", "f32_less_than_a_window", "f32_chunks_of_one")


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol, seq = CASES[case]
    got, want, _ = _wide() if case == "f32_wide" \
        else _run(config, amp, recompute, seq)
    _check(got, want, tol, unread=[
        "evabyte_l%d_eva_%s.w" % (i, part) for i in range(2)
        for part in ("mu", "phi")] if case in UNREAD else ())
    assert got["logits"].shape == (BATCH, seq, 3, 40)
    if config["initializer_range"] == 0.02:
        # random weights at this width give small logits
        assert 0.9 * np.log(40) < want["loss"] < 1.1 * np.log(40)
    assert got["used"][("eva_attention", "xla")] > 0
    assert got["used"][("eva_pool", "xla")] > 0


def test_kernels_in_interpret_mode_against_reference(interpret):
    """The same comparison with the pooling kernels, four causal flash
    windows and the staircase in the program, inside recompute
    segments: two heads of 128 token-major, 512 bytes in four windows
    of 128, 16 chunk keys a window."""
    got, want, _ = _run(LANE, False, True, seq=512)
    _check(got, want, F32)
    used = got["used"]
    assert used[("eva_attention", "interpret")] == 2
    assert used[("eva_pool", "interpret")] == 2
    assert used[("eva_attention_grad", "saved")] == 2
    # the window part: a flash call a layer forward, one backward
    assert used[("flash_attention", "interpret")] == 2
    assert used[("flash_attention_layout", "token_major")] > 0
    assert not [k for k in used if k[1] in ("xla", "recompute",
                                            "head_major")]


def test_float32_tolerance_excludes_bf16():
    got, want, _ = _run(AMP_SMALL, True, False)
    assert _logits_error(got["logits"], want["logits"]) \
        > 20 * F32["logits"]
    assert max(_grad_errors(got["grads"], want["grads"]).values()) \
        > 20 * F32["grad"]


# what each wrong model misses the float32 logits' tolerance by, at the
# least
VARIANTS = {"mean_pool": 100, "no_chunks": 1000, "sliding_window": 1000,
            "one_head": 1000}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_comparison_sees_each_wrong_model(variant):
    """The reference with a chunk's mean in place of its two softmaxes,
    without the chunk keys, with a sliding band in place of the aligned
    window, or with head 0 read for every head, is another model: the
    program's logits, which equal the reference's to 1e-5 of the
    largest, are a hundred to a thousand times further from it, and so
    is the loss from the loss's tolerance."""
    assert set(VARIANTS) == set(ref.VARIANTS)
    got, want, params = _wide()
    assert _logits_error(got["logits"], want["logits"]) <= F32["logits"]
    batch = _batch(WIDE)
    wrong = np.asarray(ref.logits(params, batch, WIDE, variant=variant))
    assert _logits_error(wrong, want["logits"]) \
        > VARIANTS[variant] * F32["logits"]
    wrong_loss = ref.loss(params, batch, WIDE, variant=variant)
    assert abs(wrong_loss - want["loss"]) > 20 * F32["loss"] * want["loss"]
    # the right model by the same entries: the jitted pieces against
    # the layers traced whole, another order of the same float32 sums
    assert _logits_error(np.asarray(ref.logits(params, batch, WIDE)),
                         want["logits"]) <= F32["logits"]
    assert ref.loss(params, batch, WIDE) == pytest.approx(want["loss"],
                                                          rel=1e-6)


def test_every_fourth_position_of_the_logits():
    _, want, params = _wide()
    assert _logits_error(
        np.asarray(ref.logits(params, _batch(WIDE), WIDE, every=4)),
        want["logits"][:, ::4]) <= F32["logits"]


def test_the_labels_are_the_next_bytes_of_one_stream():
    """Head p at position t is held to byte t + 1 + p: the label feed's
    column p is the input shifted by 1 + p (where the input has it)."""
    ids, labels = _batch(SMALL)
    assert ids.shape == (BATCH, SEQ, 1)
    assert labels.shape == (BATCH, SEQ, 3, 1)
    for p in range(3):
        np.testing.assert_array_equal(labels[:, :SEQ - 1 - p, p, 0],
                                      ids[:, 1 + p:, 0])


# -- the norm's unit offset ------------------------------------------------------

def _norm_program(unit_offset):
    _fresh()
    x = layers.data("x", shape=[8, 32], dtype="float32")
    y = layers.rms_norm(x, 1e-5, name="n", unit_offset=unit_offset)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return x, y, exe


def test_rms_norm_unit_offset_is_one_plus_w():
    rng = np.random.default_rng(3)
    xs = rng.normal(0, 2, (4, 8, 32)).astype(np.float32)
    w = rng.normal(0, 0.5, 32).astype(np.float32)
    _, y, exe = _norm_program(True)
    # the parameter starts at 0: the identity scale
    np.testing.assert_array_equal(
        np.asarray(global_scope().find_var("n.w").get()), 0.0)
    global_scope().find_var("n.w").set(jnp.asarray(w))
    got, = exe.run(feed={"x": xs}, fetch_list=[y])
    want = xs / np.sqrt((xs * xs).mean(-1, keepdims=True) + 1e-5) * (1 + w)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # and without it the parameter starts at 1 and multiplies as it is
    _, y, exe = _norm_program(False)
    np.testing.assert_array_equal(
        np.asarray(global_scope().find_var("n.w").get()), 1.0)
    global_scope().find_var("n.w").set(jnp.asarray(w))
    got, = exe.run(feed={"x": xs}, fetch_list=[y])
    np.testing.assert_allclose(got, want / (1 + w) * w, rtol=1e-5,
                               atol=1e-6)


def test_a_norm_without_the_offset_traces_to_the_parents_jaxpr():
    """The op without `unit_offset` is the computation it was before
    the attr: x in float32, the statistic, the scale in float32, the
    product, the cast: the jaxpr of the parent's lines, written out."""
    from paddle_tpu.core.registry import get_op_def

    op = get_op_def("rms_norm")

    def parent(x, scale):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + 1e-6) \
            * scale.astype(jnp.float32)
        return y.astype(x.dtype)

    x = jax.ShapeDtypeStruct((4, 8, 32), jnp.bfloat16)
    w = jax.ShapeDtypeStruct((32,), jnp.float32)
    for attrs in ({"epsilon": 1e-6},
                  {"epsilon": 1e-6, "unit_offset": False}):
        now = jax.make_jaxpr(lambda x, w: op.compute(
            {"X": x, "Scale": w}, op.canonical_attrs(attrs))["Y"])(x, w)
        assert str(now) == str(jax.make_jaxpr(parent)(x, w))
    offset = jax.make_jaxpr(lambda x, w: op.compute(
        {"X": x, "Scale": w}, op.canonical_attrs(
            {"epsilon": 1e-6, "unit_offset": True}))["Y"])(x, w)
    assert str(offset) != str(jax.make_jaxpr(parent)(x, w))


# -- the program ---------------------------------------------------------------

def test_program_is_verified_and_shape_checked():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes

    model, opt = _build(SMALL, True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    program = fluid.default_main_program()
    verifier.verify(program)
    _, diags = infer_program_shapes(program)
    assert not [d for d in diags if d.severity == "error"], diags
    assert len(model["checkpoints"]) == SMALL["num_hidden_layers"]
    block = program.global_block()
    types = [op.type for op in block.ops]
    assert {"rms_norm", "eva_pool", "eva_attention", "rotary_embedding",
            "swiglu", "recompute_segment_grad"} <= set(types)
    assert "flash_attention" not in types
    eva = [op for op in block.ops if op.type in ("eva_pool",
                                                 "eva_attention")]
    assert len(eva) == 4
    assert all((op.attrs["heads"], op.attrs["window"], op.attrs["chunk"],
                op.attrs["impl"]) == (4, 16, 4, "") for op in eva)
    assert all(op.attrs["unit_offset"] for op in block.ops
               if op.type == "rms_norm")
    rotary = [op for op in block.ops if op.type == "rotary_embedding"]
    assert [(op.attrs["pairing"], op.attrs["theta"], op.attrs["n_head"])
            for op in rotary] == [("halves", 1e5, 4)] * 4
    names = {p.name for p in program.all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(SMALL)))
    # untied: an embedding, and the three heads as ONE matrix
    assert block.var("evabyte_emb.w").shape == (40, 128)
    assert block.var("evabyte_head.w").shape == (128, 3 * 40)
    assert block.var("evabyte_l0_q.w").shape == (128, 128)
    assert block.var("evabyte_l0_gate.w").shape == (128, 192)
    assert block.var("evabyte_l0_eva_mu.w").shape == (4, 32)
    assert block.var("evabyte_l1_eva_phi.w").shape == (4, 32)
    assert block.var("tgt_label").shape[1:] == (SEQ, 3, 1)


def test_mu_and_phi_start_clipped_and_scaled():
    """N(0, 1) clipped to +-1, times d^-1/2: a chunk's weights differ
    visibly from its mean from the first step."""
    _build(SMALL, False, False)
    fluid.Executor(fluid.CPUPlace()).run(fluid.default_startup_program())
    for part in ("mu", "phi"):
        w = np.asarray(global_scope().find_var(
            "evabyte_l0_eva_%s.w" % part).get())
        assert np.abs(w).max() <= 32 ** -0.5 + 1e-7
        assert np.isclose(np.abs(w), 32 ** -0.5).mean() > 0.2   # clipped
        assert w.std() > 0.5 * 32 ** -0.5


@pytest.mark.parametrize("key,value", [
    ("attention_class", "softmax"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("rope_scaling", {"type": "linear", "factor": 2}), ("num_chunks", 8),
    ("fp32_ln", True), ("num_key_value_heads", 2)])
def test_what_is_not_built_raises(key, value):
    _fresh()
    with pytest.raises(NotImplementedError, match=key):
        evabyte_model(dict(SMALL, **{key: value}), seq_len=SEQ)


@pytest.mark.parametrize("seq,config", [
    (66, SMALL),                            # not whole chunks
    (40, SMALL),                            # past a window, not whole
    (64, dict(SMALL, chunk_size=3))])       # the chunk divides no window
def test_lengths_that_do_not_fit_are_refused(seq, config):
    _fresh()
    with pytest.raises(ValueError, match="eva_attention"):
        evabyte_model(config, seq_len=seq)


def test_scopes_counters_and_the_kernels_of_a_step(interpret):
    """The compiled step of RecomputeOptimizer(Adam) under AMP: the
    computes' named scopes and the builder's name scopes are in its op
    metadata; every layer runs pt_eva_pool_fwd, pt_flash_fwd and
    pt_eva_chunk_fwd once (the segment's replay reads the saved
    outputs) and pt_eva_pool_bwd, pt_flash_bwd_dkv and pt_eva_chunk_bwd
    once."""
    model, opt = _build(LANE, True, True, optimizer.Adam(1e-3), seq=512)
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _impl_counts()
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(LANE, seq=512)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    first, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    second, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    assert float(np.asarray(second).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])
    used = _since(before)
    assert used[("eva_attention", "interpret")] == 2
    assert used[("eva_pool", "interpret")] == 2
    assert used[("eva_attention_grad", "saved")] == 2
    assert used[("flash_attention_bwd", "fused")] == 2
    step, = [v for v in compiled._cache.values() if callable(v)]
    state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for n, v in
             ((n, global_scope().find_var(n).get())
              for n in compiled._persistable_names)}
    text = step.lower(state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in feed.items()}).as_text(
                                  debug_info=True)
    for scope in ("pt_evabyte_eva_attention", "pt_evabyte_ffn",
                  "pt_evabyte_head", "pt_eva_pool", "pt_eva_attention",
                  "pt_rms_norm", "pt_swiglu"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
    for kernel in ("pt_eva_pool_fwd", "pt_eva_pool_bwd",
                   "pt_eva_chunk_fwd", "pt_eva_chunk_bwd", "pt_flash_fwd",
                   "pt_flash_bwd_dkv"):
        assert kernel in text, kernel

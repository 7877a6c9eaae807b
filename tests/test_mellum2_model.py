"""models/mellum2.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/mellum2.py on seeded weights: the loss,
the logits and EVERY parameter's gradient, with both kinds of layer
present (three window layers to one full one, each kind with its own
rotary parameters); the window, the YaRN frequencies with their
attention factor and the renormalised gates, which the comparison has
to see; the share test that ties the one-chip cut (16 of 64 experts) to
the whole layer; the scopes, the counters and the kernels a step holds.

The reference writes the mask as two inequalities over all keys,
repeats K and V to the query heads, computes its own YaRN frequencies
and loops over the held experts with a mask; the program runs
grouped-KV flash attention with a window (off the chip: plain attention
with one), the rotary op and sorted grouped matmuls.

Tolerances, and why.

* float32: the same mathematics in another order: loss to 1e-5, logits
  to 1e-5 of the largest logit, gradients to 1e-4 of each parameter's
  largest entry.  bf16 anywhere fails this:
  `test_float32_tolerance_excludes_bf16`.
* AMP (bf16 matmul operands, attention and the expert rows; the
  residual stream, the router and the norms' statistics float32):
  logits to 3e-2 of the largest logit, the loss to 1e-3, gradients to
  0.15 of each parameter's largest entry (`xing4`'s, `ling3`'s and
  `lfm2`'s bound).

`WIDE` draws every matrix from N(0, 0.2) in place of N(0, 0.02): at 128
channels the published 0.02 leaves the scores so flat that a softmax
over the wrong keys would hardly show.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.mellum2 import layer_kinds, mellum2_model, rotary_of
from paddle_tpu.ops import pallas_kernels as pk

from conftest import load_reference

ref = load_reference("mellum2")

SEQ, BATCH = 64, 2

YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}
# published layers 1-4: sliding, sliding, full, sliding (of a pattern of
# three to one): both kinds, the full one not last
SMALL = {
    "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32,
    "moe_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 5, 9],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "num_hidden_layers": 4, "kept_layers": [1, 2, 3, 4],
    "layer_types": ["sliding_attention", "sliding_attention",
                    "sliding_attention", "full_attention"] * 2,
    "mlp_layer_types": ["sparse"] * 8,
    "sliding_window": 12, "use_sliding_window": True,
    "max_window_layers": 0, "attention_bias": False,
    "hidden_act": "silu", "rms_norm_eps": 1e-6,
    "rope_parameters": {
        "full_attention": YARN,
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "tie_word_embeddings": False, "vocab_size": 96,
    "initializer_range": 0.02, "param_prefix": "mellum2",
}
WIDE = dict(SMALL, initializer_range=0.2)
# what the kernels tile: heads of 128 token-major, two on one KV head
LANE = dict(WIDE, num_attention_heads=2, num_key_value_heads=1,
            head_dim=128, initializer_range=0.1)
# matrices from N(0, 0.05): scores that are not flat, and selections
# that a bf16 rounding upstream does not flip
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
    model = mellum2_model(config, seq_len=seq)
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
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (BATCH, seq, 1), dtype=np.int64)
    return ids, np.roll(ids, -1, axis=1)


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


def _run(config, amp, recompute, seq=SEQ):
    """{loss, logits, grads} of the program (and `used`, the kernel
    impls its step counted) and of the reference."""
    model, opt = _build(config, amp, recompute, seq=seq)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = _batch(config, seq=seq)
    params = _scope_params(config)
    ids32, labels32 = ref._split(batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids32, labels32, config))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jnp.stack([ref.sequence_logits(params, i, config)
                                 for i in ids32])
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
    |want|, by parameter."""
    return {n: float(np.abs(got[n] - np.asarray(w)).max()
                     / np.abs(np.asarray(w)).max())
            for n, w in want.items()}


def _logits_error(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


def _check(got, want, tol):
    assert set(got["grads"]) == set(want["grads"])
    assert got["loss"] == pytest.approx(want["loss"], rel=tol["loss"])
    assert _logits_error(got["logits"], want["logits"]) <= tol["logits"]
    errors = _grad_errors(got["grads"], want["grads"])
    assert max(errors.values()) <= tol["grad"], \
        sorted(errors.items(), key=lambda kv: -kv[1])[:5]
    # every parameter has a gradient that is not zero: the reference's
    # too, so none of the comparisons above is of 0 with 0
    assert all(np.abs(np.asarray(w)).max() > 0
               for w in want["grads"].values())


@pytest.fixture
def interpret(monkeypatch):
    """The kernels' auto-impl resolves to their interpret mode: the
    program then runs the flash (window and full), grouped-matmul and
    combine kernels on the CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


CASES = {
    "f32": (SMALL, False, False, F32),
    "f32_wide": (WIDE, False, False, F32),
    "f32_wide_recompute": (WIDE, False, True, F32),
    # the published period, full attention last, every expert held
    "f32_period_all_held": (dict(
        SMALL, kept_layers=[0, 1, 2, 3], num_experts=16,
        held_experts=list(range(16))), False, True, F32),
    # a window of two keys (of one, q and k have no gradient: its
    # probability is 1), and one that reaches every key
    "f32_window_2": (dict(WIDE, sliding_window=2), False, False, F32),
    "f32_window_past_the_sequence": (dict(WIDE, sliding_window=4096),
                                     False, False, F32),
    "amp_recompute": (AMP_SMALL, True, True, AMP),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol = CASES[case]
    got, want, _ = _wide() if case == "f32_wide" \
        else _run(config, amp, recompute)
    _check(got, want, tol)
    if config["initializer_range"] == 0.02:
        # random weights at this width give small logits
        assert 0.9 * np.log(96) < want["loss"] < 1.1 * np.log(96)
    # off the chip plain attention repeats K and V to the query heads
    assert got["used"][("flash_attention_kv_heads", "repeated")] > 0
    assert got["used"][("moe_route_scoring", "softmax")] > 0


def test_kernels_in_interpret_mode_against_reference(interpret):
    """The same comparison with the flash kernels (the window layers'
    on their band grid, under their own names' entries), the
    grouped-matmul and the combine kernels in the program, inside
    recompute segments: two query heads of 128 on one KV head, token-
    major, 256 tokens in two blocks of 128 under a window of 12."""
    got, want, _ = _run(LANE, False, True, seq=256)
    _check(got, want, F32)
    used = got["used"]
    assert used[("flash_attention", "interpret")] == 4
    assert used[("flash_attention_grad", "saved")] == 4
    # forward and backward of the three window layers; the full layer
    # adds no series
    assert used[("flash_attention_window", "band")] == 6
    # ... whose 256 tokens are one block of its own default
    assert used[("flash_attention_causal_fetch", "held")] == 6
    assert used[("flash_attention_causal_fetch", "all_live")] == 2
    assert used[("flash_attention_layout", "token_major")] > 0
    assert used[("flash_attention_kv_heads", "grouped")] > 0
    assert used[("moe_gmm", "interpret")] > 0
    assert not [k for k in used if k[1] in ("xla", "recompute",
                                            "repeated", "head_major")]


def test_float32_tolerance_excludes_bf16():
    got, want, _ = _run(AMP_SMALL, True, False)
    assert _logits_error(got["logits"], want["logits"]) \
        > 20 * F32["logits"]
    assert max(_grad_errors(got["grads"], want["grads"]).values()) \
        > 20 * F32["grad"]


# what each wrong model misses the float32 logits' tolerance by, at the
# least (read: 2,900, 33,000 and 4,900 times)
VARIANTS = {"no_window": 1000, "no_yarn": 1000,
            "gates_not_renormalised": 1000}


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_the_comparison_sees_each_wrong_model(variant):
    """The reference with every layer full, without the YaRN scaling
    and its attention factor on the full layer, or with the gates as
    the softmax over all 16 gave them, is another model: the program's
    logits, which equal the reference's to 1e-5 of the largest, are a
    thousand times further from it and more, and so is the loss from
    the loss's tolerance."""
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


def test_every_fourth_token_of_the_logits():
    _, want, params = _wide()
    assert _logits_error(
        np.asarray(ref.logits(params, _batch(WIDE), WIDE, every=4)),
        want["logits"][:, ::4]) <= F32["logits"]


# -- the share: four expert-parallel ranks make the uncut layer --------------

def _layer_weights(rng, c=32, w=16, e=64, heads=4, kv=2, d=8):
    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"attn_norm": 1 + 0.1 * mat(c), "ffn_norm": 1 + 0.1 * mat(c),
            "q": mat(c, heads * d), "k": mat(c, kv * d),
            "v": mat(c, kv * d), "o": mat(heads * d, c),
            "router": mat(c, e),
            "experts": {"gate": mat(e, c, w), "up": mat(e, c, w),
                        "down": mat(e, w, c)}}


LAYER = {"num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 8, "sliding_window": 10, "rms_norm_eps": 1e-6,
         "rope_parameters": SMALL["rope_parameters"],
         "num_experts_per_tok": 8, "norm_topk_prob": True,
         "num_experts": 64}


def _route_op(u, lw):
    route = get_op_def("moe_route")
    return route.compute({"X": u, "W": lw["router"]}, route.canonical_attrs({
        "k": 8, "norm_topk_prob": True, "scoring_func": "softmax"}))


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_four_shares_add_up_to_the_whole_layer(impl, kind):
    """64 experts over 4 chips, 16 held on each (ids 0-15, 16-31, 32-47,
    48-63; the cell holds the first).  Attention and the router are
    computed alike on every chip and counted ONCE; the routed parts the
    4 shares give add to them to make the uncut layer of the reference
    (no shared expert: nothing else is computed alike); and each share
    of the PROGRAM's ops (the router over all 64, then the held
    experts) equals the reference's share."""
    rng = np.random.default_rng(11)
    lw = _layer_weights(rng)
    x = jnp.asarray(rng.normal(0, 1, (48, 32)), jnp.float32)
    everyone = list(range(64))
    with jax.default_matmul_precision("highest"):
        whole = ref.layer(x, lw, dict(LAYER, held_experts=everyone), kind)
        mixed = x + ref.attention_mixer(
            ref.rms_norm(x, lw["attn_norm"], 1e-6), lw, LAYER, kind)
        u = ref.rms_norm(mixed, lw["ffn_norm"], 1e-6)
    shares = [list(range(s, s + 16)) for s in range(0, 64, 16)]

    def stack_of(held):
        return {k: v[jnp.asarray(held)] for k, v in lw["experts"].items()}

    experts = get_op_def("moe_experts")
    r = _route_op(u, lw)
    # a token's 8 experts lie on several of the chips, and not all of
    # any token's on chip 0: the shares are real parts
    chips_hit = {len(set(row // 16)) for row in np.asarray(r["TopkIdx"])}
    assert min(chips_hit) >= 2 and max(chips_hit) == 4
    total = mixed
    for held in shares:
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(u, dict(lw, experts=stack_of(held)),
                                  LAYER, held=held)
        st = stack_of(held)
        mine = experts.compute(
            {"X": u, "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
             "WGate": st["gate"], "WUp": st["up"], "WDown": st["down"]},
            experts.canonical_attrs({"held": held, "block_m": 16,
                                     "impl": impl}))["Out"]
        np.testing.assert_allclose(mine, part, rtol=1e-4, atol=1e-5)
        assert np.abs(np.asarray(part)).max() > 0
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    # the gates of a token sum to 1 over ALL chips
    np.testing.assert_allclose(np.asarray(r["TopkWeight"]).sum(-1), 1.0,
                               rtol=1e-6)
    # and the reference's selection and gates are the op's
    selected, scores = ref.route(u, lw, LAYER)
    gates = np.asarray(ref.gates(selected, scores, LAYER))
    np.testing.assert_allclose(
        np.take_along_axis(gates, np.asarray(r["TopkIdx"]), axis=1),
        r["TopkWeight"], rtol=1e-6)


def test_the_references_yarn_is_the_ops():
    """Two statements of the YaRN frequencies, the op's
    (ops/llm_ops.py yarn_inv_freq) and the reference's own, at the
    published numbers: head size 128, theta 5e5, factor 16 over 8,192
    positions, beta 32 / 1."""
    from paddle_tpu.ops.llm_ops import yarn_inv_freq

    rope = dict(YARN, original_max_position_embeddings=8192)
    freq, factor = ref.rotary_frequencies(rope, 128)
    np.testing.assert_allclose(
        freq, yarn_inv_freq(128, 5e5, 16.0, 8192, 32.0, 1.0), rtol=1e-6)
    assert factor == pytest.approx(0.1 * np.log(16) + 1)
    plain, one = ref.rotary_frequencies(
        {"rope_type": "default", "rope_theta": 500000}, 128)
    np.testing.assert_allclose(plain, yarn_inv_freq(128, 5e5, 1.0, 8192,
                                                    32.0, 1.0), rtol=1e-6)
    assert one == 1.0
    # the fast pairs are kept, the slow ones divided by 16, a ramp between
    assert freq[0] == plain[0] and freq[-1] == pytest.approx(plain[-1] / 16)
    assert np.all(np.diff(freq / plain) <= 1e-12)
    assert rotary_of(dict(SMALL, rope_parameters={
        "full_attention": rope}), "full_attention") == {
            "theta": 500000, "factor": 16, "original_max_position": 8192,
            "beta_fast": 32, "beta_slow": 1,
            "mscale": 1.2772588722239782}


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
    assert {"rms_norm", "flash_attention", "rotary_embedding", "moe_route",
            "moe_experts", "recompute_segment_grad"} <= set(types)
    assert layer_kinds(SMALL) == ["sliding_attention", "sliding_attention",
                                  "full_attention", "sliding_attention"]
    # the window is the flash op's, by the layer's kind
    flash = [op for op in block.ops if op.type == "flash_attention"]
    assert [op.attrs["window"] for op in flash] == [12, 12, 0, 12]
    assert all(op.attrs["causal"] and op.attrs["heads"] == 4
               and op.attrs["block_q"] == 0 for op in flash)
    # ... and so are the rotary parameters: q and k of each layer
    rotary = [op for op in block.ops if op.type == "rotary_embedding"]
    assert [(op.attrs["pairing"], op.attrs["theta"], op.attrs["factor"],
             op.attrs["original_max_position"], op.attrs["mscale"])
            for op in rotary] == (
        [("halves", 5e5, 1.0, 4096, 1.0)] * 4
        + [("halves", 5e5, 16.0, 16, 1.2772588722239782)] * 2
        + [("halves", 5e5, 1.0, 4096, 1.0)] * 2)
    names = {p.name for p in program.all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(SMALL)))
    # untied: an embedding and a head
    assert block.var("mellum2_emb.w").shape == (96, 128)
    assert block.var("mellum2_head.w").shape == (128, 96)
    assert block.var("mellum2_l0_q.w").shape == (128, 128)
    assert block.var("mellum2_l0_k.w").shape == (128, 64)
    assert block.var("mellum2_l0_o.w").shape == (128, 128)
    assert block.var("mellum2_l0_router.w").shape == (128, 16)
    assert block.var("mellum2_l0_experts_gate.w").shape == (4, 128, 64)
    # the softmax router has no selection bias, no shared expert is built
    assert not [n for n in names if "bias" in n or "shared" in n]
    route, = {(op.attrs["scoring_func"], op.attrs["norm_topk_prob"],
               op.attrs["routed_scaling_factor"], op.attrs["k"])
              for op in block.ops if op.type == "moe_route"}
    assert route == ("softmax", True, 1.0, 4)
    # every expert layer keeps its load ring
    assert len([op for op in block.ops if op.type == "step_stat"]) == 4


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("use_qk_norm", True), ("qk_norm", True),
    ("n_shared_experts", 1), ("num_shared_experts", 2),
    ("tie_word_embeddings", True), ("hidden_act", "gelu"),
    ("use_sliding_window", False), ("max_window_layers", 14),
    ("num_nextn_predict_layers", 1)])
def test_what_is_not_built_raises(key, value):
    _fresh()
    with pytest.raises(NotImplementedError, match=key):
        mellum2_model(dict(SMALL, **{key: value}), seq_len=SEQ)


def test_another_rope_type_kind_or_feed_forward_raises():
    _fresh()
    rope = dict(SMALL["rope_parameters"], full_attention={
        "rope_type": "llama3", "rope_theta": 5e5})
    with pytest.raises(NotImplementedError, match="rope_type"):
        mellum2_model(dict(SMALL, rope_parameters=rope), seq_len=SEQ)
    _fresh()
    with pytest.raises(NotImplementedError, match="chunked"):
        mellum2_model(dict(SMALL, layer_types=["chunked_attention"] * 8),
                      seq_len=SEQ)
    _fresh()
    with pytest.raises(NotImplementedError, match="mlp_layer_types"):
        mellum2_model(dict(SMALL, mlp_layer_types=["dense"] * 8),
                      seq_len=SEQ)


@pytest.mark.parametrize("kept", [[0, 2, 3], [0, 3, 2, 4], [0, 2, 3, 9],
                                  [-1, 0, 1, 2], [1, 1, 2, 3]])
def test_kept_layers_that_do_not_fit_raise(kept):
    with pytest.raises(ValueError, match="kept_layers"):
        layer_kinds(dict(SMALL, kept_layers=kept))
    assert layer_kinds({k: v for k, v in SMALL.items()
                        if k != "kept_layers"}) \
        == SMALL["layer_types"][:4]


def test_scopes_counters_and_the_kernels_of_a_step(interpret):
    """The compiled step of RecomputeOptimizer(Adam) under AMP: the
    computes' named scopes and the builder's name scopes are in its op
    metadata; every window layer runs pt_flash_win_fwd once (the
    segment's replay reads the saved Out and LSE) and the one-sweep
    pt_flash_win_bwd_dkv, the full layer pt_flash_fwd and
    pt_flash_bwd_dkv."""
    model, opt = _build(LANE, True, True, optimizer.Adam(1e-3), seq=256)
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _impl_counts()
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(LANE, seq=256)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    first, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    second, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    assert float(np.asarray(second).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])
    used = _since(before)
    assert used[("flash_attention", "interpret")] == 4
    assert used[("flash_attention_grad", "saved")] == 4
    assert used[("flash_attention_window", "band")] == 6
    assert used[("flash_attention_bwd", "fused")] == 4
    assert used[("moe_route_scoring", "softmax")] > 0
    step, = [v for v in compiled._cache.values() if callable(v)]
    state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for n, v in
             ((n, global_scope().find_var(n).get())
              for n in compiled._persistable_names)}
    text = step.lower(state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in feed.items()}).as_text(
                                  debug_info=True)
    for scope in ("pt_mellum2_window_attention", "pt_mellum2_full_attention",
                  "pt_mellum2_ffn", "pt_mellum2_head", "pt_moe_route",
                  "pt_moe_experts", "pt_rms_norm"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
    for kernel in ("pt_flash_win_fwd", "pt_flash_win_bwd_dkv",
                   "pt_flash_fwd", "pt_flash_bwd_dkv"):
        assert kernel in text, kernel
    assert "pt_flash_win_bwd_dq" not in text
    from paddle_tpu.observability import step_stats

    rings = step_stats.read()
    assert sorted(rings) == ["mellum2_l%d_experts.load" % i
                             for i in range(4)]
    assert all(len(r["columns"]) == 4 + 2 for r in rings.values())

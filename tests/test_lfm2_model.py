"""models/lfm2.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/lfm2.py on seeded weights: the loss,
the logits and EVERY parameter's gradient, with every kind of layer
present (conv and attention mixers, a dense and an expert
feed-forward); the input gate, the norm on q and k and the KV grouping,
which the comparison has to see; the share test that ties the one-chip
cut (16 of 64 experts) to the whole layer; the selection bias, which
selects and does not weigh; the scopes, the counters and the kernels a
step holds.

The reference convolves K shifted copies of a padded array, repeats K
and V to the query heads and loops over the held experts with a mask;
the program runs one gated causal_conv1d op on the whole projection,
grouped-KV flash attention and sorted grouped matmuls.

Tolerances, and why.

* float32: the same mathematics in another order (fused ops, a flash
  softmax by blocks, a sorted grouped matmul against a masked loop):
  loss to 1e-5, logits to 1e-5 of the largest logit, gradients to 1e-4
  of each parameter's largest entry.  bf16 anywhere fails this:
  `test_float32_tolerance_excludes_bf16`.
* AMP (bf16 matmul operands, the projection the convolution reads,
  attention and the expert rows; the residual stream, the router and
  the norms' statistics float32): logits to 3e-2 of the largest logit,
  the loss to 1e-3, gradients to 0.15 of each parameter's largest
  entry (`xing4`'s and `ling3`'s bound; the worst here are the norm
  scales on q and k, sums over every token of differences of rounded
  terms, and the router).

`WIDE` draws every matrix from N(0, 0.2) in place of N(0, 0.02): at 128
channels the published 0.02 leaves the gates' product too small for a
test to see a missing gate.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.core.registry import get_op_def
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.lfm2 import layer_kinds, lfm2_model
from paddle_tpu.ops import pallas_kernels as pk

from conftest import load_reference

ref = load_reference("lfm2")

SEQ, BATCH = 64, 2

# published layers 0 (conv, dense) and 2-5 (attention, conv, conv, conv)
SMALL = {
    "hidden_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 256,
    "moe_intermediate_size": 64, "num_experts": 4,
    "num_experts_published": 16, "held_experts": [0, 1, 5, 9],
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 1.0, "use_expert_bias": True,
    "num_hidden_layers": 5, "num_dense_layers": 1,
    "kept_layers": [0, 2, 3, 4, 5],
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv",
                    "conv", "full_attention", "conv"],
    "conv_L_cache": 3, "conv_bias": False, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 96, "initializer_range": 0.02, "param_prefix": "lfm2",
}
WIDE = dict(SMALL, initializer_range=0.2)
# what the kernels tile: 128-lane thirds, two heads of 64 a lane block
LANE = dict(WIDE, num_attention_heads=2, num_key_value_heads=1)
# no expert layer: no selection can flip under a bf16 rounding.
# Matrices from N(0, 0.05): the gates' product counts, and the logits'
# error stays at 0.02 of the largest (0.03 at N(0, 0.1): three rounded
# factors meet in every conv layer's product)
AMP_DENSE = dict(SMALL, initializer_range=0.05, num_dense_layers=5)

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
    model = lfm2_model(config, seq_len=seq)
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


def _perturb(config, bias_sd, seed=5):
    """What starts where a test would see nothing: the selection bias
    (zeros: it would select nothing; sd 0.1, the scores' spread, or ten
    times that under AMP: a selection that a bf16 rounding upstream
    does not flip) and the norm scales on q and k (ones: a scale that
    is dropped, or applied to the wrong head, would go unseen)."""
    rng = np.random.default_rng(seed)
    for layer in ref.param_names(config)["layers"]:
        for key, mean, sd in (("router_bias", 0.0, bias_sd),
                              ("q_norm", 1.0, 0.3), ("k_norm", 1.0, 0.3)):
            if key in layer:
                var = global_scope().find_var(layer[key])
                var.set(jnp.asarray(
                    rng.normal(mean, sd, np.shape(var.get())),
                    jnp.float32))


def _impl_counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}


def _run(config, amp, recompute, bias_sd=0.1, seq=SEQ):
    """{loss, logits, grads} of the program (and `used`, the kernel
    impls its step counted) and of the reference."""
    model, opt = _build(config, amp, recompute, seq=seq)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    _perturb(config, bias_sd)
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
    # the selection bias selects and is not trained: no gradient
    for layer in names["layers"]:
        want["grads"].pop(layer.get("router_bias"), None)
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


def _grad_errors(got, want):
    """|got - want| at its largest over the parameter's largest
    |want|, by parameter."""
    return {n: float(np.abs(got[n] - np.asarray(w)).max()
                     / np.abs(np.asarray(w)).max())
            for n, w in want.items()}


def _check(got, want, tol):
    assert set(got["grads"]) == set(want["grads"])
    assert got["loss"] == pytest.approx(want["loss"], rel=tol["loss"])
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        <= tol["logits"] * scale
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
    program then runs the Pallas convolution, flash, grouped-matmul and
    combine kernels on the CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


CASES = {
    "f32": (SMALL, False, False, F32, 0.1),
    "f32_wide": (WIDE, False, False, F32, 0.1),
    "f32_wide_recompute": (WIDE, False, True, F32, 0.1),
    # attention first, every layer an expert layer, four taps
    "f32_attention_first": (dict(WIDE, kept_layers=[2, 3, 6, 7],
                                 num_hidden_layers=4, num_dense_layers=0,
                                 conv_L_cache=4), False, True, F32, 0.1),
    "amp_recompute": (SMALL, True, True, AMP, 1.0),
    "amp_dense_recompute": (AMP_DENSE, True, True, AMP, 1.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol, bias_sd = CASES[case]
    got, want, _ = _run(config, amp, recompute, bias_sd)
    _check(got, want, tol)
    if config["initializer_range"] == 0.02:
        # random weights at this width give small logits
        assert 0.9 * np.log(96) < want["loss"] < 1.1 * np.log(96)
    kinds = layer_kinds(config)
    assert got["used"][("causal_conv1d_gates", "xla")] \
        >= kinds.count("conv")
    # off the chip plain attention repeats K and V to the query heads
    assert got["used"][("flash_attention_kv_heads", "repeated")] > 0
    if config["num_dense_layers"] < len(kinds):
        assert got["used"][("moe_route_scoring", "sigmoid")] > 0


def test_kernels_in_interpret_mode_against_reference(interpret):
    """The same comparison with pt_conv1d_fwd, pt_conv1d_bwd, the
    flash, the grouped-matmul and the combine kernels in the program,
    inside recompute segments: thirds of 128 lanes, two query heads of
    64 on one KV head."""
    got, want, _ = _run(LANE, False, True)
    _check(got, want, F32)
    used = got["used"]
    assert used[("causal_conv1d", "interpret")] >= 4
    assert used[("causal_conv1d_gates", "fused")] >= 4
    assert used[("flash_attention", "interpret")] == 1
    assert used[("flash_attention_kv_heads", "grouped")] > 0
    assert used[("moe_gmm", "interpret")] > 0
    # q's two heads of 64 fill a lane tile and pt_rotary turns them;
    # the ONE key head of 64 fills none, so k's op is the XLA form
    # (ISSUE 54; the cell's 8 key heads are four lane tiles)
    assert used[("rotary", "interpret")] > 0
    assert [k for k in used if k[1] in ("xla", "recompute", "repeated")] \
        == [("rotary", "xla")]


def test_float32_tolerance_excludes_bf16():
    got, want, _ = _run(AMP_DENSE, True, False)
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        > 20 * F32["logits"] * scale
    assert max(_grad_errors(got["grads"], want["grads"]).values()) \
        > 20 * F32["grad"]


@pytest.mark.parametrize("variant", ["no_input_gate", "no_qk_norm",
                                     "repeat_first_kv"])
def test_the_comparison_sees_the_gate_the_norm_and_the_grouping(variant):
    """The reference without the convolution's input gate, without the
    norm on q and k, or with every query head on KV head 0, is another
    model: the program's loss, which equals the reference's to 1e-5,
    is 20 times further from it and more."""
    got, want, params = _run(WIDE, False, False)
    wrong = ref.loss(params, _batch(WIDE), WIDE, variant=variant)
    assert abs(got["loss"] - want["loss"]) <= F32["loss"] * want["loss"]
    assert abs(wrong - want["loss"]) > 20 * F32["loss"] * want["loss"]
    assert ref.loss(params, _batch(WIDE), WIDE) == pytest.approx(
        want["loss"], rel=1e-6)


def _layer_weights(rng, c=32, w=16, e=64):
    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"router": mat(c, e),
            "router_bias": jnp.asarray(rng.normal(0, 0.1, e), jnp.float32),
            "experts": {"gate": mat(e, c, w), "up": mat(e, c, w),
                        "down": mat(e, w, c)}}


ROUTING = {"num_experts_per_tok": 4, "norm_topk_prob": True,
           "routed_scaling_factor": 1.0, "num_experts": 64}


def _route_op(u, lw, bias=True):
    route = get_op_def("moe_route")
    ins = {"X": u, "W": lw["router"]}
    if bias:
        ins["Bias"] = lw["router_bias"]
    return route.compute(ins, route.canonical_attrs({
        "k": 4, "norm_topk_prob": True, "routed_scaling_factor": 1.0,
        "norm_topk_eps": ref.ROUTER_NORM_EPS}))


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_four_shares_add_up_to_the_whole_layer(impl):
    """64 experts over 4 chips, 16 held on each (ids 0-15, 16-31, 32-47,
    48-63; the cell holds the first).  The routed parts the 4 shares
    give are the uncut layer of the reference (the layer has no shared
    expert: nothing is computed alike on every chip and nothing is
    counted once); and each share of the PROGRAM's ops (the router over
    all 64, then the held experts) equals the reference's share."""
    rng = np.random.default_rng(11)
    lw = _layer_weights(rng)
    u = jnp.asarray(rng.normal(0, 1, (48, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_ffn(u, lw, ROUTING, held=list(range(64)))
    shares = [list(range(s, s + 16)) for s in range(0, 64, 16)]

    def stack_of(held):
        return {k: v[jnp.asarray(held)] for k, v in lw["experts"].items()}

    experts = get_op_def("moe_experts")
    r = _route_op(u, lw)
    # a token's 4 experts lie on up to 4 of the chips, and not all of
    # any token's on chip 0: the shares are real parts
    chips_hit = {len(set(row // 16)) for row in np.asarray(r["TopkIdx"])}
    assert max(chips_hit) >= 3
    total = 0.0
    for held in shares:
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(u, dict(lw, experts=stack_of(held)),
                                  ROUTING, held=held)
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
    # the gates of a token sum to routed_scaling_factor over ALL chips,
    # less the epsilon in the denominator
    sums = np.asarray(r["TopkWeight"]).sum(-1)
    assert np.all(sums < 1.0) and np.all(sums > 1.0 - 2e-6)


def test_the_selection_bias_selects_and_does_not_weigh():
    """A bias that lifts four experts above every score selects them
    for every token, and their gates are the normalised SCORES, which
    the bias has not touched; without the bias another selection."""
    rng = np.random.default_rng(3)
    lw = _layer_weights(rng)
    lifted = [7, 20, 41, 63]
    lw["router_bias"] = jnp.zeros(64).at[jnp.asarray(lifted)].set(2.0)
    u = jnp.asarray(rng.normal(0, 1, (24, 32)), jnp.float32)
    r = _route_op(u, lw)
    assert {tuple(sorted(row)) for row in np.asarray(r["TopkIdx"])} \
        == {tuple(lifted)}
    with jax.default_matmul_precision("highest"):
        s = np.asarray(jax.nn.sigmoid(u @ lw["router"]))
    chosen = np.take_along_axis(s, np.asarray(r["TopkIdx"]), axis=1)
    np.testing.assert_allclose(
        r["TopkWeight"],
        chosen / (chosen.sum(-1, keepdims=True) + ref.ROUTER_NORM_EPS),
        rtol=1e-6)
    plain = _route_op(u, lw, bias=False)
    assert {tuple(sorted(row)) for row in np.asarray(plain["TopkIdx"])} \
        != {tuple(lifted)}
    # and the reference's selection and gates are the op's
    selected, scores = ref.route(u, lw, ROUTING)
    assert np.all(np.asarray(selected)[:, lifted])
    gates = np.asarray(ref.gates(selected, scores, ROUTING))
    np.testing.assert_allclose(
        np.take_along_axis(gates, np.asarray(r["TopkIdx"]), axis=1),
        r["TopkWeight"], rtol=1e-6)


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
    assert {"causal_conv1d", "head_gated_rms_norm", "rms_norm", "swiglu",
            "flash_attention", "rotary_embedding", "moe_route",
            "moe_experts", "recompute_segment_grad"} <= set(types)
    assert layer_kinds(SMALL) == ["conv", "full_attention", "conv",
                                  "conv", "conv"]
    # one convolution op a conv layer, gated, three taps, no bias, no
    # activation; one norm op each for q and k, without a gate
    convs = [op for op in block.ops if op.type == "causal_conv1d"]
    assert len(convs) == 4
    assert all(op.attrs["gated"] and op.attrs["activation"] == ""
               and "Bias" not in op.inputs for op in convs)
    norms = [op for op in block.ops if op.type == "head_gated_rms_norm"]
    assert [(op.attrs["n_head"], "Gate" in op.inputs) for op in norms] \
        == [(4, False), (2, False)]
    names = {p.name for p in program.all_parameters()}
    # (the router's selection bias is persistable and no parameter)
    assert names == {n for n in jax.tree_util.tree_leaves(
        ref.param_names(SMALL)) if not n.endswith("router_bias.w")}
    # the tied matrix: no head parameter
    assert not [n for n in names if "head" in n]
    assert block.var("lfm2_l0_conv_in.w").shape == (128, 3 * 128)
    assert block.var("lfm2_l0_conv.w").shape == (128, 3)
    assert block.var("lfm2_l0_conv_out.w").shape == (128, 128)
    assert block.var("lfm2_l0_down.w").shape == (256, 128)
    assert block.var("lfm2_l1_q.w").shape == (128, 128)
    assert block.var("lfm2_l1_k.w").shape == (128, 64)
    assert block.var("lfm2_l1_q_norm.w").shape == (32,)
    assert block.var("lfm2_l1_k_norm.w").shape == (32,)
    assert block.var("lfm2_l1_router.w").shape == (128, 16)
    assert block.var("lfm2_l1_experts_gate.w").shape == (4, 128, 64)
    assert "lfm2_l1_conv.w" not in names and "lfm2_l2_q.w" not in names
    route, = {(op.attrs["scoring_func"], op.attrs["norm_topk_eps"],
               op.attrs["n_group"])
              for op in block.ops if op.type == "moe_route"}
    assert route == ("sigmoid", 1e-6, 1)
    rotary = [op for op in block.ops if op.type == "rotary_embedding"]
    assert [(op.attrs["pairing"], op.attrs["theta"]) for op in rotary] \
        == [("halves", 1e6)] * 2
    # the router's bias is persistable and no optimizer op writes it
    assert block.var("lfm2_l1_router_bias.w").persistable
    assert not [op for op in block.ops
                if "lfm2_l1_router_bias.w" in op.output_names()]
    # every expert layer keeps its load ring: 4 held + routed + tiles
    stats = [op for op in block.ops if op.type == "step_stat"]
    assert len(stats) == 4


@pytest.mark.parametrize("key,value", [("conv_bias", True),
                                       ("use_expert_bias", False)])
def test_what_is_not_built_raises(key, value):
    _fresh()
    with pytest.raises(NotImplementedError, match=key):
        lfm2_model(dict(SMALL, **{key: value}), seq_len=SEQ)


def test_another_rope_type_and_an_unknown_kind_raise():
    _fresh()
    with pytest.raises(NotImplementedError, match="rope_type"):
        lfm2_model(dict(SMALL, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}), seq_len=SEQ)
    _fresh()
    with pytest.raises(NotImplementedError, match="sliding"):
        lfm2_model(dict(SMALL, layer_types=["sliding"] * 8), seq_len=SEQ)


@pytest.mark.parametrize("kept", [[0, 2, 3], [0, 3, 2, 4, 5],
                                  [0, 2, 3, 4, 9]])
def test_kept_layers_that_do_not_fit_raise(kept):
    with pytest.raises(ValueError, match="kept_layers"):
        layer_kinds(dict(SMALL, kept_layers=kept))
    assert layer_kinds({k: v for k, v in SMALL.items()
                        if k != "kept_layers"}) \
        == SMALL["layer_types"][:5]


def test_scopes_counters_and_the_kernels_of_a_step(interpret):
    """The compiled step of RecomputeOptimizer(Adam) under AMP: the
    computes' named scopes and the builder's name scopes are in its op
    metadata; every gated convolution runs pt_conv1d_fwd in the pass
    and in its segment's replay and pt_conv1d_bwd once, with its gates
    inside; the attention layer its forward kernel once; no split or
    concatenation of a projection's thirds is left in the gated
    convolution's scope."""
    model, opt = _build(LANE, True, True, optimizer.Adam(1e-3), seq=128)
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _impl_counts()
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(LANE, seq=128)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    first, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    second, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    assert float(np.asarray(second).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])
    used = _since(before)
    assert used[("causal_conv1d", "interpret")] >= 4
    assert set(k[1] for k in used if k[0] == "causal_conv1d_gates") \
        == {"fused"}
    assert used[("flash_attention", "interpret")] == 1
    assert used[("flash_attention_grad", "saved")] == 1
    assert used[("flash_attention_kv_heads", "grouped")] > 0
    assert used[("moe_route_scoring", "sigmoid")] > 0
    step, = [v for v in compiled._cache.values() if callable(v)]
    state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for n, v in
             ((n, global_scope().find_var(n).get())
              for n in compiled._persistable_names)}
    text = step.lower(state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in feed.items()}).as_text(
                                  debug_info=True)
    for scope in ("pt_gated_conv", "pt_head_rms_norm", "pt_moe_route",
                  "pt_moe_experts", "pt_lfm2_conv", "pt_lfm2_attention",
                  "pt_lfm2_ffn", "pt_lfm2_head", "pt_rms_norm",
                  "pt_swiglu"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope
    assert "pt_causal_conv1d" not in text
    assert "pt_head_gated_norm" not in text
    assert text.count("pt_conv1d_fwd") > 0 \
        and text.count("pt_conv1d_bwd") > 0
    under = [line for line in text.splitlines() if "pt_gated_conv" in line]
    assert under and not [line for line in under if any(
        op in line for op in ("stablehlo.concatenate", "stablehlo.pad",
                              "stablehlo.slice"))]
    # every expert layer's load ring: 4 held + routed + live_tiles
    from paddle_tpu.observability import step_stats

    rings = step_stats.read()
    assert sorted(rings) == ["lfm2_l%d_experts.load" % i
                             for i in range(1, 5)]
    assert all(len(r["columns"]) == 4 + 2 for r in rings.values())

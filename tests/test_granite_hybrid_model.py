"""models/granite_hybrid.py through the normal path (layers ->
[recompute] -> [AMP] -> backward -> Executor.run(CompiledProgram))
against the plain reference benchmarks/reference/granite_hybrid.py on
seeded weights: the loss, the logits and EVERY parameter's gradient;
the tied matrix's gradient as the sum of its two readers'; the state
that has to cross the chunks; the scopes, the counters and the number
of scan kernels a step holds.

The reference runs the state-space recurrence TOKEN BY TOKEN and
attention with K and V repeated; the program runs the chunked scan
(4 chunks of 16 here) and grouped KV heads read in place.

Tolerances, and why.

* float32: the same mathematics in another order (a chunked scan
  against a token-by-token one, fused ops): loss to 1e-5, logits to
  1e-5 of the largest logit, gradients to 1e-4 of each parameter's
  largest entry.  bf16 anywhere fails this:
  `test_float32_tolerance_excludes_bf16`.
* AMP (bf16 matmul operands, attention and the scan's X, B and C; the
  residual stream, Delta, A, D, the decays, the running state and the
  norms' statistics float32): logits to 2e-2 of the largest logit, the
  loss to 1e-3, gradients to 5e-2 of each parameter's largest entry.

`WIDE` draws every matrix from N(0, 0.2) in place of N(0, 0.02): at
128 channels the published 0.02 leaves x, B and C near 5e-3 and the
state's part of y near 1e-6 of it, and then nothing a test can see
depends on the state.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.granite_hybrid import granite_hybrid_model
from paddle_tpu.ops import pallas_kernels as pk

from conftest import load_reference

ref = load_reference("granite_hybrid")

SEQ, BATCH, CHUNK = 64, 2, 16

SMALL = {
    "hidden_size": 128, "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 4, "mamba_d_head": 64, "mamba_d_state": 32,
    "mamba_d_conv": 4, "mamba_chunk_size": CHUNK, "mamba_conv_bias": True,
    "mamba_n_groups": 1, "mamba_proj_bias": False, "attention_bias": False,
    "shared_intermediate_size": 256, "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "vocab_size": 96, "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "num_local_experts": 0,
    "position_embedding_type": "nope", "initializer_range": 0.02,
    "param_prefix": "granite",
}
WIDE = dict(SMALL, initializer_range=0.2)

F32 = {"loss": 1e-5, "logits": 1e-5, "grad": 1e-4}
AMP = {"loss": 1e-3, "logits": 2e-2, "grad": 5e-2}


def _fresh():
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program

    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _build(config, amp, recompute, opt=None):
    _fresh()
    np.random.seed(0)
    model = granite_hybrid_model(config, seq_len=SEQ)
    opt = opt or optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    return model, opt


def _batch(config, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (BATCH, SEQ, 1), dtype=np.int64)
    return ids, np.roll(ids, -1, axis=1)


def _scope_params(config):
    # copies: the step donates the weights
    return jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))


def _run(config, amp, recompute):
    """{loss, logits, grads} of the program (and `used`, the kernel
    impls its step counted) and of the reference."""
    model, opt = _build(config, amp, recompute)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = _batch(config)
    params = _scope_params(config)
    ids32, labels32 = ref._split(batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids32, labels32, config))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jnp.stack([ref.sequence_logits(params, i, config)
                                 for i in ids32])
    names = jax.tree_util.tree_leaves(ref.param_names(config))
    want = {"loss": float(want_loss), "logits": np.asarray(want_logits),
            "grads": dict(zip(names,
                              jax.tree_util.tree_leaves(want_grads)))}
    # (building the program traces every op for its shapes: the
    # counters are read round the step alone)
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
    return got, want


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
    program then runs the Pallas scan and the grouped-KV flash kernels
    on the CPU."""
    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


CASES = {
    "f32": (SMALL, False, False, F32),
    "f32_wide": (WIDE, False, False, F32),
    "f32_wide_recompute": (WIDE, False, True, F32),
    # no bias on the convolution; attention first and last
    "f32_no_conv_bias": (dict(
        WIDE, mamba_conv_bias=False,
        layer_types=["attention", "mamba", "attention"]),
        False, True, F32),
    "amp": (SMALL, True, False, AMP),
    "amp_wide_recompute": (WIDE, True, True, AMP),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol = CASES[case]
    got, want = _run(config, amp, recompute)
    _check(got, want, tol)
    if config["initializer_range"] == 0.02:
        # random weights at this width give small logits
        assert 0.9 * np.log(96) < want["loss"] < 1.1 * np.log(96)


def test_kernels_in_interpret_mode_against_reference(interpret):
    """The same comparison with pt_ssd_fwd, pt_ssd_bwd and the flash
    kernels (2 KV heads read by 4 query heads: head size 32, so the
    head-major kernels) in the program, inside recompute segments."""
    # a state of 64: the convolution's 4 x 64 + 2 x 64 = 384 channels
    # are whole lane blocks, so pt_conv1d_fwd and pt_conv1d_bwd run too
    # (at SMALL's 320 the op takes its XLA graph)
    got, want = _run(dict(WIDE, mamba_d_state=64), False, True)
    _check(got, want, F32)
    used = got["used"]
    assert used[("ssd_scan", "interpret")] == 2
    assert used[("ssd_scan_grad", "saved")] == 2
    assert used[("flash_attention_kv_heads", "grouped")] == 1
    # each of the two convolutions once in the forward pass and once in
    # its segment's replay, differentiated there by its custom_vjp
    assert used[("causal_conv1d", "interpret")] == 4
    assert not [k for k in used if k[1] in ("xla", "recompute", "repeated")]


def test_float32_tolerance_excludes_bf16():
    got, want = _run(WIDE, True, False)
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        > 20 * F32["logits"] * scale
    assert max(_grad_errors(got["grads"], want["grads"]).values()) \
        > 20 * F32["grad"]


def test_the_state_has_to_cross_the_chunks():
    """The reference with its state zeroed at every chunk start is
    another model: the program's loss, which equals the reference's to
    1e-5, is 20 times further from it and more (37 seen)."""
    got, want = _run(WIDE, False, False)
    params = _scope_params(WIDE)
    lost = ref.loss(params, _batch(WIDE), WIDE, state_reset_every=CHUNK)
    assert abs(got["loss"] - want["loss"]) <= F32["loss"] * want["loss"]
    assert abs(lost - want["loss"]) > 20 * F32["loss"] * want["loss"]
    # zeroed at a multiple of the sequence: the same model
    same = ref.loss(params, _batch(WIDE), WIDE, state_reset_every=SEQ)
    assert same == pytest.approx(want["loss"], rel=1e-6)


def test_tied_matrix_gradient_is_the_sum_of_its_two_readers():
    """One matrix read by the lookup and by the head: the program's
    gradient is the reference's with the two readers given a matrix
    each, the two gradients added."""
    got, want = _run(WIDE, False, True)
    params = _scope_params(WIDE)
    ids32, labels32 = ref._split(_batch(WIDE))

    def two_readers(lookup, head):
        def ce(h, _, labels, scaling):
            return ref.cross_entropy(h, head, labels, scaling)

        return ref.batch_loss(dict(params, emb=lookup), ids32, labels32,
                              WIDE, ce_fn=ce)

    d_lookup, d_head = jax.grad(two_readers, argnums=(0, 1))(
        params["emb"], params["emb"])
    assert float(jnp.abs(d_lookup).max()) > 0
    assert float(jnp.abs(d_head).max()) > 0
    both = np.asarray(d_lookup + d_head)
    np.testing.assert_allclose(want["grads"]["granite_emb.w"], both,
                               rtol=1e-5, atol=1e-7 * np.abs(both).max())
    err = np.abs(got["grads"]["granite_emb.w"] - both).max()
    assert err <= F32["grad"] * np.abs(both).max()
    # and neither reader's alone
    for one in (d_lookup, d_head):
        assert np.abs(got["grads"]["granite_emb.w"]
                      - np.asarray(one)).max() > 100 * err
    # one parameter, read twice, in the program
    block = fluid.default_main_program().global_block()
    readers = [op.type for op in block.ops
               if "granite_emb.w" in op.input_names()
               and op.type in ("lookup_table", "lookup_table_v2",
                               "embedding", "matmul", "matmul_v2")]
    assert len(readers) == 2, readers


def _impl_counts():
    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _since(before):
    return {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}


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
    types = {op.type for op in block.ops}
    assert {"ssd_scan", "causal_conv1d", "gated_rms_norm", "rms_norm",
            "swiglu", "flash_attention", "recompute_segment_grad"} <= types
    assert "rotary_embedding" not in types
    # layer_types[:3]: the fourth entry is not built
    names = {p.name for p in program.all_parameters()}
    assert names == set(jax.tree_util.tree_leaves(ref.param_names(SMALL)))
    assert "granite_l1_q.w" in names and "granite_l0_ssm_A_log.w" in names
    assert not [n for n in names if n.startswith("granite_l3_")]
    # k and v at the KV heads' width: 2 heads of 32 against q's 4
    assert block.var("granite_l1_q.w").shape == (128, 128)
    assert block.var("granite_l1_k.w").shape == (128, 64)
    assert block.var("granite_l1_v.w").shape == (128, 64)
    assert block.var("granite_l0_in_xbc.w").shape == (128, 4 * 64 + 2 * 32)
    assert block.var("granite_l0_conv.w").shape == (4 * 64 + 2 * 32, 4)
    flash, = [op for op in block.ops if op.type == "flash_attention"]
    assert flash.attrs["scale"] == 0.015625 and flash.attrs["causal"]
    # under AMP the scan's steps, rates and skip weights stay float32
    for op in block.ops:
        if op.type == "ssd_scan":
            for slot in ("Dt", "A", "D"):
                assert block.var(op.inputs[slot][0]).dtype != "bfloat16"
            assert block.var(op.outputs["States"][0]).dtype == "float32"


@pytest.mark.parametrize("key,value", [
    ("num_local_experts", 4), ("mamba_n_groups", 2),
    ("mamba_proj_bias", True), ("attention_bias", True),
    ("position_embedding_type", "rope")])
def test_what_is_not_built_raises(key, value):
    _fresh()
    with pytest.raises(NotImplementedError, match=key):
        granite_hybrid_model(dict(SMALL, **{key: value}), seq_len=SEQ)


def test_a_length_that_is_no_multiple_of_the_chunk_raises():
    _fresh()
    with pytest.raises(ValueError, match="nothing is padded"):
        granite_hybrid_model(SMALL, seq_len=SEQ + 8)


def test_scopes_counters_and_one_forward_kernel_a_scan(interpret):
    """The compiled step of RecomputeOptimizer(Adam) under AMP: the
    computes' named scopes and the builder's name scopes are in its op
    metadata; every scan runs its forward kernel ONCE and its backward
    kernel once (the segment binds the saved Y and chunk-start states
    on the op it replays: never a second forward for the grad op nor a
    third for the replay), and the one attention layer its forward
    kernel once."""
    model, opt = _build(SMALL, True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    before = _impl_counts()
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(SMALL)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    first, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    second, = exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    assert float(np.asarray(second).reshape(-1)[0]) \
        < float(np.asarray(first).reshape(-1)[0])
    used = _since(before)
    n_mamba = SMALL["layer_types"][:3].count("mamba")
    assert used[("ssd_scan", "interpret")] == n_mamba
    assert used[("ssd_scan_grad", "saved")] == n_mamba
    assert ("ssd_scan_grad", "recompute") not in used
    assert used[("flash_attention", "interpret")] == 1
    assert used[("flash_attention_grad", "saved")] == 1
    assert used[("flash_attention_kv_heads", "grouped")] == 1
    step, = [v for v in compiled._cache.values() if callable(v)]
    state = {n: jax.ShapeDtypeStruct(np.shape(v), v.dtype) for n, v in
             ((n, global_scope().find_var(n).get())
              for n in compiled._persistable_names)}
    text = step.lower(state, {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                              for k, v in feed.items()}).as_text(
                                  debug_info=True)
    for scope in ("pt_ssd", "pt_causal_conv1d", "pt_gated_rms_norm",
                  "pt_granite_mamba", "pt_granite_attention",
                  "pt_granite_ffn", "pt_granite_head", "pt_rms_norm",
                  "pt_swiglu"):
        assert "/%s/" % scope in text or "%s/" % scope in text, scope

"""models/ouro.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/ouro.py on seeded weights: the loss, the
R passes' logits, the exit distribution and EVERY parameter's gradient;
and what a stack run R times over ONE set of weights forced in shared
code: one VarDesc and one initializer a shared name, a chain of
partial gradients from more than two recompute segments, one cast of a
shared weight a step, the rotary pairing.

Tolerances, and why.

* float32: program and reference compute the same mathematics in
  another order (fused ops, the exit distribution in logarithms
  against products of sigmoids): loss to 2e-6 (a mean over 64 tokens;
  2e-7 seen), logits to 1e-5 of the largest logit (7e-7 seen), exit
  probabilities to 1e-6 absolute (9e-8 seen), gradients to 1e-4 of
  each parameter's largest entry (3e-6 seen).  bf16 anywhere fails
  this a thousandfold: `test_float32_tolerance_excludes_bf16` holds
  the AMP program and the reference computed wholly in bf16 to the
  same bounds and requires that both FAIL them.
* AMP (bf16 matmul operands and attention; norms, residual stream,
  gate arithmetic and loss float32): a rounding of 2^-8 = 3.9e-3 a
  matmul over R x 2 layers: logits to 5e-2 of the largest logit (2.3e-2
  at worst over seeds), the loss to 2e-4 (4.5e-5: a mean of losses
  near ln 256), exit probabilities to 3e-3 absolute (8e-4), gradients
  to 0.08 of each parameter's largest entry (2.6e-2).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, layers, optimizer, unique_name
from paddle_tpu.core import scope as scope_mod
from paddle_tpu.core.compiler import shared_param_reads
from paddle_tpu.core.program import Program
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.ouro import ouro_model

from conftest import load_reference, reference_path

ref = load_reference("ouro")

SEQ, BATCH = 32, 2

SMALL = {
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 4, "intermediate_size": 160, "vocab_size": 256,
    "num_hidden_layers": 2, "total_ut_steps": 4, "rms_norm_eps": 1e-6,
    "rope_theta": 1000000, "rope_scaling": None,
    "initializer_range": 0.02, "exit_entropy_beta": 0.05,
}
HEAD128 = dict(SMALL, hidden_size=256, num_attention_heads=2, head_dim=128,
               num_key_value_heads=2, total_ut_steps=2)

F32 = {"loss": 2e-6, "logits": 1e-5, "probs": 1e-6, "grad": 1e-4}
AMP = {"loss": 2e-4, "logits": 5e-2, "probs": 3e-3, "grad": 0.08}


def _fresh():
    framework.switch_main_program(Program())
    framework.switch_startup_program(Program())
    unique_name.switch({})
    scope_mod._global_scope = scope_mod.Scope()


def _build(config, amp, recompute, opt=None):
    """(model, params_grads) of the program as a trainer builds it."""
    model = ouro_model(config, seq_len=SEQ)
    opt = opt or optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    return model, opt.backward(model["loss"])


def _batch(config, seed=0):
    ids = np.random.default_rng(seed).integers(
        0, config["vocab_size"], (BATCH, SEQ, 1), dtype=np.int64)
    return ids, np.roll(ids, -1, axis=1)


def _reference(config, params, batch):
    """(loss, logits [B, R, T, V], exit distribution [B, R, T],
    {param: grad}) of the reference, in the dtype of `params`."""
    ids, labels = ref._split(batch)
    loss, grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids, labels, config))(params)
    with jax.default_matmul_precision("highest"):
        logits = jax.vmap(
            lambda i: ref.sequence_logits(params, i, config))(ids)
        probs = jax.vmap(
            lambda i: ref.sequence_exit_distribution(params, i, config))(ids)
    names = jax.tree_util.tree_leaves(ref.param_names(config))
    return (float(loss), np.asarray(logits, np.float32),
            np.asarray(probs, np.float32),
            {n: np.asarray(g, np.float32) for n, g in
             zip(names, jax.tree_util.tree_leaves(grads))})


def _run(config, amp, recompute, seed=0, bf16_reference=False):
    """The program's (loss, logits, exit distribution, grads), the
    float32 reference's, and where asked for the reference's computed
    wholly in bf16."""
    np.random.seed(seed)
    model, params_grads = _build(config, amp, recompute)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = _batch(config, seed)
    # the reference first: the step donates the weights
    params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))
    want = _reference(config, params, batch)
    low = _reference(config, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16), params), batch) \
        if bf16_reference else None
    n_logits, n_probs = len(model["logits"]), len(model["exit_probs"])
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"]] + model["logits"]
                   + model["exit_probs"] + [g for _, g in params_grads])
    outs = [np.asarray(o, np.float32) for o in outs]
    probs = np.stack([o[..., 0] for o in
                      outs[1 + n_logits:1 + n_logits + n_probs]], 1) \
        if n_probs else np.ones((BATCH, 1, SEQ), np.float32)
    got = (float(outs[0].reshape(-1)[0]),
           np.stack(outs[1:1 + n_logits], 1), probs,
           {p.name: o for (p, _), o in
            zip(params_grads, outs[1 + n_logits + n_probs:])})
    return got, want, low


def _errors(got, want):
    assert set(got[3]) == set(want[3])      # a gradient for EVERY one
    return {"loss": abs(got[0] - want[0]) / abs(want[0]),
            "logits": float(np.abs(got[1] - want[1]).max()
                            / np.abs(want[1]).max()),
            "probs": float(np.abs(got[2] - want[2]).max()),
            "grad": max(float(np.abs(got[3][n] - w).max() / np.abs(w).max())
                        for n, w in want[3].items())}


@pytest.fixture
def interpret(monkeypatch):
    from paddle_tpu.ops import pallas_kernels as pk

    monkeypatch.setattr(pk, "_auto_impl", lambda: "interpret")


CASES = {
    "f32_r1": (dict(SMALL, total_ut_steps=1), False, False, F32),
    "f32_r2": (dict(SMALL, total_ut_steps=2), False, False, F32),
    "f32_r4": (SMALL, False, False, F32),
    "f32_r4_recompute": (SMALL, False, True, F32),
    "amp_r2": (dict(SMALL, total_ut_steps=2), True, False, AMP),
    "amp_r4_recompute": (SMALL, True, True, AMP),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol = CASES[case]
    got, want, _ = _run(config, amp, recompute)
    errors = _errors(got, want)
    assert all(errors[k] <= tol[k] for k in tol), errors
    passes = config["total_ut_steps"]
    assert got[1].shape == (BATCH, passes, SEQ, config["vocab_size"])
    np.testing.assert_allclose(want[2].sum(1), 1.0, atol=1e-6)
    # small logits: ln 256 less what the entropy term takes (at most
    # beta ln R), and the weight-dependent part is what the bound is of
    assert np.log(256) - 0.05 * np.log(passes) - 0.1 < want[0] \
        < np.log(256) + 0.1


def test_head_size_128_through_the_token_major_kernels(interpret):
    """The published head size through the Pallas kernels in interpret
    mode, AMP and recompute on: the token-major block map at 128 (one
    head a lane block), and nothing head-major."""
    before = dict(_impl_counts())
    got, want, _ = _run(HEAD128, True, True)
    errors = _errors(got, want)
    assert all(errors[k] <= AMP[k] for k in AMP), errors
    used = {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}
    # 2 layers x 2 passes = 4 flash ops, each traced three times: twice
    # while the program is built (shape inference as the op is appended
    # and again in the AMP rewrite's check) and in the compiled step's
    # forward.  Its segment's replay does not run it again: the replay
    # takes the forward's Out and LSE, and the segment's backward is
    # the registered grad op on them (`saved`, ISSUE 33), whose entry
    # counts its layout as the forward's does
    assert used == {("flash_attention", "interpret"): 12,
                    ("flash_attention_layout", "token_major"): 16,
                    ("flash_attention_bwd", "fused"): 4,
                    ("flash_attention_grad", "saved"): 4,
                    # a forward and a backward entry, one block a
                    # sequence (ISSUE 48)
                    ("flash_attention_causal_fetch", "all_live"): 16,
                    # q and k of the 4 layer runs turned where they lie
                    # by pt_rotary (ISSUE 54): 8 ops, each traced at
                    # those three places and in its segment's replay;
                    # their gradients are the same kernel, not counted
                    ("rotary", "interpret"): 32}
    assert ("flash_attention_layout", "head_major") not in used


def _impl_counts():
    from paddle_tpu.ops import pallas_kernels as pk

    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def test_float32_tolerance_excludes_bf16():
    """The float32 bounds are tight enough that computing in bf16 where
    float32 is stated fails them: the AMP program and the reference
    computed wholly in bf16 (parameters rounded, every operation in
    bf16) are outside the logits' and the gradients' float32 bound by a
    wide margin, and a float32 run is inside (the cases above)."""
    got, want, low = _run(SMALL, True, False, bf16_reference=True)
    for other in (got, low):
        errors = _errors(other, want)
        assert errors["logits"] > 100 * F32["logits"], errors
        assert errors["grad"] > 50 * F32["grad"], errors
        assert errors["probs"] > 50 * F32["probs"], errors


@pytest.mark.parametrize("passes", [1, 2, 4])
def test_every_parameter_once_and_one_initializer(passes):
    """The global block holds each parameter once whatever R is, the
    startup program draws each once, and Adam's state does not grow."""
    from paddle_tpu.contrib.mixed_precision import decorate

    config = dict(SMALL, total_ut_steps=passes)
    model = ouro_model(config, seq_len=SEQ)
    opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-4))
    opt._set_checkpoints(model["checkpoints"])
    decorate(opt, init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    main, startup = (fluid.default_main_program(),
                     fluid.default_startup_program())
    names = set(jax.tree_util.tree_leaves(ref.param_names(config)))
    assert {p.name for p in main.all_parameters()} == names
    # 2 layers x 11, the embedding, the final norm, the head, and the
    # gate's weight and bias where a gate is read
    assert len(names) == 25 + 2 * (passes > 1)
    written = [n for op in startup.global_block().ops
               for n in op.output_names()]
    assert sorted(n for n in written if n in names) == sorted(names)
    moments = [n for n in main.global_block().vars if "_moment1_" in n]
    assert len(moments) == len(names)
    assert len(model["checkpoints"]) == passes * (2 + 1)


def test_a_shared_name_has_to_match_in_shape():
    x = layers.data("x", shape=[8], dtype="float32")
    attr = fluid.ParamAttr(name="shared.w")
    layers.fc(x, 8, param_attr=attr, bias_attr=False)
    layers.fc(x, 8, param_attr=attr, bias_attr=False)      # shares
    with pytest.raises(ValueError, match="shared by name"):
        layers.fc(x, 4, param_attr=attr, bias_attr=False)
    block = fluid.default_startup_program().global_block()
    assert sum("shared.w" in op.output_names() for op in block.ops) == 1


def test_recompute_equals_plain():
    """The same program with and without recompute segments: loss and
    every gradient agree to float32 rounding (a segment's replay is the
    forward again; the partials are summed in another order)."""
    runs = []
    for recompute in (False, True):
        _fresh()
        got, _, _ = _run(SMALL, False, recompute)
        runs.append(got)
    assert runs[0][0] == pytest.approx(runs[1][0], rel=1e-6)
    for name, g in runs[0][3].items():
        np.testing.assert_allclose(runs[1][3][name], g, rtol=1e-4,
                                   atol=1e-6 * np.abs(g).max())


def test_four_partial_gradients_through_the_acc_chain():
    """ONE weight read from four recompute segments: four partials,
    three sums, each under a name of its own (no sum reads the name it
    writes), equal to the unsegmented backward."""
    from paddle_tpu.backward import append_backward

    grads = []
    for segmented in (False, True):
        _fresh()
        np.random.seed(3)
        x = layers.data("x", shape=[16], dtype="float32")
        x.stop_gradient = False
        h, cps = x, []
        for _ in range(4):
            h = layers.tanh(layers.fc(
                h, 16, param_attr=fluid.ParamAttr(name="w"),
                bias_attr=False))
            cps.append(h)
        loss = layers.mean(h)
        pg = append_backward(loss, checkpoints=cps if segmented else None)
        assert [p.name for p, _ in pg] == ["w"]
        block = fluid.default_main_program().global_block()
        if segmented:
            sums = [op for op in block.ops if op.type == "sum"]
            assert len(sums) == 3
            outs = [op.outputs["Out"][0] for op in sums]
            assert len(set(outs)) == 3
            assert all(o not in op.inputs["X"] for op, o in zip(sums, outs))
            # the four layers' segments and the mean after the last
            assert sum(op.type == "recompute_segment_grad"
                       for op in block.ops) == 5
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        feed = {"x": np.random.default_rng(3).normal(
            0, 1, (8, 16)).astype(np.float32)}
        grads.append(exe.run(
            fluid.CompiledProgram(fluid.default_main_program()), feed=feed,
            fetch_list=[pg[0][1], block.var("x@GRAD")]))
    for a, b in zip(*grads):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), rtol=1e-5,
                                   atol=1e-8)


def test_amp_makes_one_cast_of_a_weight_read_from_four_segments():
    """The AMP rewrite casts a shared weight once, and the recompute
    backward takes that cast out of its first reader's segment: no
    segment's replay makes the bf16 copy a second time, and the weight's
    gradient is the cast of the four partials' sum.
    `shared_param_reads` says so: 4 readers, 1 cast."""
    np.random.seed(0)
    _build(SMALL, True, True)
    main = fluid.default_main_program()
    ops = main.global_block().ops
    w = "ouro_l0_q.w"
    casts = [op for op in ops if op.type == "cast"
             and op.inputs["X"] == [w]]
    assert len(casts) == 1
    bf16 = casts[0].outputs["Out"][0]
    segments = [op for op in ops if op.type == "recompute_segment_grad"]
    replayed = [[d["type"] for d in s.attrs["ops"]] for s in segments
                if any(w in names for d in s.attrs["ops"]
                       for names in d["inputs"].values())]
    assert replayed == [["cast"]]           # its own one-op segment
    assert sum(bf16 in s.inputs["X"] for s in segments) == 4
    reads = shared_param_reads(main)
    assert reads[w] == (4, 1)
    assert reads["ouro_head.w"] == (4, 1)
    assert reads["ouro_exit_gate.w"] == (3, 1)
    assert reads["ouro_l1_norm3.w"] == (4, 0)       # float32: no cast
    assert reads["ouro_final_norm.w"] == (4, 0)
    assert "ouro_emb.w" not in reads                # one reader
    # 2 layers x 7 matrices and the head; the gate's weight has 3
    kinds = sorted(reads.values())
    assert kinds.count((4, 1)) == 15 and kinds.count((3, 1)) == 1
    assert kinds.count((4, 0)) == 9


def test_shared_weight_partials_summed_in_bf16_cost_nothing_seen():
    """What `assumed.optimizer` of benchmarks/configs/ouro-2.6b.json
    states: under AMP the four partial gradients of a shared weight
    are bf16 (taken with respect to the ONE bf16 copy) and are added in
    bf16, where the reference adds in float32.  The same four partials
    added in float32 are no nearer the reference's gradient: relative
    L2 error 0.0209 against 0.0208 at worst over the 14 layer matrices
    (the bf16 matmuls make the error; the sums add under a hundredth
    of it), so the bf16 sum may cost at most a tenth more."""
    np.random.seed(0)
    model, params_grads = _build(SMALL, True, True)
    block = fluid.default_main_program().global_block()
    fetch, names = [], []
    for p, g in params_grads:
        if not p.name.startswith("ouro_l") or "norm" in p.name:
            continue
        cast, = [op for op in block.ops if op.type == "cast"
                 and op.inputs["X"] == [p.name]]
        acc = cast.outputs["Out"][0] + "@GRAD"
        partials = [n for op in block.ops if op.type == "sum"
                    and op.outputs["Out"][0].startswith(acc)
                    for n in op.inputs["X"] if "@ACC" not in n]
        assert len(partials) == 4, (p.name, partials)
        fetch += partials + [g]
        names.append(p.name)
    assert len(names) == 14
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = _batch(SMALL)
    params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(SMALL, lambda n: global_scope().find_var(n).get()))
    want = _reference(SMALL, params, batch)[3]
    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=fetch)
    for i, name in enumerate(names):
        *partials, got = outs[5 * i:5 * i + 5]
        assert all(np.asarray(x).dtype == jnp.bfloat16 for x in partials)
        in_f32 = sum(np.asarray(x, np.float32) for x in partials)
        err = [float(np.linalg.norm(np.asarray(x, np.float32) - want[name])
                     / np.linalg.norm(want[name])) for x in (got, in_f32)]
        assert err[0] <= 1.1 * err[1] <= 1.1 * 0.05, (name, err)


def test_scopes_are_in_the_compiled_step():
    """pt_ut_step, pt_exit_gate and pt_loop_loss (framework.name_scope
    around the builder's ops) reach the compiled step's op_name
    metadata."""
    np.random.seed(0)
    model, _ = _build(dict(SMALL, total_ut_steps=2), True, True)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(SMALL)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    text = compiled.step_text(feed)
    for scope in ("pt_ut_step", "pt_exit_gate", "pt_loop_loss",
                  "pt_rms_norm", "pt_swiglu"):
        assert "/%s/" % scope in text, scope
    # a segment's replay runs its ops under the same scopes
    scopes = {d.get("scope") for op in
              fluid.default_main_program().global_block().ops
              if op.type == "recompute_segment_grad"
              for d in op.attrs["ops"]}
    assert scopes == {None, "pt_ut_step", "pt_exit_gate", "pt_loop_loss"}


@pytest.mark.parametrize("rotary_dim", [0, 8])
def test_rotary_halves_against_the_reference_default_unchanged(rotary_dim):
    from paddle_tpu.core.registry import get_op_def

    op = get_op_def("rotary_embedding")
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (2, 12, 3, 16)),
                    jnp.float32)
    keep = 16 - (rotary_dim or 16)

    def run(**attrs):
        return np.asarray(op.compute({"X": x}, op.canonical_attrs(
            dict(attrs, rotary_dim=rotary_dim, theta=1e6)))["Out"])

    halves = run(pairing="halves")
    want = np.concatenate(
        [np.asarray(x[..., :keep])]
        + [np.stack([np.asarray(ref.rope(x[b, ..., keep:], 1e6))
                     for b in range(2)])], axis=-1)
    np.testing.assert_allclose(halves, want, rtol=1e-5, atol=1e-6)
    # the default: interleaved pairs, as before the attribute
    assert op.canonical_attrs({})["pairing"] == "interleaved"
    rd = rotary_dim or 16
    inv = 1.0 / 1e6 ** (np.arange(0, rd, 2) / rd)
    ang = np.arange(12)[:, None] * inv[None]
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    xr = np.asarray(x[..., keep:], np.float64)
    a, b = xr[..., 0::2], xr[..., 1::2]
    turned = np.stack([a * cos - b * sin, a * sin + b * cos],
                      -1).reshape(xr.shape)
    np.testing.assert_allclose(
        run(), np.concatenate([np.asarray(x[..., :keep]), turned], -1),
        rtol=1e-5, atol=1e-6)
    assert not np.allclose(halves, run(), atol=1e-3)
    with pytest.raises(ValueError, match="pairing"):
        run(pairing="pairs")


def test_program_is_verified_and_shape_checked():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes

    _build(SMALL, True, True, opt=optimizer.Adam(1e-3))
    program = fluid.default_main_program()
    verifier.verify(program)
    _, diags = infer_program_shapes(program)
    assert not [d for d in diags if d.severity == "error"], diags
    forward = [op for op in program.global_block().ops
               if op.op_role == "forward"]
    assert sum(op.type == "flash_attention" for op in forward) == 8
    assert all(op.attrs["heads"] == 4 for op in forward
               if op.type == "flash_attention")
    assert not [op for op in forward
                if op.type in ("transpose", "transpose2")]


def test_the_benchmarks_reference_is_this_one():
    """benchmarks/reference/ouro.py, which decides the cell's `correct`
    on the chip, is the file these tests compare the program with, and
    not a copy of it."""
    assert os.path.samefile(ref.__file__, reference_path("ouro"))

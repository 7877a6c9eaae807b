"""models/deepseek_v2.py through the normal path (layers -> [recompute]
-> [AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/deepseek_v2.py on seeded weights: the
loss, its two terms (cross-entropy, expert-balance), the logits and EVERY
parameter's gradient; the balance loss by hand; the share test that
ties the one-chip cut to the whole layer; and `xing4`'s program, which
now builds its attention through the function the two models share.

Tolerances, and why.

* float32: program and reference compute the same mathematics in
  another order (fused ops, a sorted grouped matmul against a masked
  loop, one stacked shared SwiGLU against two experts summed): loss and
  both terms to 1e-5, logits to 1e-5 of the largest logit, gradients to
  1e-4 of each parameter's largest entry (7e-7 measured).  bf16
  anywhere fails this: `test_float32_tolerance_excludes_bf16` runs the
  AMP program against the same bounds and requires that it FAILS them
  by a factor of 20.
* AMP (bf16 matmul operands and expert rows; norms, router scores,
  gates, the balance loss and the residual stream float32): a rounding
  of 2^-8 = 3.9e-3 a matmul over 6 sublayers: logits to 2e-2 of the
  largest logit, the cross-entropy to 1e-3 (a mean over 64 tokens of
  small logits), gradients to 5e-2 of each parameter's largest entry
  (1.4e-2 measured at worst).  The balance loss is computed from
  float32 scores of a bf16-rounded input: 2e-3 relative.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import layers, optimizer
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.deepseek_v2 import deepseek_v2_model

from conftest import load_reference, reference_path

ref = load_reference("deepseek_v2")

SEQ, BATCH = 32, 2

SMALL = {
    "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "q_lora_rank": None, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "held_experts": [0, 1, 2, 3],
    "n_shared_experts": 2, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "routed_scaling_factor": 1.0,
    "scoring_func": "softmax", "seq_aux": True, "aux_loss_alpha": 0.001,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "initializer_range": 0.02,
}

F32 = {"loss": 1e-5, "aux": 1e-5, "logits": 1e-5, "grad": 1e-4}
AMP = {"loss": 1e-3, "aux": 2e-3, "logits": 2e-2, "grad": 5e-2}


def _build(config, amp, recompute, opt=None):
    model = deepseek_v2_model(config, seq_len=SEQ)
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


def _run(config, amp, recompute):
    """{loss, ce, aux, logits, grads} of the program and of the
    reference."""
    np.random.seed(0)
    model, opt = _build(config, amp, recompute)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    batch = _batch(config)

    # the reference first: the step donates the weights
    params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))
    ids32, labels32 = ref._split(batch)

    def terms(p):
        ce, aux = ref.loss_terms(p, ids32, labels32, config)
        return ce + aux, (ce, aux)

    (want_loss, (want_ce, want_aux)), want_grads = jax.value_and_grad(
        terms, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jax.vmap(
            lambda i: ref.sequence_logits(params, i, config))(ids32)
    names = jax.tree_util.tree_leaves(ref.param_names(config))
    want = {"loss": float(want_loss), "ce": float(want_ce),
            "aux": float(want_aux), "logits": np.asarray(want_logits),
            "grads": dict(zip(names,
                              jax.tree_util.tree_leaves(want_grads)))}

    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"], model["ce_loss"],
                               model["aux_loss"], model["logits"]]
                   + [g for _, g in params_grads])
    scalar = [float(np.asarray(o).reshape(-1)[0]) for o in outs[:3]]
    got = {"loss": scalar[0], "ce": scalar[1], "aux": scalar[2],
           "logits": np.asarray(outs[3], np.float32),
           "grads": {p.name: np.asarray(o, np.float32)
                     for (p, _), o in zip(params_grads, outs[4:])}}
    return got, want


def _worst_grad(got, want):
    """Largest |got - want| over the parameter's largest |want|."""
    return max(float(np.abs(got[n] - np.asarray(w)).max()
                     / np.abs(np.asarray(w)).max())
               for n, w in want.items())


def _check(got, want, tol):
    assert set(got["grads"]) == set(want["grads"])
    assert got["loss"] == pytest.approx(want["loss"], rel=tol["loss"])
    assert got["ce"] == pytest.approx(want["ce"], rel=tol["loss"])
    assert got["aux"] == pytest.approx(want["aux"], rel=tol["aux"])
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        <= tol["logits"] * scale
    assert _worst_grad(got["grads"], want["grads"]) <= tol["grad"]


_UNEQUAL_MSCALE = dict(SMALL["rope_scaling"], mscale=1.0,
                       mscale_all_dim=0.707)
CASES = {
    "f32": (dict(SMALL), False, False, F32),
    # the rotary factor yarn(mscale) / yarn(mscale_all_dim) is not 1,
    # the gates are scaled, two experts held out of order
    "f32_mscale_held2": (dict(SMALL, rope_scaling=_UNEQUAL_MSCALE,
                              routed_scaling_factor=2.5,
                              n_routed_experts=2, held_experts=[6, 1]),
                         False, False, F32),
    "f32_recompute": (dict(SMALL), False, True, F32),
    # a rank for the query too: the shared attention's other branch
    "f32_q_lora_norm_topk": (dict(SMALL, q_lora_rank=24,
                                  norm_topk_prob=True), False, True, F32),
    "amp": (dict(SMALL), True, False, AMP),
    "amp_recompute_held3": (dict(SMALL, n_routed_experts=3,
                                 held_experts=[0, 3, 5]), True, True, AMP),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol = CASES[case]
    got, want = _run(config, amp, recompute)
    _check(got, want, tol)
    # random weights at this width give small logits: the loss is near
    # ln(128); two expert layers near alpha each (near-uniform scores)
    assert 0.9 * np.log(128) < want["ce"] < 1.1 * np.log(128)
    assert want["aux"] == pytest.approx(2 * 0.001, rel=0.05)
    assert want["loss"] == pytest.approx(want["ce"] + want["aux"], rel=1e-6)


def test_float32_tolerance_excludes_bf16():
    """The float32 bounds are tight enough that computing in bf16 where
    float32 is stated fails them: the AMP program is outside the
    logits' and the gradients' float32 bound by a wide margin."""
    got, want = _run(dict(SMALL), True, False)
    scale = float(np.abs(want["logits"]).max())
    assert float(np.abs(got["logits"] - want["logits"]).max()) \
        > 20 * F32["logits"] * scale
    assert _worst_grad(got["grads"], want["grads"]) > 20 * F32["grad"]


def test_program_is_verified_and_shape_checked():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes

    model, opt = _build(dict(SMALL), True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    program = fluid.default_main_program()
    verifier.verify(program)
    _, diags = infer_program_shapes(program)
    assert not [d for d in diags if d.severity == "error"], diags
    assert len(model["checkpoints"]) == SMALL["num_hidden_layers"]
    block = program.global_block()
    types = {op.type for op in block.ops}
    assert {"moe_route", "moe_experts", "rms_norm", "rotary_embedding",
            "swiglu", "flash_attention", "one_hot",
            "recompute_segment_grad"} <= types
    assert not types & {"mhc_pre", "mhc_post"}
    # one router op a layer with the config's scoring, no bias made
    routers = [op for op in block.ops if op.type == "moe_route"]
    assert [op.attrs["scoring_func"] for op in routers] == ["softmax"] * 2
    assert all("Bias" not in op.inputs for op in routers)
    assert not [n for n in block.vars if n.endswith("_router_bias.w")]
    # no rank for the query: one projection, no query norm
    names = {p.name for p in program.all_parameters()}
    assert "dsv2_l0_q.w" in names
    assert not [n for n in names if "_q_a" in n or "_q_b" in n]
    assert block.var("dsv2_l0_q.w").shape == (64, 4 * 24)
    # two shared experts are one stacked SwiGLU
    assert block.var("dsv2_l1_shared_gate.w").shape == (64, 2 * 32)
    # an expert layer's segment hands on its balance loss beside the
    # stream: two outputs with a gradient; the dense layer's has one
    segments = [op for op in block.ops
                if op.type == "recompute_segment_grad"]
    by_outputs = sorted(len(op.attrs["out_names"]) for op in segments)
    assert by_outputs == [1, 1, 2, 2]
    # the balance loss is float32 under AMP: no cast touches its ops
    balance = [op for op in block.ops if op.scope == "pt_moe_balance"]
    assert balance and not [op for op in balance if op.type == "cast"]
    for op in balance:
        for n in op.output_names():
            # (the selected ids are reshaped as int32)
            assert block.var(n).dtype != "bfloat16", (op.type, n)


def test_scope_and_counters_after_a_build(monkeypatch):
    """The scope pt_moe_balance (framework.name_scope around the
    balance loss's ops) and the kernels' scopes reach the compiled
    step's op_name metadata, forward and in the segment's replay; the
    router's scoring is counted in paddle_tpu_kernel_impl_total once a
    trace, and a grouped matmul that runs a kernel counts its block
    shape."""
    from paddle_tpu.ops import pallas_gmm, pallas_kernels as pk

    def counts():
        return {(lbl["kernel"], lbl["impl"]): v
                for lbl, v in pk._M_KERNEL_IMPL.items()}

    before = counts()
    np.random.seed(0)
    model, opt = _build(dict(SMALL), True, True, optimizer.Adam(1e-3))
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(fluid.default_main_program())
    batch = _batch(SMALL)
    feed = {"src_ids": batch[0], "tgt_label": batch[1]}
    exe.run(compiled, feed=feed, fetch_list=[model["loss"]])
    text = compiled.step_text(feed)
    for scope in ("pt_moe_balance", "pt_mla", "pt_moe_route",
                  "pt_moe_experts", "pt_rms_norm", "pt_swiglu"):
        assert "/%s/" % scope in text, scope
    scopes = {d.get("scope") for op in
              fluid.default_main_program().global_block().ops
              if op.type == "recompute_segment_grad"
              for d in op.attrs["ops"]}
    assert scopes == {None, "pt_moe_balance"}
    used = {k: v - before.get(k, 0) for k, v in counts().items()
            if v - before.get(k, 0)}
    assert used[("moe_route_scoring", "softmax")] >= 2
    assert ("moe_route_scoring", "sigmoid") not in used
    assert used[("moe_gmm", "xla")] >= 2
    # off the chip the experts run their XLA form: no block shape.  What
    # the two expert layers were given is in their stat rings: one row
    # for the one step: of the 2 x 32 tokens' top 3 of 8 experts, the
    # pairs routed to the four held here
    assert not [k for k in used if k[0] == "moe_gmm_tile"]
    from paddle_tpu.observability import step_stats

    loads = step_stats.read()
    assert sorted(loads) == ["dsv2_l1_experts.load", "dsv2_l2_experts.load"]
    for got in loads.values():
        assert got["columns"] == ("expert_0", "expert_1", "expert_2",
                                  "expert_3", "routed", "live_tiles")
        assert got["steps"].tolist() == [0]
        (row,) = got["rows"]
        assert 0 < row[:4].sum() == row[4] < BATCH * SEQ * 3
        assert 4 <= row[5] <= -(-BATCH * SEQ * 3 // 256) + 4
    # a kernel call counts the blocks `_tiles` gives it: at 256 rows a
    # tile in bf16 an expert width of 11 x 128 is taken whole, in either
    # place and by either kernel, and so is the axis beside it; under
    # Mosaic's default scope alone 1,408 still is, and the other axis
    # splits
    for kernel in ("gmm", "tgmm"):
        assert pallas_gmm._tiles(kernel, 2048, 1408, 256, 2) == (1408, 2048)
        assert pallas_gmm._tiles(kernel, 1408, 2048, 256, 2) == (2048, 1408)
        assert pallas_gmm._tiles(kernel, 3584, 1024, 256, 2) == (1024, 3584)
    with monkeypatch.context() as smaller:
        smaller.setattr(pallas_gmm, "_VMEM_BUDGET", 12 << 20)
        assert pallas_gmm._tiles("gmm", 2048, 1408, 256, 2) == (1408, 1024)
        assert pallas_gmm._tiles("tgmm", 2048, 1408, 256, 2) == (1408, 512)
        assert pallas_gmm._tiles("gmm", 1408, 2048, 256, 2) == (1024, 1408)
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(0, 1, (32, 256)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (2, 256, 1408)), jnp.float32)
    tg, na = jnp.asarray([0, 1], jnp.int32), jnp.asarray([2], jnp.int32)
    got = pallas_gmm.gmm(lhs, rhs, tg, na, 16, "interpret")
    np.testing.assert_allclose(
        got, pallas_gmm.gmm(lhs, rhs, tg, na, 16, "xla"), rtol=1e-5,
        atol=1e-4)
    assert counts().get(("moe_gmm_tile", "1408x256"), 0) \
        - before.get(("moe_gmm_tile", "1408x256"), 0) == 1
    assert not [k for k in counts() if k[0] == "moe_gmm_tile"
                and k not in before and k[1] != "1408x256"]


# -- the balance loss ---------------------------------------------------------

def _balance_program(b, t, k, e, alpha):
    idx = layers.data("idx", shape=[t, k], dtype="int32")
    scores = layers.data("scores", shape=[t, e], dtype="float32")
    scores.stop_gradient = False
    loss = layers.moe_balance_loss(idx, scores, alpha)
    return idx, scores, loss


def test_balance_loss_by_hand():
    """Uniform routing gives alpha: every expert selected by k T / E
    tokens has f = 1, scores of 1 / E each sum to 1.  All of a
    sequence's tokens on the same k experts: f = E / k there, 0
    elsewhere, so sum f P = (E / k) x (the mean score of those k)."""
    t, k, e, alpha = 8, 2, 4, 0.25
    _, _, loss = _balance_program(2, t, k, e, alpha)
    exe = fluid.Executor(fluid.CPUPlace())
    # sequence 0: tokens take experts (0, 1), (2, 3) in turn: uniform;
    # sequence 1: every token takes (1, 3)
    idx = np.zeros((2, t, k), np.int32)
    idx[0, 0::2], idx[0, 1::2], idx[1] = (0, 1), (2, 3), (1, 3)
    scores = np.full((2, t, e), 0.25, np.float32)
    scores[1] = (0.1, 0.4, 0.2, 0.3)
    got, = exe.run(feed={"idx": idx, "scores": scores}, fetch_list=[loss])
    # sequence 0: 1; sequence 1: (4 / 2) (0.4 + 0.3) = 1.4
    assert float(np.asarray(got).reshape(-1)[0]) == \
        pytest.approx(alpha * (1.0 + 1.4) / 2, rel=1e-6)
    uniform, = exe.run(feed={"idx": np.stack([idx[0]] * 2),
                             "scores": np.full((2, t, e), 0.25, np.float32)},
                       fetch_list=[loss])
    assert float(np.asarray(uniform).reshape(-1)[0]) == \
        pytest.approx(alpha, rel=1e-6)


def test_balance_loss_gradient_against_the_reference():
    """d loss / d scores through the composed ops equals jax.grad of the
    reference's term: alpha f / (B T) at EVERY expert of every token,
    selected or not; the count f passes no gradient."""
    from paddle_tpu import backward

    t, k, e, alpha = 16, 3, 8, 0.01
    _, scores_var, loss = _balance_program(2, t, k, e, alpha)
    grad, = backward.gradients([loss], [scores_var])
    rng = np.random.default_rng(2)
    logits = rng.normal(0, 1, (2, t, e)).astype(np.float32)
    scores = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    idx = np.argsort(-scores, -1)[..., :k].astype(np.int32)
    got_loss, got = fluid.Executor(fluid.CPUPlace()).run(
        feed={"idx": idx, "scores": scores}, fetch_list=[loss, grad])
    config = {"num_experts_per_tok": k}
    chosen = np.zeros((2, t, e), bool)
    np.put_along_axis(chosen, idx, True, -1)

    def want(s):
        return alpha * jnp.mean(jnp.stack([
            ref.balance(jnp.asarray(chosen[b]), s[b], config)
            for b in range(2)]))

    want_loss, want_grad = jax.value_and_grad(want)(jnp.asarray(scores))
    assert float(np.asarray(got_loss).reshape(-1)[0]) == \
        pytest.approx(float(want_loss), rel=1e-6)
    np.testing.assert_allclose(got, want_grad, rtol=1e-5, atol=1e-9)
    # every expert some token of the sequence selected gets a gradient
    # at EVERY token, also at the tokens that did not select it
    f = chosen.sum(1) * (e / (k * t))
    np.testing.assert_allclose(
        got, np.broadcast_to((alpha * f / (2 * t))[:, None, :], got.shape),
        rtol=1e-5)
    assert (np.asarray(got)[~chosen] > 0).any()


# -- the share test -----------------------------------------------------------

def _layer_weights(rng, c=32, w=16, e=16, n_shared=2):
    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"router": mat(c, e),
            "experts": {"gate": mat(e, c, w), "up": mat(e, c, w),
                        "down": mat(e, w, c)},
            "shared": {"gate": mat(c, n_shared * w),
                       "up": mat(c, n_shared * w),
                       "down": mat(n_shared * w, c)}}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_shares_add_up_to_the_whole_layer(impl):
    """16 experts over 4 chips, 4 held on each.  The routed parts the 4
    shares give, plus the shared experts ONCE, are the uncut layer of
    the reference; each share of the PROGRAM's ops equals the
    reference's share; and the balance loss, which every share computes
    alike from the same 16 scores and the same selection, is counted
    once."""
    from paddle_tpu.core.registry import get_op_def

    rng = np.random.default_rng(11)
    lw = _layer_weights(rng)
    config = {"num_experts_per_tok": 6, "norm_topk_prob": False,
              "routed_scaling_factor": 1.0, "n_routed_experts": 16,
              "n_shared_experts": 2, "scoring_func": "softmax"}
    u = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole, whole_balance = ref.expert_ffn(u, lw, config,
                                              held=list(range(16)))
        shared = ref.shared_experts(u, lw["shared"], 2)
        # two shared experts are one SwiGLU over the stacked matrices
        np.testing.assert_allclose(shared, ref.swiglu(u, lw["shared"]),
                                   rtol=1e-5, atol=1e-6)
    shares = [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 11], [12, 13, 14, 15]]

    def stack_of(held):
        return {k: v[jnp.asarray(held)] for k, v in lw["experts"].items()}

    route, experts = get_op_def("moe_route"), get_op_def("moe_experts")
    r = route.compute({"X": u, "W": lw["router"]}, route.canonical_attrs(
        {"k": 6, "norm_topk_prob": False, "scoring_func": "softmax"}))
    chosen = np.zeros((40, 16), bool)
    np.put_along_axis(chosen, np.asarray(r["TopkIdx"]), True, -1)
    total = shared
    for held in shares:
        with jax.default_matmul_precision("highest"):
            part, part_balance = ref.expert_ffn(
                u, dict(lw, experts=stack_of(held)), config, held=held,
                shared=False)
        # every share sees all 16 scores: the same balance term
        assert float(part_balance) == float(whole_balance)
        st = stack_of(held)
        mine = experts.compute(
            {"X": u, "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
             "WGate": st["gate"], "WUp": st["up"], "WDown": st["down"]},
            experts.canonical_attrs({"held": held, "block_m": 16,
                                     "impl": impl}))["Out"]
        np.testing.assert_allclose(mine, part, rtol=1e-4, atol=1e-5)
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    # the program's router gives the reference's selection and scores,
    # so its balance term is the one every share has
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            ref.balance(jnp.asarray(chosen), r["Scores"], config),
            whole_balance, rtol=1e-5)
    # the gates are not renormalised: over ALL experts a token's gates
    # sum to less than 1, and the shares of the gates add up to that
    gate_sum = np.asarray(r["TopkWeight"]).sum(-1)
    assert (gate_sum < 1.0).all() and (gate_sum > 6 / 16).all()


# -- xing4's program, before and after the shared attention -------------------

def _old_xing4_attention(u, config, seq_len, fc, p, lp):
    """models/xing4.py's attention closure as it was at commit 2f47422
    (PR 33), word for word but for the names the closure took from its
    model."""
    heads = config["num_attention_heads"]
    nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    vd, kvr = config["v_head_dim"], config["kv_lora_rank"]
    eps, rs = config["rms_norm_eps"], config.get("rope_scaling") or {}

    def rotary(x):
        mscale = 1.0
        if rs.get("mscale") and rs.get("mscale_all_dim"):
            mscale = rs["mscale"] / rs["mscale_all_dim"]
        return layers.rotary_embedding(
            x, rotary_dim=rope, theta=config["rope_theta"],
            factor=rs.get("factor", 1.0),
            original_max_position=rs.get(
                "original_max_position_embeddings",
                config.get("max_position_embeddings", seq_len)),
            beta_fast=rs.get("beta_fast", 32),
            beta_slow=rs.get("beta_slow", 1), mscale=mscale)

    import math

    scale = (nope + rope) ** -0.5
    if rs.get("factor", 1) > 1 and rs.get("mscale_all_dim"):
        m = 0.1 * rs["mscale_all_dim"] * math.log(rs["factor"]) + 1.0
        scale = (nope + rope) ** -0.5 * m * m
    cq = layers.rms_norm(fc(u, config["q_lora_rank"], lp + "_q_a"),
                         eps, name="%s_%s_q_a_norm" % (p, lp))
    q = layers.reshape(fc(cq, heads * (nope + rope), lp + "_q_b"),
                       [-1, seq_len, heads, nope + rope])
    q = layers.transpose(rotary(q), [0, 2, 1, 3])
    ckv, k_r = layers.split(fc(u, kvr + rope, lp + "_kv_a"),
                            [kvr, rope], dim=2)
    ckv = layers.rms_norm(ckv, eps, name="%s_%s_kv_a_norm" % (p, lp))
    kv = layers.reshape(fc(ckv, heads * (nope + vd), lp + "_kv_b"),
                        [-1, seq_len, heads, nope + vd])
    k_nope, v = layers.split(kv, [nope, vd], dim=3)
    k_r = rotary(layers.reshape(k_r, [-1, seq_len, 1, rope]))
    k = layers.concat([k_nope, layers.expand(k_r, [1, 1, heads, 1])],
                      axis=3)
    out = layers.flash_attention(
        q, layers.transpose(k, [0, 2, 1, 3]),
        layers.transpose(v, [0, 2, 1, 3]), causal=True, scale=scale)
    out = layers.reshape(layers.transpose(out, [0, 2, 1, 3]),
                         [-1, seq_len, heads * vd])
    return fc(out, config["hidden_size"], lp + "_o")


# the program of the parent commit 2f47422 for
# tests/test_xing4_model.py's SMALL configuration
# under RecomputeOptimizer(Adam) [+ AMP], recorded there before the
# refactoring: the number of ops, a hash of their types in order, a hash
# of the sorted parameter names, and the first two losses, which the
# refactored tree gave to the last bit on the same machine.  The AMP
# run's SECOND loss is PR 50's: moe_experts keeps d act, a grouped
# matmul's float32 accumulator, where it rounded it to bfloat16 before
# SwiGLU's gradient, so a bfloat16 step's d W_gate, d W_up and d x
# moved in their last bits (4.608451843261719 before; the first loss,
# and both float32 losses, are the record's).  That the new form is
# the closer one to float32:
# test_llm_ops.test_swiglu_in_the_kernels_is_no_further_from_float32_in_bf16
_XING4_BEFORE = {
    False: (239, "574d01011dfe0835", 91, "bf9f7372699d7510",
            (4.847245216369629, 4.608573436737061)),
    True: (286, "23903eb1f615e51b", 91, "bf9f7372699d7510",
           (4.847033500671387, 4.608583450317383)),
}


@pytest.mark.parametrize("amp", [False, True])
def test_xing4_program_is_the_one_before(amp, monkeypatch):
    """`xing4` builds its attention through models/latent_attention.py
    and its router through the op that gained `scoring_func` and
    `Scores`: same op types in the same order and same parameter names
    as the parent commit's record, and the first losses equal TO THE
    LAST BIT to those of the old closure built in this process."""
    import hashlib

    from test_xing4_model import SMALL as XING
    from paddle_tpu import framework, unique_name
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program
    from paddle_tpu.models import xing4

    def build_and_run():
        framework.switch_main_program(Program())
        framework.switch_startup_program(Program())
        unique_name.switch({})
        scope_mod._global_scope = scope_mod.Scope()
        np.random.seed(0)
        model = xing4.xing4_model(dict(XING), seq_len=32)
        opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-3))
        opt._set_checkpoints(model["checkpoints"])
        if amp:
            opt = decorate(opt, init_loss_scaling=1.0,
                           use_dynamic_loss_scaling=False)
        opt.minimize(model["loss"])
        program = fluid.default_main_program()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids = np.random.default_rng(0).integers(0, 128, (2, 32, 1),
                                                dtype=np.int64)
        compiled = fluid.CompiledProgram(program)
        losses = [float(np.asarray(exe.run(
            compiled, feed={"src_ids": ids, "tgt_label": np.roll(ids, -1, 1)},
            fetch_list=[model["loss"]])[0]).reshape(-1)[0])
            for _ in range(2)]
        # without the stat ops an expert layer has had since PR 36
        # (one `increment`, a `step_stat` a layer, role `stat`): they
        # are no part of the record, and tests/test_step_stats.py
        # holds the losses with and without them to the last bit
        return ([op.type for op in program.global_block().ops
                 if op.op_role != "stat"],
                sorted(p.name for p in program.all_parameters()), losses)

    def digest(lines):
        return hashlib.sha256("\n".join(lines).encode()).hexdigest()[:16]

    types, names, losses = build_and_run()
    n_ops, ops_hash, n_params, names_hash, before = _XING4_BEFORE[amp]
    assert (len(types), digest(types)) == (n_ops, ops_hash)
    assert (len(names), digest(names)) == (n_params, names_hash)
    # the recorded losses are another machine's: equal to rounding
    assert losses == pytest.approx(before, rel=1e-6)
    monkeypatch.setattr(xing4, "latent_attention", _old_xing4_attention)
    old_types, old_names, old_losses = build_and_run()
    assert (old_types, old_names) == (types, names)
    assert [x.hex() for x in old_losses] == [x.hex() for x in losses]


def test_the_benchmarks_reference_is_this_one():
    """benchmarks/reference/deepseek_v2.py, which decides the cell's `correct`
    on the chip, is the file these tests compare the program with, and
    not a copy of it."""
    assert os.path.samefile(ref.__file__, reference_path("deepseek_v2"))

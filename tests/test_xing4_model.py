"""models/xing4.py through the normal path (layers -> [recompute] ->
[AMP] -> backward -> Executor.run(CompiledProgram)) against the plain
reference benchmarks/reference/xing4.py on seeded weights: logits, loss
and EVERY parameter's gradient; and the share test that ties the one-chip
cut (8 of 64 experts held) to the whole layer.

Tolerances, and why.

* float32: program and reference compute the same mathematics in
  another order (fused ops, a sorted grouped matmul against a masked
  loop): logits and loss to 1e-5 of the largest logit, gradients to
  1e-4 of each parameter's largest entry.  bf16 anywhere fails this by
  a factor of 50: `test_float32_tolerance_excludes_bf16` runs the AMP
  program against the same bounds and requires that it FAILS them.
* AMP (bf16 activations and matmul operands; norms, router, mixing
  coefficients and Sinkhorn float32): a rounding of 2^-8 = 3.9e-3 a
  matmul over 8 sublayers: logits to 2e-2 of the largest logit, the
  loss to 1e-3 (a mean over 64 tokens of small logits), gradients to
  0.15 of each parameter's largest entry (1% at the median; the worst,
  4-9% over seeds, are the hyper-connections' biases, sums over every
  token with cancellation), except the gains `*_hc_alpha.w`: each is
  ONE scalar summed over every token and stream element, whose terms
  cancel to a thousandth of their size (a reference computed wholly in
  bf16 is off by 15% of the value there, the program by up to 90%), so
  its error is held to 0.2 of the larger of its own size and its
  hyper-connection's bias gradient, the same terms unweighted (0.07 at
  worst over the cases).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import optimizer
from paddle_tpu.core.scope import global_scope
from paddle_tpu.models.xing4 import xing4_model

from conftest import load_reference, reference_path

ref = load_reference("xing4")

SEQ, BATCH = 32, 2

SMALL = {
    "hidden_size": 64, "intermediate_size": 160,
    "moe_intermediate_size": 32, "num_hidden_layers": 4,
    "first_k_dense_replace": 2, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "n_routed_experts": 4,
    "n_routed_experts_published": 8, "held_experts": [0, 1, 2, 3],
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.0, "hc_mult": 4,
    "hc_sinkhorn_iters": 5, "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30,
    "mhc_h_res_clamp_max": 30, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "vocab_size": 128, "initializer_range": 0.02,
}

F32 = {"logits": 1e-5, "loss": 1e-5, "grad": 1e-4, "alpha": 1e-4}
AMP = {"logits": 2e-2, "loss": 1e-3, "grad": 0.15, "alpha": 0.2}


def _run(config, amp, recompute):
    """(program's loss, logits, {param: grad}), (reference's same)."""
    model = xing4_model(config, seq_len=SEQ)
    opt = optimizer.SGD(0.0)
    if recompute:
        opt = optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints(model["checkpoints"])
    if amp:
        from paddle_tpu.contrib.mixed_precision import decorate

        opt = decorate(opt, init_loss_scaling=1.0,
                       use_dynamic_loss_scaling=False)
    params_grads = opt.backward(model["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(0)
    # seeded selection biases, so that selection and weight are told
    # apart (zeros would select by score alone)
    for i in range(config["first_k_dense_replace"],
                   config["num_hidden_layers"]):
        global_scope().find_var("xing_l%d_router_bias.w" % i).set(
            jnp.asarray(rng.normal(0, 0.05, 8).astype(np.float32)))
    ids = rng.integers(0, config["vocab_size"], (BATCH, SEQ, 1),
                       dtype=np.int64)
    batch = (ids, np.roll(ids, -1, axis=1))

    # the reference first: the step donates the weights
    params = jax.tree_util.tree_map(
        lambda a: jnp.array(a, copy=True),
        ref.read_params(config, lambda n: global_scope().find_var(n).get()))
    ids32, labels32 = ref._split(batch)
    want_loss, want_grads = jax.value_and_grad(
        lambda p: ref.batch_loss(p, ids32, labels32, config))(params)
    with jax.default_matmul_precision("highest"):
        want_logits = jax.vmap(
            lambda i: ref.sequence_logits(params, i, config))(ids32)
    names = jax.tree_util.tree_leaves(ref.param_names(config))
    want = dict(zip(names, jax.tree_util.tree_leaves(want_grads)))

    outs = exe.run(fluid.CompiledProgram(fluid.default_main_program()),
                   feed={"src_ids": batch[0], "tgt_label": batch[1]},
                   fetch_list=[model["loss"], model["logits"]]
                   + [g for _, g in params_grads])
    got = {p.name: np.asarray(o, np.float32)
           for (p, _), o in zip(params_grads, outs[2:])}
    return ((float(np.asarray(outs[0]).reshape(-1)[0]),
             np.asarray(outs[1], np.float32), got),
            (float(want_loss), np.asarray(want_logits), want))


def _worst(got, want):
    """Largest |got - want| over the parameter's largest |want|, for
    the hyper-connection gains (against the larger of their own and
    their bias gradient's size) and for every other parameter."""
    alpha, rest = 0.0, 0.0
    for name, w in want.items():
        if name.endswith("_router_bias.w"):
            assert name not in got          # no gradient, by design
            continue
        w = np.asarray(w)
        err = float(np.abs(got[name] - w).max())
        if name.endswith("_hc_alpha.w"):
            bias = np.asarray(want[name.replace("_alpha.w", "_bias.w")])
            alpha = max(alpha, err / max(np.abs(w).max(),
                                         np.abs(bias).max()))
        else:
            rest = max(rest, err / float(np.abs(w).max()))
    return alpha, rest


def _check(got, want, tol):
    (loss, logits, grads), (want_loss, want_logits, want_grads) = got, want
    assert set(grads) == {n for n in want_grads
                          if not n.endswith("_router_bias.w")}
    assert loss == pytest.approx(want_loss, rel=tol["loss"])
    scale = float(np.abs(want_logits).max())
    assert float(np.abs(logits - want_logits).max()) <= tol["logits"] * scale
    alpha, rest = _worst(grads, want_grads)
    assert rest <= tol["grad"], rest
    assert alpha <= tol["alpha"], alpha


CASES = {
    "f32": (dict(SMALL), False, False, F32),
    "f32_sinkhorn20_held2": (dict(SMALL, hc_sinkhorn_iters=20,
                                  n_routed_experts=2, held_experts=[1, 6]),
                             False, False, F32),
    "f32_recompute": (dict(SMALL), False, True, F32),
    "amp": (dict(SMALL, hc_sinkhorn_iters=20), True, False, AMP),
    "amp_recompute_held3": (dict(SMALL, n_routed_experts=3,
                                 held_experts=[0, 3, 5]), True, True, AMP),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_program_against_reference(case):
    config, amp, recompute, tol = CASES[case]
    got, want = _run(config, amp, recompute)
    _check(got, want, tol)
    # random weights at this width give small logits: the loss is near
    # ln(128) and its weight-dependent part is what the bound is of
    assert 0.9 * np.log(128) < want[0] < 1.1 * np.log(128)


def test_float32_tolerance_excludes_bf16():
    """The float32 bounds are tight enough that computing in bf16 where
    float32 is stated fails them: the AMP program is outside the
    logits' and the gradients' float32 bound by a wide margin."""
    got, want = _run(dict(SMALL), True, False)
    scale = float(np.abs(want[1]).max())
    assert float(np.abs(got[1] - want[1]).max()) > 50 * F32["logits"] * scale
    _, rest = _worst(got[2], want[2])
    assert rest > 20 * F32["grad"]


def test_program_is_verified_and_shape_checked():
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes
    from paddle_tpu.contrib.mixed_precision import decorate

    model = xing4_model(dict(SMALL), seq_len=SEQ)
    opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-3))
    opt._set_checkpoints(model["checkpoints"])
    decorate(opt, init_loss_scaling=1.0,
             use_dynamic_loss_scaling=False).minimize(model["loss"])
    program = fluid.default_main_program()
    verifier.verify(program)
    _, diags = infer_program_shapes(program)
    assert not [d for d in diags if d.severity == "error"], diags
    assert len(model["checkpoints"]) == SMALL["num_hidden_layers"]
    types = {op.type for op in program.global_block().ops}
    assert {"mhc_pre", "mhc_post", "moe_route", "moe_experts", "rms_norm",
            "rotary_embedding", "swiglu", "flash_attention",
            "recompute_segment_grad"} <= types
    # the streams are bf16 under AMP: both halves of the first
    # hyper-connection read ONE cast of the float32 embedding
    casts = [op for op in program.global_block().ops
             if op.type == "cast" and op.inputs["X"][0].startswith("expand")]
    assert len(casts) == 1


# -- the share test -----------------------------------------------------------

def _layer_weights(rng, c=32, w=16, e=8):
    import jax.numpy as jnp

    def mat(*shape):
        return jnp.asarray(rng.normal(0, 0.3, shape), jnp.float32)

    return {"router": mat(c, e),
            "router_bias": jnp.asarray(rng.normal(0, 0.05, e), jnp.float32),
            "experts": {"gate": mat(e, c, w), "up": mat(e, c, w),
                        "down": mat(e, w, c)},
            "shared": {"gate": mat(c, w), "up": mat(c, w),
                       "down": mat(w, c)}}


@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_the_shares_add_up_to_the_whole_layer(impl):
    """8 experts over 4 chips, 2 held on each.  The routed parts the 4
    shares give, plus the shared expert ONCE, are the uncut layer of the
    reference; and each share of the PROGRAM's ops equals the
    reference's share."""
    from paddle_tpu.core.registry import get_op_def
    from paddle_tpu.ops import pallas_kernels as pk

    def counted(kernel):
        return sum(v for lbl, v in pk._M_KERNEL_IMPL.items()
                   if lbl["kernel"] == kernel)

    before = counted("moe_gmm_tile")
    rng = np.random.default_rng(11)
    lw = _layer_weights(rng)
    config = {"num_experts_per_tok": 3, "norm_topk_prob": True,
              "routed_scaling_factor": 2.0, "n_routed_experts": 8}
    u = jnp.asarray(rng.normal(0, 1, (40, 32)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = ref.expert_ffn(u, lw, config, held=list(range(8)))
        shared = ref.swiglu(u, lw["shared"])
    shares = [[0, 1], [2, 3], [4, 5], [6, 7]]

    def stack_of(held):
        return {k: v[jnp.asarray(held)] for k, v in lw["experts"].items()}

    route, experts = get_op_def("moe_route"), get_op_def("moe_experts")
    r = route.compute({"X": u, "W": lw["router"],
                       "Bias": lw["router_bias"]},
                      route.canonical_attrs(
                          {"k": 3, "routed_scaling_factor": 2.0}))
    total, loads = shared, []
    for held in shares:
        with jax.default_matmul_precision("highest"):
            part = ref.expert_ffn(u, dict(lw, experts=stack_of(held)),
                                  config, held=held, shared=False)
        st = stack_of(held)
        outs = experts.compute(
            {"X": u, "TopkIdx": r["TopkIdx"], "TopkWeight": r["TopkWeight"],
             "WGate": st["gate"], "WUp": st["up"], "WDown": st["down"]},
            experts.canonical_attrs({"held": held, "block_m": 16,
                                     "impl": impl}))
        np.testing.assert_allclose(outs["Out"], part, rtol=1e-4, atol=1e-5)
        total = total + part
        loads.append(np.asarray(outs["Load"]))
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)
    # every grouped-matmul call that ran a kernel (3 a share; none on
    # the XLA form) counted its block shape
    assert counted("moe_gmm_tile") - before == \
        (12 if impl == "interpret" else 0)
    # and each share's Load says what its grids ran: the pairs routed to
    # its two experts, their sum, and the row tiles of 16 that hold
    # them; over the four shares every one of the 40 x 3 pairs once
    ids = np.asarray(r["TopkIdx"])
    for held, load in zip(shares, loads):
        sizes = [int((ids == e).sum()) for e in held]
        assert load.tolist() == sizes + [sum(sizes), sum(
            max(-(-s // 16), 1) for s in sizes)]
    assert sum(load[2] for load in loads) == 40 * 3
    # every token's gates sum to the scaling factor over ALL experts, so
    # the shares of the gates add up too
    np.testing.assert_allclose(np.asarray(r["TopkWeight"]).sum(-1), 2.0,
                               rtol=1e-6)


# -- the AMP rewrite casts a var once -----------------------------------------

def test_amp_gradients_of_a_var_read_by_several_matmuls():
    """A float32 activation read by the q, k and v projections used to
    be cast once a reader under ONE name, and append_backward then fed
    the summed gradient of that name to every cast's grad op: the
    embedding's gradient came out 1.5x too large (slope of the AMP
    gradient on the float32 one)."""
    from paddle_tpu import framework, unique_name
    from paddle_tpu.contrib.mixed_precision import decorate
    from paddle_tpu.core import scope as scope_mod
    from paddle_tpu.core.program import Program
    from paddle_tpu.models.transformer import transformer_encoder_model

    grads = {}
    for amp in (False, True):
        framework.switch_main_program(Program())
        framework.switch_startup_program(Program())
        unique_name.switch({})
        scope_mod._global_scope = scope_mod.Scope()
        np.random.seed(0)
        m = transformer_encoder_model(
            vocab_size=64, max_len=16, d_model=32, n_head=2, d_inner=64,
            n_layer=2, dropout_rate=0.0, param_prefix="tfm")
        opt = optimizer.SGD(0.0)
        if amp:
            opt = decorate(opt, init_loss_scaling=1.0,
                           use_dynamic_loss_scaling=False)
        pg = opt.backward(m["loss"])
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids = np.random.default_rng(0).integers(0, 64, (2, 16, 1),
                                                dtype=np.int64)
        outs = exe.run(
            fluid.CompiledProgram(fluid.default_main_program()),
            feed={"src_ids": ids, "tgt_label": np.roll(ids, -1, 1)},
            fetch_list=[g for _, g in pg])
        grads[amp] = {p.name: np.asarray(o, np.float32)
                      for (p, _), o in zip(pg, outs)}
        if amp:
            names = [op.outputs["Out"][0] for op in
                     fluid.default_main_program().global_block().ops
                     if op.type == "cast"]
            assert len(names) == len(set(names))
    for name, want in grads[False].items():
        got = grads[True][name]
        slope = float((got * want).sum() / (want * want).sum())
        assert slope == pytest.approx(1.0, abs=0.03), name


def test_the_benchmarks_reference_is_this_one():
    """benchmarks/reference/xing4.py, which decides the cell's `correct`
    on the chip, is the file these tests compare the program with, and
    not a copy of it."""
    assert os.path.samefile(ref.__file__, reference_path("xing4"))

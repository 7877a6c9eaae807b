"""A looped decoder (model_type `ouro`, "Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741), built from a
`config.json`-style dict: a stack of `num_hidden_layers` dense layers
run `total_ut_steps` times over ONE set of weights, with an output head
and a one-output exit gate read after every pass and a loss that weighs
the passes' cross-entropies by the exit distribution the gates define.
docs/OURO_BLOCK.md writes the equations out;
benchmarks/reference/ouro.py is the plain float32 reference of the
same equations.

A layer: RMSNorm, full causal attention (16 heads of 128 at the
published size, no bias, split-half rotary; k and v at
`num_key_value_heads` heads where a configuration has fewer), RMSNorm of what attention
gives, residual; RMSNorm, SwiGLU, RMSNorm, residual: four norms.  After
the last layer of a pass ONE final norm gives h^r, which the head and
the gate read and from which pass r + 1 starts.

The passes are R runs of the layer builder over parameter names that
do not carry r: the global block holds each parameter once
(LayerHelper.create_parameter shares by name), so the persistables and
the optimizer's state do not grow with R, and R = 1 is a plain
four-norm decoder.  Attention is token-major end to end: [B, T, H*d]
projections, turned where they lie (`rotary_embedding(..., n_head=H)`:
no reshape to [B, T, H, d], which is a relayout of every tile, PERF.md,
PR 54), `flash_attention(..., n_head=H)`.

As a Fluid trainer uses it:

    model = ouro_model(config, seq_len=4096)
    opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-4))
    opt._set_checkpoints(model["checkpoints"])
    opt = decorate(opt, init_loss_scaling=1.0,
                   use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    exe.run(fluid.CompiledProgram(fluid.default_main_program()), ...)
"""

from __future__ import annotations

from paddle_tpu import layers
from paddle_tpu.framework import name_scope
from paddle_tpu.initializer import Constant, Normal
from paddle_tpu.param_attr import ParamAttr


def ouro_model(config, seq_len, param_prefix="ouro"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), `logits` (the R passes'
    [B, T, vocab]), `exit_probs` (the R passes' p^r, [B, T, 1]; none
    for R = 1, where the one pass has all the mass), loss,
    and `checkpoints` for RecomputeOptimizer._set_checkpoints: the state
    after every layer execution (the last of a pass after the final
    norm: h^r) and every pass's per-token cross-entropy, so that a
    pass's logits live only inside its head segment."""
    c, heads, d = (config["hidden_size"], config["num_attention_heads"],
                   config["head_dim"])
    kv_heads = config.get("num_key_value_heads") or heads
    if config.get("rope_scaling"):
        raise NotImplementedError("ouro_model: rope_scaling %r"
                                  % (config["rope_scaling"],))
    width, vocab = config["intermediate_size"], config["vocab_size"]
    eps, passes = config["rms_norm_eps"], config.get("total_ut_steps", 1)
    beta = config.get("exit_entropy_beta", 0.05)
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name, bias=False):
        return layers.fc(
            x, size, num_flatten_dims=2,
            param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                 initializer=init),
            bias_attr=ParamAttr(name="%s_%s.b" % (p, name),
                                initializer=Constant(0.0))
            if bias else False)

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name))

    def rotary(x, n):
        # the projection as it comes: n heads side by side
        return layers.rotary_embedding(x, theta=config["rope_theta"],
                                       pairing="halves", n_head=n)

    def layer(x, lp):
        a = norm(x, lp + "_norm1")
        # k and v at num_key_value_heads heads: the kernels read a
        # query head's KV head in place (grouped-query attention)
        o = layers.flash_attention(
            rotary(fc(a, heads * d, lp + "_q"), heads),
            rotary(fc(a, kv_heads * d, lp + "_k"), kv_heads),
            fc(a, kv_heads * d, lp + "_v"), causal=True, n_head=heads,
            n_kv_head=kv_heads)
        x = layers.elementwise_add(x, norm(fc(o, c, lp + "_o"),
                                           lp + "_norm2"))
        m = norm(x, lp + "_norm3")
        f = fc(layers.swiglu(fc(m, width, lp + "_gate"),
                             fc(m, width, lp + "_up")), c, lp + "_down")
        return layers.elementwise_add(x, norm(f, lp + "_norm4"))

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    h = layers.embedding(
        src, [vocab, c], param_attr=ParamAttr(name=p + "_emb.w",
                                              initializer=init))
    checkpoints, logits, ce, gates = [], [], [], []
    for r in range(passes):
        with name_scope("pt_ut_step"):
            for i in range(config["num_hidden_layers"]):
                h = layer(h, "l%d" % i)
                if i + 1 < config["num_hidden_layers"]:
                    checkpoints.append(h)
            # ONE final norm, after every pass and before re-entry
            h = norm(h, "final_norm")
            checkpoints.append(h)
        # the pass's head segment.  The gate first: g leaves the segment
        # beside the per-token cross-entropy, which closes it.  The last
        # pass takes the mass that is left: no gate is read there, so
        # none is formed
        if r + 1 < passes:
            with name_scope("pt_exit_gate"):
                gates.append(fc(h, 1, "exit_gate", bias=True))
        with name_scope("pt_loop_loss"):
            z = fc(h, vocab, "head")
            ell = layers.softmax_with_cross_entropy(z, label)
        logits.append(z)
        ce.append(ell)
        checkpoints.append(ell)
    with name_scope("pt_loop_loss"):
        # ln p^r = ln sigma(g^r) + sum_{j<r} ln (1 - sigma(g^j)), the
        # last pass the sum alone; ln (1 - sigma(g)) = ln sigma(g) - g.
        # In logarithms a saturated gate gives p = 0 and p ln p = 0,
        # not 0 x -inf
        log_exit, stayed = [], None
        for g in gates:
            ls = layers.logsigmoid(g)
            log_exit.append(ls if stayed is None
                            else layers.elementwise_add(ls, stayed))
            gone = layers.elementwise_sub(ls, g)
            stayed = gone if stayed is None \
                else layers.elementwise_add(stayed, gone)
        exit_probs, per_token = [], ce[0]
        if gates:
            log_exit.append(stayed)
            exit_probs = [layers.exp(lp) for lp in log_exit]
            # sum_r p^r (l^r + beta ln p^r) = sum_r p^r l^r - beta H(p)
            per_token = layers.sums([
                layers.elementwise_mul(
                    pr, layers.elementwise_add(
                        ell, layers.scale(lp, scale=float(beta))))
                for pr, ell, lp in zip(exit_probs, ce, log_exit)])
        loss = layers.mean(per_token)
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "exit_probs": exit_probs, "loss": loss,
            "checkpoints": checkpoints}

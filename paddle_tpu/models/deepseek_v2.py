"""A DeepSeek-V2 decoder (model_type `deepseek_v2`, arXiv:2405.04434),
built from a `config.json`-style dict.  docs/DSV2_BLOCK.md writes the
equations out; benchmarks/reference/deepseek_v2.py is the plain
float32 reference of the same equations.

Per layer, on ONE pre-norm residual stream: x + attn(norm(x)), then
x + ffn(norm(x)).  Attention is latent (models/latent_attention.py):
with `q_lora_rank` null the query is one full-rank projection, keys and
values come from a rank-`kv_lora_rank` latent plus a shared rotary key.
The feed-forward is a SwiGLU of width `intermediate_size` in the first
`first_k_dense_replace` layers; after them the `n_shared_experts`
shared experts, which are ONE SwiGLU of width n_shared_experts x
moe_intermediate_size (stacked gate, up and down matrices compute the
sum of the experts), plus the routed experts in `held_experts` chosen
by a softmax top-k router over ALL `n_routed_experts_published`
experts, gates not renormalised (`norm_topk_prob` false).

With `seq_aux` the training loss is the mean next-token cross-entropy
plus, for every expert layer, the sequence-wise balance loss
(`layers.moe_balance_loss`, coefficient `aux_loss_alpha`), which
reaches the router through all of a token's scores.

As a Fluid trainer uses it:

    model = deepseek_v2_model(config, seq_len=4096)
    opt = optimizer.RecomputeOptimizer(optimizer.Adam(1e-4))
    opt._set_checkpoints(model["checkpoints"])
    opt = decorate(opt, init_loss_scaling=1.0,
                   use_dynamic_loss_scaling=False)
    opt.minimize(model["loss"])
    exe.run(fluid.CompiledProgram(fluid.default_main_program()), ...)
"""

from __future__ import annotations

from paddle_tpu import layers
from paddle_tpu.initializer import Normal
from paddle_tpu.models.latent_attention import (
    held_experts, latent_attention, router_width)
from paddle_tpu.param_attr import ParamAttr


def deepseek_v2_model(config, seq_len, param_prefix="dsv2"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, `ce_loss` (mean
    cross-entropy over all positions), `aux_loss` (the expert layers'
    balance losses summed; None without `seq_aux` or expert layers),
    `loss` (their sum: what is minimised) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer.  An expert layer's balance loss is formed before the add
    that closes its segment, and leaves the segment beside the
    stream."""
    c, eps = config["hidden_size"], config["rms_norm_eps"]
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                              initializer=init))

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name))

    def swiglu_ffn(u, width, lp):
        act = layers.swiglu(fc(u, width, lp + "_gate"),
                            fc(u, width, lp + "_up"))
        return fc(act, c, lp + "_down")

    def expert_ffn(u, lp):
        """(shared(u) + routed(u), the layer's balance loss or None)"""
        idx, gate, scores = layers.moe_route(
            u, router_width(config), config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp),
            scoring_func=config["scoring_func"], return_scores=True)
        aux = None
        if config.get("seq_aux"):
            aux = layers.moe_balance_loss(idx, scores,
                                          config["aux_loss_alpha"])
        routed = layers.moe_experts(
            u, idx, gate, held_experts(config),
            config["moe_intermediate_size"], param_attr=init,
            name="%s_%s_experts" % (p, lp))
        shared = swiglu_ffn(
            u, config["moe_intermediate_size"] * config["n_shared_experts"],
            lp + "_shared")
        return layers.elementwise_add(shared, routed), aux

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    x = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    checkpoints, aux_losses = [], []
    for i in range(config["num_hidden_layers"]):
        lp = "l%d" % i
        attn = latent_attention(norm(x, lp + "_attn_norm"), config,
                                seq_len, fc, p, lp)
        x = layers.elementwise_add(x, attn)
        u = norm(x, lp + "_ffn_norm")
        if i < config["first_k_dense_replace"]:
            y = swiglu_ffn(u, config["intermediate_size"], lp)
        else:
            y, aux = expert_ffn(u, lp)
            if aux is not None:
                aux_losses.append(aux)
        x = layers.elementwise_add(x, y)
        checkpoints.append(x)
    logits = fc(norm(x, "final_norm"), config["vocab_size"], "head")
    ce_loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    aux_loss, loss = None, ce_loss
    if aux_losses:
        aux_loss = layers.sums(aux_losses)
        loss = layers.elementwise_add(ce_loss, aux_loss)
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "ce_loss": ce_loss, "aux_loss": aux_loss,
            "checkpoints": checkpoints}

"""A DeepSeek-V3-family decoder with manifold-constrained hyper-
connections, built from a `config.json`-style dict (model_type
`xing4_0`: deepseek_v3's keys plus hc_mult, hc_sinkhorn_iters, hc_eps,
mhc_h_res_clamp_min/max).  docs/XING4_BLOCK.md writes the equations
out; benchmarks/reference/xing4.py is the plain float32 reference of
the same equations.

Per layer: latent attention (q through a rank-`q_lora_rank` bottleneck,
k and v from one rank-`kv_lora_rank` latent plus a shared rotary key;
q.k size qk_nope + qk_rope, v size v_head_dim; `flash_attention`), then
a SwiGLU feed-forward: dense in the first `first_k_dense_replace`
layers, after them a shared expert plus the routed experts in `held`
chosen by a sigmoid top-k router over ALL `n_routed_experts_published`
experts.  Each sublayer reads from and writes to `hc_mult` residual
streams through mhc_pre / mhc_post.

As a Fluid trainer uses it:

    model = xing4_model(config, seq_len=4096)
    opt = decorate(optimizer.Adam(1e-4), init_loss_scaling=1.0,
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


def xing4_model(config, seq_len, param_prefix="xing"):
    """Builds the training program into the default programs: next-token
    cross-entropy over `vocab_size` ids, mean over all positions.
    Returns src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss
    and `checkpoints`: the stream state after each layer, for
    RecomputeOptimizer._set_checkpoints."""
    c, n = config["hidden_size"], config["hc_mult"]
    eps = config["rms_norm_eps"]
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def w(name):
        return ParamAttr(name="%s_%s.w" % (p, name), initializer=init)

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=w(name))

    def attention(u, lp):
        return latent_attention(u, config, seq_len, fc, p, lp)

    def swiglu_ffn(u, width, lp):
        act = layers.swiglu(fc(u, width, lp + "_gate"),
                            fc(u, width, lp + "_up"))
        return fc(act, c, lp + "_down")

    def expert_ffn(u, lp):
        idx, gate = layers.moe_route(
            u, router_width(config), config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp))
        routed = layers.moe_experts(
            u, idx, gate, held_experts(config),
            config["moe_intermediate_size"], param_attr=init,
            name="%s_%s_experts" % (p, lp))
        shared = swiglu_ffn(
            u, config["moe_intermediate_size"] * config["n_shared_experts"],
            lp + "_shared")
        return layers.elementwise_add(shared, routed)

    def sublayer(x, fn, lp, kind):
        u, h_post, h_res = layers.mhc_pre(
            x, sinkhorn_iters=config["hc_sinkhorn_iters"],
            eps=config["hc_eps"], clamp_min=config["mhc_h_res_clamp_min"],
            clamp_max=config["mhc_h_res_clamp_max"], param_attr=init,
            name="%s_%s_%s_hc" % (p, lp, kind))
        u = layers.rms_norm(u, eps, name="%s_%s_%s_norm" % (p, lp, kind))
        return layers.mhc_post(x, fn(u, lp), h_post, h_res)

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    h = layers.embedding(src, [config["vocab_size"], c],
                         param_attr=w("emb"))
    # the input replicated into every stream (Hyper-Connections, 3)
    # stream-major: [B, n, T, C]
    x = layers.expand(layers.reshape(h, [-1, 1, seq_len, c]), [1, n, 1, 1])
    checkpoints = []
    for i in range(config["num_hidden_layers"]):
        lp = "l%d" % i
        x = sublayer(x, attention, lp, "attn")
        if i < config["first_k_dense_replace"]:
            x = sublayer(
                x, lambda u, lp: swiglu_ffn(u, config["intermediate_size"],
                                            lp), lp, "ffn")
        else:
            x = sublayer(x, expert_ffn, lp, "ffn")
        checkpoints.append(x)
    out = layers.rms_norm(layers.reduce_sum(x, dim=1), eps,
                          name="%s_final_norm" % p)
    logits = fc(out, config["vocab_size"], "head")
    loss = layers.mean(layers.softmax_with_cross_entropy(logits, label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

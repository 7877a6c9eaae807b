"""The language model of Ling-3.0-flash-VL, built from a
`config.json`-style dict: a hybrid stack in which, of every
`layer_group_size` layers, the last mixes by latent attention (the
function `xing4` and `deepseek-v2-lite` share, here with a head-wise
output gate) and the others by Kimi Delta Attention (KDA: a delta-rule
linear attention with a decay per state channel; arXiv:2510.26692);
after `first_k_dense_replace` dense layers the feed-forward is a shared
expert plus the routed experts in `held_experts`, chosen by a
group-limited sigmoid router over ALL `num_experts_published` experts
(DeepSeek-V3's `noaux_tc`).  docs/LING3_BLOCK.md writes the equations
out; benchmarks/reference/ling3.py is the plain float32 reference of
the same equations.  The vision tower is not built.

    h <- h + Mixer_l(RMSNorm(h)),  h <- h + FFN_l(RMSNorm(h))
    logits = RMSNorm(h_L) W_head                         (untied)

As a Fluid trainer uses it:

    model = ling3_model(config, seq_len=4096)
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
from paddle_tpu.initializer import Normal
from paddle_tpu.models.latent_attention import latent_attention
from paddle_tpu.param_attr import ParamAttr

# (key, the one value that is built, what another value would ask for)
_UNBUILT = (
    ("num_kv_heads_for_linear_attn", 0, "grouped heads in a KDA layer"),
    ("group_norm_size", 1, "a norm over several heads"),
    ("linear_silu", True, "another activation after the short conv"),
    ("use_kda_lora", False, "a low-rank decay projection"),
    ("no_kda_lora", True, "a low-rank decay projection"),
    ("kda_safe_gate", True, "an unbounded decay gate"),
    ("use_mla_nope", False, "latent attention without rotary keys"),
    ("use_nGPT", False, "normalised-GPT updates"),
    ("scale_router_input", False, "a scaled router input"),
    ("value_norm", False, "a norm on the values"),
    ("up_proj_norm", False, "a norm on the up projection"),
    ("score_function", "sigmoid", "another router score"),
    ("moe_router_enable_expert_bias", True, "a router without its bias"),
    ("gated_attention_proj_granularity_type", "head_wise",
     "another output gate"),
    ("q_lora_rank", None, "a low-rank query"),
)


def layer_kinds(config):
    """"kda" or "mla" for each layer kept: the published layers 0 ..
    num_hidden_layers - 1; every `layer_group_size`-th is latent
    attention."""
    period = config["layer_group_size"]
    return ["mla" if (i + 1) % period == 0 else "kda"
            for i in range(config["num_hidden_layers"])]


def ling3_model(config, seq_len, param_prefix="ling3"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss (mean
    cross-entropy over all positions) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError("ling3_model: %s %r (%s)"
                                      % (key, config[key], what))
    layers_n = config["num_hidden_layers"]
    for key in ("expert_swiglu_limit_list",
                "share_expert_swiglu_limit_list"):
        if any(config.get(key, [])[:layers_n]):
            raise NotImplementedError(
                "ling3_model: %s is non-zero in a layer that is kept (a "
                "clamped SwiGLU)" % key)
    c, eps = config["hidden_size"], config["rms_norm_eps"]
    heads, d = config["num_attention_heads"], config["head_dim"]
    held = list(config.get("held_experts")
                or range(config["num_experts"]))
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix
    # what latent_attention() reads beside the published keys
    mla_config = dict(config, attention_output_gate=config[
        "gated_attention_proj_granularity_type"])

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

    def kda(u, lp):
        def branch(name):
            return layers.causal_conv1d(
                fc(u, heads * d, "%s_kda_%s" % (lp, name)),
                config["short_conv_kernel_size"], activation="silu",
                bias_attr=False,
                name="%s_%s_kda_%s_conv" % (p, lp, name))

        q = layers.head_l2_norm(branch("q"), heads, scale=d ** -0.5)
        k = layers.head_l2_norm(branch("k"), heads)
        g = layers.kda_gate(fc(u, heads * d, lp + "_kda_a"), heads,
                            lower_bound=config["kda_lower_bound"],
                            name="%s_%s_kda_decay" % (p, lp))
        # the write strength in float32, whatever the projection's dtype
        beta = layers.sigmoid(layers.cast(fc(u, heads, lp + "_kda_beta"),
                                          "float32"))
        o = layers.kda_scan(q, k, branch("v"), g, beta,
                            chunk_size=config.get("kda_chunk_size", 64),
                            block_chunks=config.get("kda_block_chunks", 4),
                            name="%s_%s_kda" % (p, lp))
        o = layers.head_gated_rms_norm(
            o, fc(u, heads, lp + "_kda_gate"), eps,
            name="%s_%s_kda_norm" % (p, lp))
        return fc(o, c, lp + "_kda_o")

    def expert_ffn(u, lp):
        idx, gate = layers.moe_route(
            u, config["num_experts_published"],
            config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp), scoring_func="sigmoid",
            n_group=config["n_group"], topk_group=config["topk_group"])
        routed = layers.moe_experts(
            u, idx, gate, held, config["moe_intermediate_size"],
            param_attr=init, name="%s_%s_experts" % (p, lp))
        shared = swiglu_ffn(u, config["moe_shared_expert_intermediate_size"],
                            lp + "_shared")
        return layers.elementwise_add(shared, routed)

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    x = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    checkpoints = []
    for i, kind in enumerate(layer_kinds(config)):
        lp = "l%d" % i
        with name_scope("pt_ling3_" + kind):
            u = norm(x, lp + "_mixer_norm")
            mixed = kda(u, lp) if kind == "kda" else latent_attention(
                u, mla_config, seq_len, fc, p, lp + "_mla")
            x = layers.elementwise_add(x, mixed)
        with name_scope("pt_ling3_ffn"):
            u = norm(x, lp + "_ffn_norm")
            if i < config["first_k_dense_replace"]:
                y = swiglu_ffn(u, config["intermediate_size"], lp)
            else:
                y = expert_ffn(u, lp)
            x = layers.elementwise_add(x, y)
        checkpoints.append(x)
    with name_scope("pt_ling3_head"):
        logits = fc(norm(x, "final_norm"), config["vocab_size"], "head")
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

"""Solar-Open2-250B (model_type `solar_open2`), built from a
`config.json`-style dict as ONE tensor- and expert-parallel rank holds
it: a stack in which the layers in `gqa_layers` mix by gated grouped-KV
softmax attention WITHOUT positions (`use_rope` false) and the others
by Kimi Delta Attention (KDA, arXiv:2510.26692) with an UNBOUNDED
channel decay (the softplus gate) and write strengths up to 2
(`kda_allow_neg_eigval`); every layer ends in one shared expert plus
the routed experts in `held_experts`, chosen by a bias-selected sigmoid
router without groups over ALL `n_routed_experts_published` experts.
docs/SOLAR_OPEN2_BLOCK.md writes the equations out;
benchmarks/reference/solar_open2.py is the plain float32 reference of
the same equations.

    h <- h + Mixer_l(RMSNorm(h)),  h <- h + FFN_l(RMSNorm(h))
    logits = RMSNorm(h_L) W_head                         (untied)

The share of a mixer.  The counts of heads in the configuration are
the heads HELD here (a tensor-parallel rank's): `num_attention_heads`
query heads with their `num_key_value_heads` KV heads, and
`kda_heads_held` KDA heads (default: all `linear_attn_config.num_heads`
of them).  A rank holds its heads' columns of W_q, W_k, W_v, of the
gates' up projections and of w_beta, its heads' A_log, dt_bias and
filters, and its heads' ROWS of W_o; the low-rank gates' down
projections, the norms, the router and the shared expert are whole on
every rank.  What W_o gives is the rank's PART of the mixer's output:
it goes on into the residual stream as it is, without the all-reduce
that would add the other ranks' parts, as `moe_experts(held=...)`
gives the held experts' part of a layer.  Nothing stands in for the
absent ranks.

As a Fluid trainer uses it:

    model = solar_open2_model(config, seq_len=8192)
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
from paddle_tpu.param_attr import ParamAttr

# (key, the one value that is built, what another value would ask for)
_UNBUILT = (
    ("use_rope", False, "rotary positions in the attention layers"),
    ("use_gqa_gate", True, "an attention layer without its output gate"),
    ("kda_use_full_proj", False, "full-rank decay and gate projections"),
    ("kda_allow_neg_eigval", True, "write strengths in (0, 1)"),
    ("first_k_dense_replace", 0, "leading dense layers"),
    ("n_shared_experts", 1, "another number of shared experts"),
    ("tie_word_embeddings", False, "a tied head"),
)


def layer_kinds(config):
    """"gqa" or "kda" for each layer kept: the published layers 0 ..
    num_hidden_layers - 1; those in `gqa_layers` are attention."""
    gqa = set(config["gqa_layers"])
    return ["gqa" if i in gqa else "kda"
            for i in range(config["num_hidden_layers"])]


def solar_open2_model(config, seq_len, param_prefix="solar"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss (mean
    cross-entropy over all positions) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError("solar_open2_model: %s %r (%s)"
                                      % (key, config[key], what))
    linear = config["linear_attn_config"]
    if linear.get("num_kv_heads") is not None:
        raise NotImplementedError(
            "solar_open2_model: linear_attn_config.num_kv_heads %r "
            "(grouped heads in a KDA layer)" % (linear["num_kv_heads"],))
    c, eps = config["hidden_size"], config["rms_norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["head_dim"]
    kda_heads = config.get("kda_heads_held", linear["num_heads"])
    kda_d = linear["head_dim"]
    held = list(config.get("held_experts")
                or range(config["n_routed_experts"]))
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                              initializer=init))

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name))

    def gqa(u, lp):
        # k and v at the KV heads held: the kernels read a query
        # head's KV head in place; no positions, no norm on q or k
        o = layers.flash_attention(
            fc(u, heads * d, lp + "_gqa_q"),
            fc(u, kv_heads * d, lp + "_gqa_k"),
            fc(u, kv_heads * d, lp + "_gqa_v"), causal=True,
            n_head=heads, n_kv_head=kv_heads)
        # a gate a channel from its own projection, before W_o
        o = layers.head_gated_rms_norm(
            o, fc(u, heads * d, lp + "_gqa_gate"), norm=False)
        return fc(o, c, lp + "_gqa_o")

    def kda(u, lp):
        def branch(name):
            return layers.causal_conv1d(
                fc(u, kda_heads * kda_d, "%s_kda_%s" % (lp, name)),
                linear["short_conv_kernel_size"], activation="silu",
                bias_attr=False,
                name="%s_%s_kda_%s_conv" % (p, lp, name))

        def low_rank(name):
            # rank head_dim (Kimi Linear's): the down projection whole
            # on every rank, the up projection's columns of the heads
            # held
            return fc(fc(u, kda_d, "%s_kda_%s_a" % (lp, name)),
                      kda_heads * kda_d, "%s_kda_%s_b" % (lp, name))

        q = layers.head_l2_norm(branch("q"), kda_heads,
                                scale=kda_d ** -0.5)
        k = layers.head_l2_norm(branch("k"), kda_heads)
        # no bound: the scan takes the path that is exact for any g
        g = layers.kda_gate(low_rank("f"), kda_heads, form="softplus",
                            name="%s_%s_kda_decay" % (p, lp))
        # the write strength in float32, in (0, 2): the transition
        # I - beta k k^T then has an eigenvalue in (-1, 1)
        beta = layers.scale(layers.sigmoid(layers.cast(
            fc(u, kda_heads, lp + "_kda_beta"), "float32")), scale=2.0)
        o = layers.kda_scan(q, k, branch("v"), g, beta,
                            chunk_size=config.get("kda_chunk_size", 64),
                            block_chunks=config.get("kda_block_chunks", 4),
                            name="%s_%s_kda" % (p, lp))
        o = layers.head_gated_rms_norm(
            o, low_rank("g"), eps, n_head=kda_heads,
            name="%s_%s_kda_norm" % (p, lp))
        return fc(o, c, lp + "_kda_o")

    def swiglu_ffn(u, width, lp):
        act = layers.swiglu(fc(u, width, lp + "_gate"),
                            fc(u, width, lp + "_up"))
        return fc(act, c, lp + "_down")

    def expert_ffn(u, lp):
        idx, gate = layers.moe_route(
            u, config["n_routed_experts_published"],
            config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp), scoring_func="sigmoid")
        routed = layers.moe_experts(
            u, idx, gate, held, config["moe_intermediate_size"],
            param_attr=init, name="%s_%s_experts" % (p, lp))
        shared = swiglu_ffn(u, config["moe_intermediate_size"]
                            * config["n_shared_experts"], lp + "_shared")
        return layers.elementwise_add(shared, routed)

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    x = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    checkpoints = []
    for i, kind in enumerate(layer_kinds(config)):
        lp = "l%d" % i
        with name_scope("pt_solar_" + kind):
            u = norm(x, lp + "_mixer_norm")
            x = layers.elementwise_add(
                x, gqa(u, lp) if kind == "gqa" else kda(u, lp))
        with name_scope("pt_solar_ffn"):
            x = layers.elementwise_add(
                x, expert_ffn(norm(x, lp + "_ffn_norm"), lp))
        checkpoints.append(x)
    with name_scope("pt_solar_head"):
        logits = fc(norm(x, "final_norm"), config["vocab_size"], "head")
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

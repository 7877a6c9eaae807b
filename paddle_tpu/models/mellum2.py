"""Mellum2-12B-A2.5B (model_type `mellum`), built from a
`config.json`-style dict as ONE expert-parallel rank holds it: a stack
whose `layer_types` says, layer by layer, whether attention is causal
over a sliding window of `sliding_window` keys ("sliding_attention") or
over everything ("full_attention"), each kind turning q and k by its
own entry of `rope_parameters` (default rotary in the window layers,
YaRN in the full ones), and whose every layer ends in the routed
experts in `held_experts`, chosen by a softmax router over ALL
`num_experts_published` experts.  docs/MELLUM2_BLOCK.md writes the
equations out; benchmarks/reference/mellum2.py is the plain float32
reference of the same equations.

    h <- h + Attention_l(RMSNorm(h)),  h <- h + Experts_l(RMSNorm(h))
    logits = RMSNorm(h_L) W_head                         (untied)

Attention: q at `num_attention_heads`, k and v at
`num_key_value_heads` heads of `head_dim`, no bias, no norm on q or k;
q and k turned over the whole head (split halves) at the layer kind's
frequencies, cos and sin times its `attention_factor`; softmax at
head_dim^-1/2 over the keys j <= i and, in a window layer, j > i -
sliding_window; token-major end to end, K and V read in place by the
flash kernels.  The FIRST model builder whose attention layers differ
by kind in mask, rotary parameters and kernel geometry: the window
layers' kernels walk the band alone (`layers.flash_attention(window=)`),
at blocks the kernels choose.

`kept_layers` (this repo's key; default every layer) lists the
published layers that are built, in order: a layer's kind is
`layer_types[its published index]`.

As a Fluid trainer uses it:

    model = mellum2_model(config, seq_len=16384)
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

KINDS = ("sliding_attention", "full_attention")

# (key, the one value that is built, what another value would ask for)
_UNBUILT = (
    ("attention_bias", False, "a bias on the attention projections"),
    ("use_qk_norm", False, "a norm on q and k"),
    ("qk_norm", False, "a norm on q and k"),
    ("n_shared_experts", 0, "a shared expert"),
    ("num_shared_experts", 0, "a shared expert"),
    ("tie_word_embeddings", False, "a tied head"),
    ("hidden_act", "silu", "another activation in the experts"),
    ("use_sliding_window", True, "window layers without their window"),
    ("max_window_layers", 0, "a depth from which layer_types is not "
     "what decides a layer's window"),
    ("num_nextn_predict_layers", 0, "a prediction (MTP) head"),
)


def layer_kinds(config):
    """"sliding_attention" or "full_attention" for each layer that is
    built: the published layers in `kept_layers` (default 0 ..
    num_hidden_layers - 1), each the kind `layer_types` gives its
    published index."""
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    kept = list(kept)
    if len(kept) != config["num_hidden_layers"] \
            or kept != sorted(set(kept)) \
            or kept[0] < 0 or kept[-1] >= len(config["layer_types"]):
        raise ValueError(
            "mellum2: kept_layers %r are not num_hidden_layers = %d "
            "rising indices into %d layer_types"
            % (kept, config["num_hidden_layers"],
               len(config["layer_types"])))
    kinds = [config["layer_types"][i] for i in kept]
    for kind in kinds:
        if kind not in KINDS:
            raise NotImplementedError(
                "mellum2_model: layer_types gives %r" % (kind,))
    sparse = config.get("mlp_layer_types")
    if sparse is not None and {sparse[i] for i in kept} != {"sparse"}:
        raise NotImplementedError(
            "mellum2_model: mlp_layer_types %r of the kept layers (a "
            "dense feed-forward)" % ([sparse[i] for i in kept],))
    return kinds


def rotary_of(config, kind):
    """`layers.rotary_embedding`'s keywords for a layer of `kind`, from
    `rope_parameters[kind]`: the default embedding, or YaRN's scaled
    frequencies with cos and sin times `attention_factor`."""
    rope = config["rope_parameters"][kind]
    kind_of = rope.get("rope_type", "default")
    if kind_of == "default":
        return {"theta": rope["rope_theta"]}
    if kind_of != "yarn":
        raise NotImplementedError(
            "mellum2_model: rope_parameters[%r].rope_type %r"
            % (kind, kind_of))
    return {"theta": rope["rope_theta"], "factor": rope["factor"],
            "original_max_position":
            rope["original_max_position_embeddings"],
            "beta_fast": rope["beta_fast"], "beta_slow": rope["beta_slow"],
            "mscale": rope["attention_factor"]}


def mellum2_model(config, seq_len, param_prefix="mellum2"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss (mean
    cross-entropy over all positions) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError("mellum2_model: %s %r (%s)"
                                      % (key, config[key], what))
    kinds = layer_kinds(config)
    c, eps = config["hidden_size"], config["rms_norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = config["head_dim"]
    rotary = {kind: rotary_of(config, kind) for kind in set(kinds)}
    held = list(config.get("held_experts")
                or range(config["num_experts"]))
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                              initializer=init))

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name))

    def turned(x, n, kind):
        # the projection as it comes: n heads side by side
        return layers.rotary_embedding(x, pairing="halves", n_head=n,
                                       **rotary[kind])

    def attention(u, lp, kind):
        # k and v at num_key_value_heads heads: the kernels read a
        # query head's KV head in place; the window is the kernels'
        # band, and so are the blocks they walk it in
        o = layers.flash_attention(
            turned(fc(u, heads * d, lp + "_q"), heads, kind),
            turned(fc(u, kv_heads * d, lp + "_k"), kv_heads, kind),
            fc(u, kv_heads * d, lp + "_v"), causal=True, n_head=heads,
            n_kv_head=kv_heads,
            window=config["sliding_window"]
            if kind == "sliding_attention" else None)
        return fc(o, c, lp + "_o")

    def expert_ffn(u, lp):
        idx, gate = layers.moe_route(
            u, config.get("num_experts_published", config["num_experts"]),
            config["num_experts_per_tok"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp), scoring_func="softmax")
        return layers.moe_experts(
            u, idx, gate, held, config["moe_intermediate_size"],
            param_attr=init, name="%s_%s_experts" % (p, lp))

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    x = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    checkpoints = []
    for i, kind in enumerate(kinds):
        lp = "l%d" % i
        with name_scope("pt_mellum2_" + ("window_attention"
                                         if kind == "sliding_attention"
                                         else "full_attention")):
            x = layers.elementwise_add(
                x, attention(norm(x, lp + "_attn_norm"), lp, kind))
        with name_scope("pt_mellum2_ffn"):
            x = layers.elementwise_add(
                x, expert_ffn(norm(x, lp + "_ffn_norm"), lp))
        checkpoints.append(x)
    with name_scope("pt_mellum2_head"):
        logits = fc(norm(x, "final_norm"), config["vocab_size"], "head")
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

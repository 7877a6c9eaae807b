"""LFM2-24B-A2B (model_type `lfm2_moe`), built from a `config.json`-style
dict: a stack whose `layer_types` says, layer by layer, whether the
mixer is a doubly gated short convolution ("conv") or grouped-query
attention with an RMS norm a head on q and k and a rotary embedding
("full_attention"), and whose `num_dense_layers` says how many leading
layers end in a dense SwiGLU; every layer after them ends in the routed
experts in `held_experts`, chosen by a bias-selected sigmoid router
over ALL `num_experts_published` experts.  docs/LFM2_BLOCK.md writes
the equations out; benchmarks/reference/lfm2.py is the plain float32
reference of the same equations.

    h <- h + Mixer_l(RMSNorm(h)),  h <- h + FFN_l(RMSNorm(h))
    logits = RMSNorm(h_L) E^T                            (E tied)

The conv mixer (`conv_L_cache` taps, no bias, no activation):
[B | C | x] = u W_in (ONE projection, 3 x hidden wide), y = (C *
conv(B * x)) W_out: layers.gated_short_conv reads the thirds in place.
Attention: q at `num_attention_heads`, k and v at `num_key_value_heads`
heads of hidden / heads, no bias; q and k normed a head with one
learned scale of the head size each, then turned by the rotary
embedding over the whole head (split halves); causal softmax at
head_dim^-1/2; token-major end to end, K and V read in place by the
flash kernels.

`kept_layers` (this repo's key; default every layer) lists the
published layers that are built, in order: a layer's kind is
`layer_types[its published index]`, and the first `num_dense_layers`
of the kept layers are the dense ones.

As a Fluid trainer uses it:

    model = lfm2_model(config, seq_len=8192)
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
    ("conv_bias", False, "a bias on the convolution and its projections"),
    ("use_expert_bias", True, "a router without its selection bias"),
)

# what lfm2_moe's modelling code adds to the sum of a token's selected
# scores before it divides by it
ROUTER_NORM_EPS = 1e-6


def layer_kinds(config):
    """"conv" or "full_attention" for each layer that is built: the
    published layers in `kept_layers` (default 0 .. num_hidden_layers -
    1), each the kind `layer_types` gives its published index."""
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    kept = list(kept)
    if len(kept) != config["num_hidden_layers"] \
            or kept != sorted(set(kept)) \
            or kept[-1] >= len(config["layer_types"]):
        raise ValueError(
            "lfm2: kept_layers %r are not num_hidden_layers = %d rising "
            "indices into %d layer_types"
            % (kept, config["num_hidden_layers"],
               len(config["layer_types"])))
    return [config["layer_types"][i] for i in kept]


def lfm2_model(config, seq_len, param_prefix="lfm2"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss (mean
    cross-entropy over all positions) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError("lfm2_model: %s %r (%s)"
                                      % (key, config[key], what))
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise NotImplementedError("lfm2_model: rope_type %r"
                                  % (rope["rope_type"],))
    kinds = layer_kinds(config)
    c, eps = config["hidden_size"], config["norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = c // heads
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

    def conv(u, lp):
        y = layers.gated_short_conv(
            fc(u, 3 * c, lp + "_conv_in"), config["conv_L_cache"],
            name="%s_%s_conv" % (p, lp))
        return fc(y, c, lp + "_conv_out")

    def head_norm_rotary(x, n, name):
        x = layers.head_gated_rms_norm(
            x, None, eps, n_head=n, name="%s_%s" % (p, name))
        return layers.rotary_embedding(x, theta=rope["rope_theta"],
                                       pairing="halves", n_head=n)

    def attention(u, lp):
        # k and v at num_key_value_heads heads: the kernels read a
        # query head's KV head in place (grouped-query attention)
        o = layers.flash_attention(
            head_norm_rotary(fc(u, heads * d, lp + "_q"), heads,
                             lp + "_q_norm"),
            head_norm_rotary(fc(u, kv_heads * d, lp + "_k"), kv_heads,
                             lp + "_k_norm"),
            fc(u, kv_heads * d, lp + "_v"), causal=True, n_head=heads,
            n_kv_head=kv_heads)
        return fc(o, c, lp + "_o")

    def swiglu_ffn(u, width, lp):
        act = layers.swiglu(fc(u, width, lp + "_gate"),
                            fc(u, width, lp + "_up"))
        return fc(act, c, lp + "_down")

    def expert_ffn(u, lp):
        idx, gate = layers.moe_route(
            u, config["num_experts_published"],
            config["num_experts_per_tok"],
            routed_scaling_factor=config["routed_scaling_factor"],
            norm_topk_prob=config["norm_topk_prob"], param_attr=init,
            name="%s_%s_router" % (p, lp), scoring_func="sigmoid",
            norm_topk_eps=ROUTER_NORM_EPS)
        return layers.moe_experts(
            u, idx, gate, held, config["moe_intermediate_size"],
            param_attr=init, name="%s_%s_experts" % (p, lp))

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    x = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    table = x.block.program.global_block().var(p + "_emb.w")
    checkpoints = []
    for i, kind in enumerate(kinds):
        lp = "l%d" % i
        if kind not in ("conv", "full_attention"):
            raise NotImplementedError(
                "lfm2_model: layer_types gives %r" % (kind,))
        with name_scope("pt_lfm2_" + ("conv" if kind == "conv"
                                      else "attention")):
            u = norm(x, lp + "_operator_norm")
            x = layers.elementwise_add(
                x, conv(u, lp) if kind == "conv" else attention(u, lp))
        with name_scope("pt_lfm2_ffn"):
            u = norm(x, lp + "_ffn_norm")
            if i < config["num_dense_layers"]:
                y = swiglu_ffn(u, config["intermediate_size"], lp)
            else:
                y = expert_ffn(u, lp)
            x = layers.elementwise_add(x, y)
        checkpoints.append(x)
    with name_scope("pt_lfm2_head"):
        # the tied matrix's second reader
        logits = layers.matmul(norm(x, "final_norm"), table,
                               transpose_y=True)
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

"""A Granite 4.0-H decoder (model_type `granitemoehybrid`), built from a
`config.json`-style dict: a stack whose `layer_types` says, layer by
layer, whether the mixer is a Mamba-2 state-space mixer ("mamba") or
grouped-query attention without positions ("attention"); every layer
ends in one SwiGLU (`shared_intermediate_size`; with `num_local_experts`
0 there is no expert), and Granite's four multipliers scale the
embedding, each residual branch, the attention scores and the logits.
docs/GRANITE4_BLOCK.md writes the equations out;
benchmarks/reference/granite_hybrid.py is the plain float32 reference
of the same equations.

    h0 = embedding_multiplier E[ids]
    h <- h + residual_multiplier Mixer(RMSNorm(h))
    h <- h + residual_multiplier SwiGLU(RMSNorm(h))
    logits = RMSNorm(h_L) E^T / logits_scaling          (E tied)

The Mamba mixer (H heads of P, state N, one group, a causal conv of
`mamba_d_conv` taps over x, B and C): [z | xBC | dt] = W_in u (held as
three matrices), xBC <- silu(conv(xBC) + b), the scan
(layers.mamba2_scan), y <- RMSNorm(y silu(z)) w, out = W_out y.
Attention: q at `num_attention_heads`, k and v at
`num_key_value_heads` heads, no bias, no rotary or other position term
(`position_embedding_type` "nope"), causal softmax of q.k
`attention_multiplier`; token-major end to end, K and V read in place
by the flash kernels.

As a Fluid trainer uses it:

    model = granite_hybrid_model(config, seq_len=8192)
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

_UNBUILT = (
    ("num_local_experts", 0, "routed experts"),
    ("mamba_n_groups", 1, "more than one B/C group"),
    ("mamba_proj_bias", False, "a bias on the mixer's projections"),
    ("attention_bias", False, "a bias on attention's projections"),
)


def granite_hybrid_model(config, seq_len, param_prefix="granite"):
    """Builds the training program into the default programs.  Returns
    src_ids, tgt_label ([B, T, 1] int64 feeds), logits, loss (mean
    cross-entropy over all positions) and `checkpoints` for
    RecomputeOptimizer._set_checkpoints: the residual stream after each
    layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError(
                "granite_hybrid_model: %s %r (%s)"
                % (key, config[key], what))
    if config.get("position_embedding_type", "nope") != "nope":
        raise NotImplementedError(
            "granite_hybrid_model: position_embedding_type %r"
            % (config["position_embedding_type"],))
    c, eps = config["hidden_size"], config["rms_norm_eps"]
    heads, kv_heads = (config["num_attention_heads"],
                       config["num_key_value_heads"])
    d = c // heads
    mh, mp, mn = (config["mamba_n_heads"], config["mamba_d_head"],
                  config["mamba_d_state"])
    width = config["shared_intermediate_size"]
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    if len(kinds) != config["num_hidden_layers"]:
        raise ValueError("granite_hybrid_model: %d layer_types for %d "
                         "layers" % (len(kinds),
                                     config["num_hidden_layers"]))
    res = float(config["residual_multiplier"])
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                              initializer=init))

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name))

    def mamba(u, lp):
        z = fc(u, mh * mp, lp + "_in_z")
        xbc = layers.causal_conv1d(
            fc(u, mh * mp + 2 * mn, lp + "_in_xbc"),
            config["mamba_d_conv"], activation="silu",
            bias_attr=None if config["mamba_conv_bias"] else False,
            name="%s_%s_conv" % (p, lp))
        x, b, cc = layers.split(xbc, [mh * mp, mn, mn], dim=-1)
        y = layers.mamba2_scan(
            x, fc(u, mh, lp + "_in_dt"), b, cc,
            chunk_size=config["mamba_chunk_size"],
            name="%s_%s_ssm" % (p, lp))
        y = layers.gated_rms_norm(y, z, eps,
                                  name="%s_%s_mixer_norm" % (p, lp))
        return fc(y, c, lp + "_out")

    def attention(u, lp):
        o = layers.flash_attention(
            fc(u, heads * d, lp + "_q"), fc(u, kv_heads * d, lp + "_k"),
            fc(u, kv_heads * d, lp + "_v"), causal=True,
            scale=config["attention_multiplier"], n_head=heads,
            n_kv_head=kv_heads)
        return fc(o, c, lp + "_o")

    def branch(x, y):
        return layers.elementwise_add(x, layers.scale(y, scale=res))

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, 1], dtype="int64")
    emb = layers.embedding(
        src, [config["vocab_size"], c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    x = layers.scale(emb, scale=float(config["embedding_multiplier"]))
    table = emb.block.program.global_block().var(p + "_emb.w")
    checkpoints = []
    for i, kind in enumerate(kinds):
        lp = "l%d" % i
        if kind not in ("mamba", "attention"):
            raise NotImplementedError(
                "granite_hybrid_model: layer_types[%d] = %r" % (i, kind))
        with name_scope("pt_granite_" + kind):
            u = norm(x, lp + "_norm1")
            x = branch(x, mamba(u, lp) if kind == "mamba"
                       else attention(u, lp))
        with name_scope("pt_granite_ffn"):
            u = norm(x, lp + "_norm2")
            f = fc(layers.swiglu(fc(u, width, lp + "_gate"),
                                 fc(u, width, lp + "_up")), c,
                   lp + "_down")
            x = branch(x, f)
        checkpoints.append(x)
    with name_scope("pt_granite_head"):
        # the tied matrix's second reader
        logits = layers.matmul(
            norm(x, "final_norm"), table, transpose_y=True,
            alpha=1.0 / float(config["logits_scaling"]))
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

"""EvaByte (model_type `evabyte`, attention_class `eva`), built from a
`config.json`-style dict: a byte-level decoder whose every layer mixes
tokens by EVA attention (Zheng et al., arXiv:2302.04542, in the
deterministic form of the EvaByte release) and whose head predicts the
next `num_pred_heads` bytes of ONE stream position.
docs/EVABYTE_BLOCK.md writes the equations out;
benchmarks/reference/evabyte.py is the plain float32 reference of the
same equations.

    h <- h + EVA_l(n(h)) W_o,  h <- h + SwiGLU_l(n(h))
    logits = n(h_L) W_head, [T, num_pred_heads, vocab]   (untied)

n(x) = x / rms(x) * (1 + w) (`norm_add_unit_offset`).  EVA: q, k, v at
`num_attention_heads` heads, no bias, q and k turned over the whole
head (split halves, `rope_theta`); a query sees the tokens of its own
`window_size`-aligned window up to itself and, of every earlier window,
one learned summary a chunk of `chunk_size` tokens, in one softmax
(`layers.eva_attention`).  Token-major end to end.  Head p at position
t predicts byte t + 1 + p: the label feed is [T, num_pred_heads, 1] and
the loss the mean of the T x num_pred_heads cross-entropies.  The FIRST
model builder with several heads over one position (ouro's four are
over four passes).

As a Fluid trainer uses it:

    model = evabyte_model(config, seq_len=8192)
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
    ("attention_class", "eva", "another attention"),
    ("attention_bias", False, "a bias on the attention projections"),
    ("tie_word_embeddings", False, "a tied head"),
    ("hidden_act", "silu", "another activation in the feed-forward"),
    ("rope_scaling", None, "scaled rotary frequencies"),
    ("num_chunks", None, "a fixed number of chunks in place of a "
     "chunk size"),
    ("fp32_ln", False, "norms whose OUTPUT is float32 under AMP"),
)


def evabyte_model(config, seq_len, param_prefix="evabyte"):
    """Builds the training program into the default programs.  Returns
    src_ids ([B, T, 1] int64), tgt_label ([B, T, num_pred_heads, 1]
    int64: byte t + 1 + p), logits [B, T, num_pred_heads, vocab], loss
    and `checkpoints` for RecomputeOptimizer._set_checkpoints: the
    residual stream after each layer."""
    for key, built, what in _UNBUILT:
        if config.get(key, built) != built:
            raise NotImplementedError("evabyte_model: %s %r (%s)"
                                      % (key, config[key], what))
    c, heads = config["hidden_size"], config["num_attention_heads"]
    if config.get("num_key_value_heads", heads) != heads or c % heads:
        raise NotImplementedError(
            "evabyte_model: num_key_value_heads %r for %d query heads "
            "of %d channels (EVA's summaries are a head's own)"
            % (config.get("num_key_value_heads"), heads, c))
    eps, offset = config["rms_norm_eps"], config["norm_add_unit_offset"]
    n_pred, vocab = config["num_pred_heads"], config["vocab_size"]
    init = Normal(0.0, config.get("initializer_range", 0.02), fast=True)
    p = param_prefix

    def fc(x, size, name):
        return layers.fc(x, size, num_flatten_dims=2, bias_attr=False,
                         param_attr=ParamAttr(name="%s_%s.w" % (p, name),
                                              initializer=init))

    def norm(x, name):
        return layers.rms_norm(x, eps, name="%s_%s" % (p, name),
                               unit_offset=offset)

    def turned(x):
        # the projection as it comes: the heads side by side
        return layers.rotary_embedding(x, theta=config["rope_theta"],
                                       pairing="halves", n_head=heads)

    def attention(u, lp):
        o = layers.eva_attention(
            turned(fc(u, c, lp + "_q")), turned(fc(u, c, lp + "_k")),
            fc(u, c, lp + "_v"), n_head=heads,
            window=config["window_size"], chunk=config["chunk_size"],
            name="%s_%s_eva" % (p, lp))
        return fc(o, c, lp + "_o")

    def ffn(u, lp):
        width = config["intermediate_size"]
        return fc(layers.swiglu(fc(u, width, lp + "_gate"),
                                fc(u, width, lp + "_up")), c, lp + "_down")

    src = layers.data("src_ids", shape=[seq_len, 1], dtype="int64")
    label = layers.data("tgt_label", shape=[seq_len, n_pred, 1],
                        dtype="int64")
    x = layers.embedding(
        src, [vocab, c],
        param_attr=ParamAttr(name=p + "_emb.w", initializer=init))
    checkpoints = []
    for i in range(config["num_hidden_layers"]):
        lp = "l%d" % i
        with name_scope("pt_evabyte_eva_attention"):
            x = layers.elementwise_add(
                x, attention(norm(x, lp + "_attn_norm"), lp))
        with name_scope("pt_evabyte_ffn"):
            x = layers.elementwise_add(
                x, ffn(norm(x, lp + "_ffn_norm"), lp))
        checkpoints.append(x)
    with name_scope("pt_evabyte_head"):
        # the num_pred_heads heads are ONE matrix, [c, n_pred * vocab]
        logits = layers.reshape(
            fc(norm(x, "final_norm"), n_pred * vocab, "head"),
            [-1, seq_len, n_pred, vocab])
        loss = layers.mean(layers.softmax_with_cross_entropy(logits,
                                                             label))
    return {"src_ids": src, "tgt_label": label, "logits": logits,
            "loss": loss, "checkpoints": checkpoints}

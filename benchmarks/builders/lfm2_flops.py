"""Operations and bytes of the `lfm2_moe` block (doubly gated
short-convolution layers and grouped-query attention layers by
`layer_types`, a dense SwiGLU in the leading layers and the chip's
share of the routed experts after them, a tied head), from shapes
alone: what benchmarks/flops.py is to the 2017 Transformer.  Loaded by
builders/lfm2.py; checked against hand-worked values in
tests/test_lfm2.py.  The grouped matmuls' operations and bytes are
builders/xing4_flops.py's `gmm_step`: the same kernels.  Work the
program repeats (a recompute segment's replay, scores the flash
backward forms again, rows padded to a tile, the other head's zeroed
lanes of a 128-lane block) is never counted; nor are the norms and the
rotary embedding.  A token's FLOPs count only the parameters that
multiply, so not the convolution's taps and gates (elementwise: 7
operations a channel forward); `gated_conv_step` counts those for the
kernels' roofline, where the bytes bind.
"""

from __future__ import annotations


def layer_kinds(config):
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    return [config["layer_types"][i] for i in kept]


def _published_experts(config):
    return config.get("num_experts_published", config["num_experts"])


def layer_params(config, kind, dense):
    """All parameters of one layer of `kind` ("conv" |
    "full_attention") with a dense or an expert feed-forward, by part:
    {"mixer": the matrices that multiply a token's activations, "ffn":
    the dense SwiGLU's three or every HELD expert's three, "router",
    "other": the conv filter, the norm scales}.  The router's selection
    bias is persistable state and no parameter."""
    c = config["hidden_size"]
    if kind == "conv":
        mixer, other = 3 * c * c + c * c, c * config["conv_L_cache"]
    else:
        d = c // config["num_attention_heads"]
        kv = config["num_key_value_heads"] * d
        mixer, other = 2 * c * c + 2 * c * kv, 2 * d
    if dense:
        ffn, router = 3 * c * config["intermediate_size"], 0
    else:
        ffn = config["num_experts"] * 3 * c * config["moe_intermediate_size"]
        router = c * _published_experts(config)
    return {"mixer": mixer, "ffn": ffn, "router": router,
            "other": other + 2 * c}


def n_params(config):
    """Every parameter of the configuration as run: the layers, the
    tied embedding/head matrix once, the final norm."""
    c = config["hidden_size"]
    return sum(sum(layer_params(config, k,
                                i < config["num_dense_layers"]).values())
               for i, k in enumerate(layer_kinds(config))) \
        + config["vocab_size"] * c + c


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply (the tied matrix
    as the head; the embedding is a gather; the routed experts at the
    share a token meets HERE in expectation, num_experts_per_tok x held
    / published experts), and causal attention, 2 T H d a token and
    layer (half the square of QK^T and of PV, 2 T H d each over the
    whole of it)."""
    kinds = layer_kinds(config)
    c = config["hidden_size"]
    dense = min(config["num_dense_layers"], len(kinds))
    conv, attn = kinds.count("conv"), kinds.count("full_attention")
    met = config["num_experts_per_tok"] * config["num_experts"] \
        / _published_experts(config)
    return {
        "dense_ffn": 2.0 * dense * 3 * c * config["intermediate_size"],
        "conv_proj": 2.0 * conv
        * layer_params(config, "conv", True)["mixer"],
        "attention_proj": 2.0 * attn
        * layer_params(config, "full_attention", True)["mixer"],
        "routed_experts": 2.0 * (len(kinds) - dense) * met * 3 * c
        * config["moe_intermediate_size"],
        "router": 2.0 * (len(kinds) - dense) * c
        * _published_experts(config),
        "head": 2.0 * c * config["vocab_size"],
        "flash": 2.0 * attn * seq_len * c,
    }


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def gqa_flash_step(config, batch, seq_len, flops, bytes_per_el=2):
    """(flops, bytes) of the flash kernels of one train step, all
    attention layers: causal attention at the QUERY heads' count (half
    the square, backward twice the forward: flops.py's), and each
    operand moved once with K and V read once a KV head: forward q, o
    at H heads and k, v at H_kv; backward q, o, dO, dq at H and k, v,
    dk, dv at H_kv."""
    attn = layer_kinds(config).count("full_attention")
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    d = config["hidden_size"] // heads
    args = (batch, heads, seq_len, seq_len, d)
    ops = flops.flash_attention_flops(*args, causal=True) \
        + flops.flash_attention_flops(*args, causal=True, backward=True)
    row = batch * seq_len * d * bytes_per_el
    nbytes = (2 * heads + 2 * kv) * row + (4 * heads + 4 * kv) * row
    return attn * ops, float(attn * nbytes)


def gated_conv_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the gated convolution's kernels of one train
    step, all conv layers.  Bytes, each [T, C] array moved once:
    forward reads the thirds B, C and x of the projection and writes y
    (4); backward reads them and dy and writes dB, dC and dx (7); the
    filter and its gradient are a few KB.  Operations a channel and
    token, K taps: forward B x (1), the taps (2 K - 1), the output gate
    (1); backward B x and the taps again, dy C and dy c (2), the taps
    backward (2 K - 1), dB and dx (2), dW (2 K).  The forward that a
    recompute segment runs again is not counted, so it shows as lost
    share.  The bytes bind: 369 MB and 0.06 GFLOP a layer at 8,192
    tokens."""
    conv = layer_kinds(config).count("conv")
    k, c = config["conv_L_cache"], config["hidden_size"]
    els = batch * seq_len * c
    fwd, bwd = 2 * k + 1, 1 + (2 * k - 1) + 2 + (2 * k - 1) + 2 + 2 * k
    return float(conv * els * (fwd + bwd)), \
        float(conv * els * (4 + 7) * bytes_per_el)

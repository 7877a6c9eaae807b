"""Operations and bytes of the `solar-open2-250b` block as one tensor-
and expert-parallel rank holds it (gated grouped-KV attention layers
without positions and Kimi Delta Attention layers over a shared expert
plus the chip's share of the routed experts), from shapes alone.
Loaded by builders/solar_open2.py; checked against hand-worked values
in tests/test_solar_open2.py.  The grouped matmuls' operations and
bytes are builders/xing4_flops.py's `gmm_step`: the same kernels.  The
KDA kernels' count is the WY form's, the count builders/ling3_flops.py
`kda_step` makes at this block's heads and tokens: the extra products
of the path that is exact for an unbounded decay (four more score
products a chunk, forward and backward) are NOT in it, so the share of
the roofline says what that exactness costs.  Work the program repeats
(recomputed segments, the chunk's forward that the KDA backward kernel
runs again from a block's saved state, scores the flash backward forms
again, rows padded to a tile, the masked half of a triangular product)
is never counted, nor are the depthwise convolutions, the norms and
the gates (elementwise: 4 taps a channel).
"""

from __future__ import annotations


def layer_kinds(config):
    gqa = set(config["gqa_layers"])
    return ["gqa" if i in gqa else "kda"
            for i in range(config["num_hidden_layers"])]


def kda_heads(config):
    """The KDA heads held here."""
    return config.get("kda_heads_held",
                      config["linear_attn_config"]["num_heads"])


def parameters(config):
    """Every parameter the program holds, by part (the router's
    selection bias is persistable and no parameter: not counted)."""
    c, d = config["hidden_size"], config["head_dim"]
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    lin = config["linear_attn_config"]
    kh, kd, taps = kda_heads(config), lin["head_dim"], \
        lin["short_conv_kernel_size"]
    kinds = layer_kinds(config)
    held = len(config.get("held_experts")
               or range(config["n_routed_experts"]))
    expert = 3 * c * config["moe_intermediate_size"]
    # the low-rank gates' rank is the head size
    kda = (4 * c * kh * kd + 2 * (c * kd + kd * kh * kd) + c * kh
           + 3 * kh * kd * taps + kh + kh * kd + kd)
    gqa = 3 * c * heads * d + 2 * c * kv * d
    return {
        "kda_mixer": kinds.count("kda") * kda,
        "gqa_mixer": kinds.count("gqa") * gqa,
        "shared_expert": len(kinds) * config["n_shared_experts"] * expert,
        "router": len(kinds) * c * config["n_routed_experts_published"],
        "routed_experts": len(kinds) * held * expert,
        "norms": (2 * len(kinds) + 1) * c,
        "embedding": config["vocab_size"] * c,
        "head": config["vocab_size"] * c,
    }


def matmul_params(config):
    """Parameters that multiply a token's activations in one forward
    pass, by part, each the total over the layers of the configuration
    as run.  The routed experts count the expected share a token meets
    HERE: num_experts_per_tok x held / published experts.  The
    embedding is a gather."""
    c, d = config["hidden_size"], config["head_dim"]
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    kh, kd = kda_heads(config), config["linear_attn_config"]["head_dim"]
    kinds = layer_kinds(config)
    published = config["n_routed_experts_published"]
    held = len(config.get("held_experts")
               or range(config["n_routed_experts"]))
    expert = 3 * c * config["moe_intermediate_size"]
    met = config["num_experts_per_tok"] * held / published
    return {
        # q, k, v and the output; the two low-rank gates; beta
        "kda_proj": kinds.count("kda") * (
            4 * c * kh * kd + 2 * (c * kd + kd * kh * kd) + c * kh),
        # q, the gate and the output at the query heads; k and v
        "gqa_proj": kinds.count("gqa") * (3 * c * heads * d
                                          + 2 * c * kv * d),
        "shared_expert": len(kinds) * config["n_shared_experts"] * expert,
        "routed_experts": len(kinds) * met * expert,
        "router": len(kinds) * c * published,
        "head": c * config["vocab_size"],
    }


def kda_flops_per_token(config, backward=False):
    """Operations a token of ONE KDA layer costs in the chunked WY form
    (ops/pallas_kda.py), the heads held; C the chunk, D the head size,
    a triangular product counted as half its square: forward 5 C D +
    6 D^2 + 2 C^2 / 3, backward 10 C D + 12 D^2 + 2 C^2 a head
    (builders/ling3_flops.py kda_flops_per_token has the terms)."""
    c = config.get("kda_chunk_size", 64)
    d = config["linear_attn_config"]["head_dim"]
    per_head = 10.0 * c * d + 12.0 * d * d + 2.0 * c * c if backward \
        else 5.0 * c * d + 6.0 * d * d + 2.0 * c * c / 3.0
    return kda_heads(config) * per_head


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply, the KDA layers'
    chunk products, and causal attention, T H 2 d a layer and token
    (half the square of QK^T and of PV)."""
    kinds = layer_kinds(config)
    parts = {k: 2.0 * v for k, v in matmul_params(config).items()}
    parts["flash"] = float(seq_len * config["num_attention_heads"]
                           * 2 * config["head_dim"] * kinds.count("gqa"))
    parts["kda"] = kinds.count("kda") * kda_flops_per_token(config)
    return parts


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward of everything but
    the KDA chunk products, whose backward is counted as its own."""
    parts = forward_flops_per_token(config, seq_len)
    kda_layers = layer_kinds(config).count("kda")
    return 3.0 * (sum(parts.values()) - parts["kda"]) + parts["kda"] \
        + kda_layers * kda_flops_per_token(config, backward=True)


def kda_head_chunks(config, batch, seq_len):
    """Chunks of the scan a step walks, all KDA layers and held heads:
    layers x B x heads x T / C.  Each is walked once forward and once
    backward; kda_head_chunk_us divides the two kernels' time by it."""
    return layer_kinds(config).count("kda") * batch * kda_heads(config) \
        * seq_len // config.get("kda_chunk_size", 64)


def kda_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the KDA kernels of one train step, all KDA
    layers.  FLOPs: kda_flops_per_token, forward and backward.  Bytes,
    each operand moved once: forward reads Q, K, V (bytes_per_el) and
    the log-decays G (float32) and beta (float32, a head), writes O and
    the block-start states (float32 D x D a head and block of
    kda_block_chunks chunks); backward reads Q, K, V, G, beta, dO and
    the states and writes dQ, dK, dV, dG (float32) and d beta.  The
    chunks' inverses (a residual written and read) and a replay by a
    recompute segment are not counted, as in ling3's count."""
    layers = layer_kinds(config).count("kda")
    h, d = kda_heads(config), config["linear_attn_config"]["head_dim"]
    block = config.get("kda_chunk_size", 64) \
        * config.get("kda_block_chunks", 4)
    tokens = batch * seq_len
    act = tokens * h * d * bytes_per_el            # one of Q, K, V, O, dO
    decay = tokens * h * d * 4.0                   # G or dG
    beta = tokens * h * 4.0
    states = (tokens / block) * h * d * d * 4.0
    fwd = 3 * act + decay + beta + act + states
    bwd = 3 * act + decay + beta + act + states + 3 * act + decay + beta
    flops = tokens * (kda_flops_per_token(config)
                      + kda_flops_per_token(config, backward=True))
    return layers * flops, float(layers * (fwd + bwd))


def gqa_flash_step(config, batch, seq_len, flops, bytes_per_el=2):
    """(flops, bytes) of the flash kernels of one train step, all
    attention layers: causal attention at the QUERY heads' count (half
    the square, backward twice the forward: flops.py's), and each
    operand moved once with K and V read once a KV head: forward q, o
    at H heads and k, v at H_kv; backward q, o, dO, dq at H and k, v,
    dk, dv at H_kv."""
    attn = layer_kinds(config).count("gqa")
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    d = config["head_dim"]
    args = (batch, heads, seq_len, seq_len, d)
    ops = flops.flash_attention_flops(*args, causal=True) \
        + flops.flash_attention_flops(*args, causal=True, backward=True)
    row = batch * seq_len * d * bytes_per_el
    nbytes = (2 * heads + 2 * kv) * row + (4 * heads + 4 * kv) * row
    return attn * ops, float(attn * nbytes)

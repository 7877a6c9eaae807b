"""Operations and bytes of the `granitemoehybrid` block (Mamba-2
state-space layers and grouped-query attention layers by
`layer_types`, one SwiGLU a layer, a tied head), from shapes alone:
what benchmarks/flops.py is to the 2017 Transformer.  Loaded by
builders/granite_hybrid.py; checked against hand-worked values in
tests/test_granite_hybrid.py.  Work the program repeats (a recompute
segment's replay, scores and decay matrices the backward kernels form
again, the other head's zeroed lanes of a 128-lane block) is never
counted.
"""

from __future__ import annotations


def layer_kinds(config):
    return list(config["layer_types"][:config["num_hidden_layers"]])


def layer_params(config, kind):
    """All parameters of one layer of `kind` ("mamba" | "attention"),
    by part: {"mixer": the matrices that multiply a token's
    activations, "ffn": SwiGLU's three, "other": conv filter and bias,
    A_log, D, dt_bias, norm scales}."""
    c = config["hidden_size"]
    ffn = 3 * c * config["shared_intermediate_size"]
    if kind == "attention":
        d = c // config["num_attention_heads"]
        kv = config["num_key_value_heads"] * d
        return {"mixer": 2 * c * c + 2 * c * kv, "ffn": ffn,
                "other": 2 * c}
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    inner, conv = h * p, h * p + 2 * config["mamba_n_groups"] * n
    taps = config["mamba_d_conv"] + (1 if config["mamba_conv_bias"] else 0)
    return {"mixer": c * (inner + conv + h) + inner * c, "ffn": ffn,
            "other": conv * taps + 3 * h + inner + 2 * c}


def n_params(config):
    """Every parameter of the configuration as run: the layers, the
    tied embedding/head matrix once, the final norm."""
    c = config["hidden_size"]
    return sum(sum(layer_params(config, k).values())
               for k in layer_kinds(config)) \
        + config["vocab_size"] * c + c


def scan_flops_per_token(config):
    """The chunked scan's forward products a token and layer, chunk L,
    one group: C B^T once a group (2 L N), and per head the masked
    product (2 L P), the chunk's state (2 P N) and the state's output
    (2 P N)."""
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    ln = config["mamba_chunk_size"]
    return 2.0 * ln * n * config["mamba_n_groups"] \
        + h * (2.0 * ln * p + 4.0 * p * n)


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply (the tied matrix
    as the head; the embedding is a gather), the scan's products, and
    causal attention, 2 T H d a token and layer (half the square of
    QK^T and of PV, 2 T H d each over the whole of it)."""
    kinds = layer_kinds(config)
    mamba, attn = kinds.count("mamba"), kinds.count("attention")
    c = config["hidden_size"]
    return {
        "ffn": 2.0 * len(kinds) * layer_params(config, "mamba")["ffn"],
        "mamba_proj": 2.0 * mamba * layer_params(config, "mamba")["mixer"],
        "attention_proj": 2.0 * attn
        * layer_params(config, "attention")["mixer"],
        "head": 2.0 * c * config["vocab_size"],
        "scan": mamba * scan_flops_per_token(config),
        "flash": 2.0 * attn * seq_len * c,
    }


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def ssd_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the scan kernels of one train step, all
    state-space layers.  FLOPs: the forward's products, and the
    backward counted as its own: per chunk and group C B^T again and
    the two products of its gradient (dG B, dG^T C); per head dy x^T,
    the masked product's transpose, and two products each for the
    chunk's state and the state's output (their operands' gradients):
    2 L N + 4 L N a group and 4 L P + 8 P N a head, a token.  Bytes:
    X, B, C, dt and Y moved once and the chunk-start states written
    once forward; X, B, C, dt, dY and the states read and dX, dB, dC,
    d dt written once backward.  A replay by a recompute segment is
    not counted (the program makes none)."""
    kinds = layer_kinds(config)
    mamba = kinds.count("mamba")
    h, p, n = (config["mamba_n_heads"], config["mamba_d_head"],
               config["mamba_d_state"])
    ln, g = config["mamba_chunk_size"], config["mamba_n_groups"]
    tokens = batch * seq_len
    fwd = scan_flops_per_token(config)
    bwd = 6.0 * ln * n * g + h * (4.0 * ln * p + 8.0 * p * n)
    states = 4.0 * (tokens / ln) * h * p * n          # float32
    acts = tokens * (h * p + 2 * g * n) * bytes_per_el
    steps = tokens * h * 4.0                          # dt, float32
    nbytes = (2 * tokens * h * p * bytes_per_el + 2 * tokens * g * n
              * bytes_per_el + steps + states) \
        + (2 * acts + 2 * tokens * h * p * bytes_per_el + 2 * steps
           + states)
    return mamba * tokens * (fwd + bwd), mamba * nbytes


def gqa_flash_step(config, batch, seq_len, flops, bytes_per_el=2):
    """(flops, bytes) of the flash kernels of one train step, all
    attention layers: causal attention at the QUERY heads' count (half
    the square, backward twice the forward: flops.py's), and each
    operand moved once with K and V read once a KV head: forward q, o
    at H heads and k, v at H_kv; backward q, o, dO, dq at H and k, v,
    dk, dv at H_kv."""
    attn = layer_kinds(config).count("attention")
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    d = config["hidden_size"] // heads
    args = (batch, heads, seq_len, seq_len, d)
    ops = flops.flash_attention_flops(*args, causal=True) \
        + flops.flash_attention_flops(*args, causal=True, backward=True)
    row = batch * seq_len * d * bytes_per_el
    nbytes = (2 * heads + 2 * kv) * row + (4 * heads + 4 * kv) * row
    return attn * ops, float(attn * nbytes)

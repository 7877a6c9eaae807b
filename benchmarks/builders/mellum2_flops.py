"""Operations and bytes of the `mellum` block (sliding-window and full
attention layers by `layer_types`, the chip's share of the routed
experts in every layer, an untied head), from shapes alone: what
benchmarks/flops.py is to the 2017 Transformer.  Loaded by
builders/mellum2.py; checked against hand-worked values and a
brute-force count of allowed pairs in tests/test_mellum2.py.  The
grouped matmuls' operations and bytes are builders/xing4_flops.py's
`gmm_step`: the same kernels.  Work the program repeats (a recompute
segment's replay, scores the flash backward forms again, rows padded to
a tile, the masked part of a block pair on the band's edges) is never
counted; nor are the norms and the rotary embedding.

Attention is counted by the pairs (query, key) the mask ALLOWS, whatever
implements it: a full layer's T (T + 1) / 2, a window layer's
sum_i min(i + 1, W), never the half square for a window layer.  A pair
costs 4 d operations a query head forward (q . k and p v, 2 d each)
and twice that backward.
"""

from __future__ import annotations

KINDS = ("sliding_attention", "full_attention")


def layer_kinds(config):
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    return [config["layer_types"][i] for i in kept]


def _published_experts(config):
    return config.get("num_experts_published", config["num_experts"])


def allowed_pairs(seq_len, window=0):
    """Pairs (i, j) of one sequence with j <= i and, with a window,
    j > i - window: sum_i min(i + 1, W) in closed form."""
    w = min(window, seq_len) if window else seq_len
    return w * (w + 1) // 2 + (seq_len - w) * w


def layer_pairs(config, kind, seq_len):
    return allowed_pairs(seq_len, config["sliding_window"]
                         if kind == "sliding_attention" else 0)


def layer_params(config):
    """All parameters of one layer (both kinds have the same), by part:
    {"mixer": W_q, W_k, W_v, W_o, "ffn": every HELD expert's three,
    "router", "other": the two norm scales}."""
    c, d = config["hidden_size"], config["head_dim"]
    q, kv = (config["num_attention_heads"] * d,
             config["num_key_value_heads"] * d)
    return {"mixer": 2 * c * q + 2 * c * kv,
            "ffn": config["num_experts"] * 3 * c
            * config["moe_intermediate_size"],
            "router": c * _published_experts(config), "other": 2 * c}


def n_params(config):
    """Every parameter of the configuration as run: the layers, the
    embedding and the untied head, the final norm."""
    c = config["hidden_size"]
    return len(layer_kinds(config)) * sum(layer_params(config).values()) \
        + 2 * config["vocab_size"] * c + c


def forward_flops_per_token(config, seq_len):
    """By part: 2 N over the parameters that multiply (the head; the
    embedding is a gather; the routed experts at the share a token
    meets HERE in expectation, num_experts_per_tok x held / published
    experts), and attention by its allowed pairs, 4 d a pair and query
    head, the window layers and the full ones apart."""
    kinds = layer_kinds(config)
    c, d = config["hidden_size"], config["head_dim"]
    heads = config["num_attention_heads"]
    params = layer_params(config)
    met = config["num_experts_per_tok"] * config["num_experts"] \
        / _published_experts(config)
    flash = {kind: sum(4.0 * d * heads * layer_pairs(config, k, seq_len)
                       for k in kinds if k == kind) / seq_len
             for kind in KINDS}
    return {
        "attention_proj": 2.0 * len(kinds) * params["mixer"],
        "routed_experts": 2.0 * len(kinds) * met * 3 * c
        * config["moe_intermediate_size"],
        "router": 2.0 * len(kinds) * params["router"],
        "head": 2.0 * c * config["vocab_size"],
        "window_flash": flash["sliding_attention"],
        "full_flash": flash["full_attention"],
    }


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def _flash_step(config, kind, batch, seq_len, bytes_per_el):
    """(flops, bytes) of the flash kernels of one train step over the
    layers of `kind`: the allowed pairs at the QUERY heads' count,
    backward twice the forward; each operand moved once with K and V
    once a KV head: forward q, o at H heads and k, v at H_kv; backward
    q, o, dO, dq at H and k, v, dk, dv at H_kv."""
    n = layer_kinds(config).count(kind)
    heads, kv = (config["num_attention_heads"],
                 config["num_key_value_heads"])
    d = config["head_dim"]
    ops = 3 * 4.0 * d * heads * batch * layer_pairs(config, kind, seq_len)
    row = batch * seq_len * d * bytes_per_el
    nbytes = (2 * heads + 2 * kv) * row + (4 * heads + 4 * kv) * row
    return float(n * ops), float(n * nbytes)


def window_flash_step(config, batch, seq_len, bytes_per_el=2):
    """The window layers' flash kernels (pt_flash_win_*): the band's
    pairs, sum_i min(i + 1, W) a sequence."""
    return _flash_step(config, "sliding_attention", batch, seq_len,
                       bytes_per_el)


def gqa_flash_step(config, batch, seq_len, bytes_per_el=2):
    """The FULL layers' flash kernels (pt_flash_fwd, pt_flash_bwd_*)
    alone: the window layers' calls carry other names and are
    `window_flash_step`'s."""
    return _flash_step(config, "full_attention", batch, seq_len,
                       bytes_per_el)

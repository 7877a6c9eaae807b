"""Operations and bytes of the `evabyte` block (EVA attention: a
window's tokens exactly, every earlier window as one summary a chunk,
one softmax over both; a dense SwiGLU; num_pred_heads heads of
vocab_size ids over one position), from shapes alone.  Loaded by
builders/evabyte.py; checked against hand-worked values in
tests/test_evabyte.py.  Work the program repeats (a recompute segment's
replay, the scores and the chunk softmaxes the backward kernels form
again) is never counted, and masked scores are not work: attention is
charged the pairs the windows ALLOW.
"""

from __future__ import annotations


def eva_pairs(config, seq_len):
    """(token pairs, chunk pairs) ONE sequence and head scores: query
    i sees the i mod W + 1 tokens of its own window up to itself and
    the W/c chunk keys of each of the i // W windows before it."""
    w, c = config["window_size"], config["chunk_size"]
    whole, rest = divmod(seq_len, w)
    tokens = whole * (w * (w + 1) // 2) + rest * (rest + 1) // 2
    # every query of window n sees n W/c chunk keys
    chunks = (w // c) * (w * (whole * (whole - 1) // 2) + rest * whole)
    return tokens, chunks


def layer_params(config):
    c = config["hidden_size"]
    return {"mixer": 4 * c * c,
            "ffn": 3 * c * config["intermediate_size"],
            "norms": 2 * c,
            "eva": 2 * c}     # mu and phi, [heads, head size] each


def n_params(config):
    """Parameters here: the layers, the embedding, the untied heads
    (one [hidden, num_pred_heads x vocab] matrix) and the final norm."""
    c, v = config["hidden_size"], config["vocab_size"]
    return config["num_hidden_layers"] * sum(layer_params(config).values()) \
        + v * c + c * config["num_pred_heads"] * v + c


def forward_flops_per_token(config, seq_len):
    """Forward FLOPs a token, by part: 2 x the parameters that
    multiply; attention 4 x hidden (QK^T and PV, 2 d a head each) a
    pair; the summariser 8 x hidden a token (two pooling logits and two
    weighted sums, 2 d a head each).  The embedding is a gather."""
    layers, c = config["num_hidden_layers"], config["hidden_size"]
    params = layer_params(config)
    tokens, chunks = eva_pairs(config, seq_len)
    return {
        "attention_proj": 2.0 * layers * params["mixer"],
        "ffn": 2.0 * layers * params["ffn"],
        "head": 2.0 * c * config["num_pred_heads"] * config["vocab_size"],
        "eva_window": 4.0 * c * layers * tokens / seq_len,
        "eva_chunks": 4.0 * c * layers * chunks / seq_len,
        "eva_pool": 8.0 * c * layers,
    }


def train_flops_per_token(config, seq_len):
    """Forward + backward: three times the forward."""
    return 3.0 * sum(forward_flops_per_token(config, seq_len).values())


def eva_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the aggregation's kernels of one train step,
    whatever implements them: the allowed pairs, backward twice the
    forward; each operand moved once: forward q, k, v, o and the
    summaries k~, v~ (1/chunk of a row each), backward q, k, v, o, dO,
    dq, dk, dv and k~, v~, dk~, dv~."""
    layers, c = config["num_hidden_layers"], config["hidden_size"]
    tokens, chunks = eva_pairs(config, seq_len)
    ops = 3 * 4.0 * c * batch * (tokens + chunks)
    row = batch * seq_len * c * bytes_per_el
    nbytes = (4 + 8) * row + (2 + 4) * row / config["chunk_size"]
    return float(layers * ops), float(layers * nbytes)


def eva_pool_step(config, batch, seq_len, bytes_per_el=2):
    """(flops, bytes) of the summariser's kernels of one train step:
    forward K and V read and 1/chunk of them written; backward K, V
    read, dK, dV written and the summaries' gradients read.  Bound by
    the bytes."""
    layers, c = config["num_hidden_layers"], config["hidden_size"]
    row = batch * seq_len * c * bytes_per_el
    ops = 3 * 8.0 * c * batch * seq_len
    nbytes = (2 + 4) * row + (2 + 2) * row / config["chunk_size"]
    return float(layers * ops), float(layers * nbytes)

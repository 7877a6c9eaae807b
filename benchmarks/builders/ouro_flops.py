"""Operations of the looped decoder `ouro` (a stack of layers run
total_ut_steps times over one set of weights, a head after every
pass), from shapes alone: what benchmarks/flops.py is to the 2017
Transformer.  Loaded by builders/ouro.py; checked against hand-worked
values in tests/test_ouro.py.  A parameter that R executions read
multiplies R times; work the program repeats (a recompute segment's
replay, scores the backward kernels form again) is never counted.
The flash kernels' operations and bytes are flops.py's
transformer_flash_step over R x L layer executions.
"""

from __future__ import annotations


def layer_matmul_params(config):
    """Parameters of one layer that multiply activations: q, k, v, o
    (4 hidden x heads x head_dim) and SwiGLU's gate, up, down
    (3 hidden x intermediate).  The four norm scales do not."""
    c = config["hidden_size"]
    attn = 4 * c * config["num_attention_heads"] * config["head_dim"]
    return attn + 3 * c * config["intermediate_size"]


def parts_per_token(config, seq_len):
    """Forward + backward FLOPs a token, by part: 6 x the parameters
    that multiply (2 forward, 4 backward), each counted once an
    execution (R for a layer's and the head's, R - 1 for the exit
    gate's); causal attention 12 x heads x head_dim x seq / 2 a layer
    execution (QK^T and PV over half the square, backward twice the
    forward).  The embedding is a gather."""
    passes = config.get("total_ut_steps", 1)
    runs = passes * config["num_hidden_layers"]
    c = config["hidden_size"]
    width = config["num_attention_heads"] * config["head_dim"]
    return {
        "layers": 6.0 * layer_matmul_params(config) * runs,
        "flash": 6.0 * width * seq_len * runs,
        "heads": 6.0 * c * config["vocab_size"] * passes,
        "exit_gate": 6.0 * c * (passes - 1),
    }


def train_flops_per_token(config, seq_len):
    return sum(parts_per_token(config, seq_len).values())

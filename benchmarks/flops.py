"""Operations and bytes the algorithms need, from shapes alone.

The benchmark's own arithmetic: utilization and roofline shares divide
these by measured time, so no program change can move them.  Work the
program repeats (recomputed attention scores, rematerialised
activations) is never counted.  Every function is checked against a
hand-worked value in tests/test_flops.py.

Copied from bench.py (_transformer_train_flops_per_token,
_transformer_n_params, _resnet50_train_flops_per_image), with three
changes that PERF.md section 6 explains: parameters that feed no
matrix multiplication (the embedding table, a positional table this
model does not have) are not in N, a causal model is charged the
causal half of the attention square, and ResNet's 8.2 GFLOP is walked
from shapes instead of quoted.
"""

from __future__ import annotations

import json
import os


def transformer_matmul_params(d_model, n_layer, d_inner, vocab_size):
    """Parameters that multiply activations: per layer the four
    attention projections (4 d^2) and the two feed-forward matrices
    (2 d d_inner), plus the untied output projection (d vocab).  The
    embedding table is a gather and the positions are a constant:
    neither needs a multiplication."""
    return (n_layer * (4 * d_model * d_model + 2 * d_model * d_inner)
            + d_model * vocab_size)


def transformer_train_flops_per_token(n_params, d_model, n_layer, seq_len,
                                      causal):
    """Forward + backward: 6 N for the parameter matmuls (2 forward, 4
    backward) plus attention, 12 L d s over the full square (QK^T and
    PV, 2 s d each forward, twice that backward) and half of it when
    causal."""
    attn = 12.0 * n_layer * d_model * seq_len
    return 6.0 * n_params + (attn / 2 if causal else attn)


_RESNET_DEPTHS = {18: ("basic", (2, 2, 2, 2)), 34: ("basic", (3, 4, 6, 3)),
                  50: ("bottleneck", (3, 4, 6, 3)),
                  101: ("bottleneck", (3, 4, 23, 3)),
                  152: ("bottleneck", (3, 8, 36, 3))}


def resnet_forward_flops_per_image(depth, image_size, num_classes):
    """2 x multiply-adds of every convolution and of the classifier of
    an ImageNet ResNet (stride on the 3x3, v1.5), walked from shapes:
    7x7/2 stem, 3x3/2 max-pool, four stages at 64/128/256/512 filters,
    1x1 projection shortcuts where shape changes.  ResNet-50 at 224^2:
    4.09 G multiply-adds = 8.2 GFLOP.  Batch norm, ReLU, pooling and
    the loss are not counted."""
    kind, counts = _RESNET_DEPTHS[depth]

    def conv(hw, cin, cout, k, stride):
        out = -(-hw // stride)
        return out, out * out * cin * cout * k * k

    hw, macs = conv(image_size, 3, 64, 7, 2)
    hw, cin, total = -(-hw // 2), 64, macs
    for stage, count in enumerate(counts):
        f = 64 * 2 ** stage
        for i in range(count):
            stride = 2 if i == 0 and stage > 0 else 1
            if kind == "bottleneck":
                cout = 4 * f
                _, a = conv(hw, cin, f, 1, 1)
                out, b = conv(hw, f, f, 3, stride)
                _, c = conv(out, f, cout, 1, 1)
                total += a + b + c
            else:
                cout = f
                out, a = conv(hw, cin, f, 3, stride)
                _, b = conv(out, f, f, 3, 1)
                total += a + b
            if cin != cout or stride != 1:
                total += conv(hw, cin, cout, 1, stride)[1]
            hw, cin = out, cout
    return 2.0 * (total + cin * num_classes)


def resnet_train_flops_per_image(depth, image_size, num_classes):
    """Forward + backward: the backward pass costs twice the forward
    (gradients to inputs and to weights): 3 x 8.2 GFLOP for ResNet-50
    at 224^2."""
    return 3 * resnet_forward_flops_per_image(depth, image_size,
                                              num_classes)


def flash_attention_flops(batch, heads, seq_q, seq_k, head_dim, causal,
                          backward=False):
    """Attention's matmuls from shapes.  Forward: QK^T and PV, 2 B H Tq
    Tk d each = 4 B H Tq Tk d; causal needs half the square (2 B H T^2 d
    for Tq = Tk = T).  Backward: dV, dP, dQ, dK, four such products = 2 x
    forward; the scores the kernel recomputes are not counted."""
    fwd = 4.0 * batch * heads * seq_q * seq_k * head_dim
    if causal:
        fwd /= 2
    return 2 * fwd if backward else fwd


def flash_attention_bytes(batch, heads, seq_q, seq_k, head_dim,
                          bytes_per_el, backward=False):
    """Least HBM traffic: each operand read or written once.  Forward
    reads q, k, v and writes o; backward reads q, k, v, o, do and
    writes dq, dk, dv.  Row statistics (a few bytes a row) left out."""
    q = batch * heads * seq_q * head_dim
    kv = batch * heads * seq_k * head_dim
    els = (3 * q + 2 * kv + 3 * kv) if backward else (2 * q + 2 * kv)
    return float(els * bytes_per_el)


def transformer_flash_step(batch, heads, seq_len, head_dim, n_layer,
                           bytes_per_el=2):
    """(flops, bytes) of all causal self-attention calls of one train
    step: n_layer x (forward + backward)."""
    args = (batch, heads, seq_len, seq_len, head_dim)
    flops = n_layer * (flash_attention_flops(*args, causal=True)
                       + flash_attention_flops(*args, causal=True,
                                               backward=True))
    nbytes = n_layer * (flash_attention_bytes(*args, bytes_per_el)
                        + flash_attention_bytes(*args, bytes_per_el,
                                                backward=True))
    return flops, nbytes


def roofline_seconds(flops, nbytes, peak):
    """Least time the chip could take, and which peak bounds it."""
    t_c = flops / peak["bf16_flops_per_s"]
    t_m = nbytes / peak["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")


def load_peaks(path, device_kind):
    """The published peaks of `device_kind`.  A device the table does
    not know is an error, not a default: a utilization against a
    made-up peak is not a number."""
    with open(path) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError("no published peaks for device kind %r in %s "
                       "(known: %s)" % (device_kind,
                                        os.path.basename(path),
                                        sorted(table)))
    return table[device_kind]

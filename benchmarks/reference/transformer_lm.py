"""Plain reference of the decoder-only causal Transformer LM
configurations: forward pass and loss in straightforward jax.numpy,
float32, matmul precision "highest", no kernels, no AMP.

Follows Vaswani et al. 2017 as models/transformer.py builds it, each
departure from the paper being the configuration's, not the
reference's: post-layer-norm residual blocks (x = LN(x + sublayer(x))),
no final layer norm, ReLU feed-forward, sinusoidal positions added to
embeddings scaled by sqrt(d_model), an untied output projection, mean
cross-entropy over all positions; decoder-only, causal.

Memory: attention is computed one sequence at a time and in blocks of
query rows (a whole 8192^2 score matrix per head is 268 MB in f32), and
the 32000-wide logits one sequence at a time.
"""

from __future__ import annotations

import functools

import numpy as np

LN_EPS = 1e-5          # layers.layer_norm's default
QUERY_BLOCK = 512


def param_names(config):
    p = config["param_prefix"]
    names = {"emb": p + "_emb.w", "out": p + "_out_fc.w", "layers": []}
    for i in range(config["n_layer"]):
        lp = "%s_l%d" % (p, i)
        names["layers"].append({
            "q": lp + "_self_q.w", "k": lp + "_self_k.w",
            "v": lp + "_self_v.w", "o": lp + "_self_out.w",
            "ln1_g": lp + "_ln1.scale", "ln1_b": lp + "_ln1.bias",
            "w1": lp + "_ffn_fc1.w", "b1": lp + "_ffn_fc1.b",
            "w2": lp + "_ffn_fc2.w", "b2": lp + "_ffn_fc2.b",
            "ln2_g": lp + "_ln2.scale", "ln2_b": lp + "_ln2.bias"})
    return names


def read_params(config, get):
    """The program's own weights as float32 copies.  `get(name)`
    returns the array the scope holds under `name`."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n: jnp.array(get(n), dtype=jnp.float32, copy=True),
        param_names(config))


def positional_encoding(seq_len, d_model):
    pos = np.arange(seq_len)[:, None]
    i = np.arange(d_model)[None, :]
    angle = pos / np.power(10000.0, (2 * (i // 2)) / d_model)
    enc = np.zeros((seq_len, d_model), np.float64)
    enc[:, 0::2] = np.sin(angle[:, 0::2])
    enc[:, 1::2] = np.cos(angle[:, 1::2])
    return enc.astype(np.float32)


def causal_attention(q, k, v):
    """softmax(q k^T / sqrt(d) + causal mask) v for one sequence,
    [H, T, d] each, the whole score matrix at once."""
    import jax
    import jax.numpy as jnp

    t = q.shape[1]
    s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(q.shape[-1])
    seen = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    return jnp.einsum("hqk,hkd->hqd",
                      jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)


def causal_attention_blocked(q, k, v, block=QUERY_BLOCK):
    """The same, `block` query rows at a time against all keys."""
    import jax
    import jax.numpy as jnp

    h, t, d = q.shape
    block = min(block, t)
    if t % block:
        raise ValueError("seq %d not a multiple of the query block %d"
                         % (t, block))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) / np.sqrt(d)
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(block))[:, None]
        return jnp.einsum(
            "hqk,hkd->hqd",
            jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1), v)

    out = jax.lax.map(rows, jnp.arange(0, t, block))     # [nb, H, block, d]
    return jnp.moveaxis(out, 0, 1).reshape(h, t, d)


def _layer_norm(x, g, b):
    import jax.numpy as jnp

    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def sequence_loss(params, ids, labels, n_head):
    """Sum of the token cross-entropies of ONE sequence, ids and labels
    [T] int."""
    import jax
    import jax.numpy as jnp

    t = ids.shape[0]
    d_model = params["emb"].shape[1]
    x = params["emb"][ids] * np.sqrt(d_model) \
        + positional_encoding(t, d_model)

    def heads(y):
        return y.reshape(t, n_head, d_model // n_head).transpose(1, 0, 2)

    for lw in params["layers"]:
        a = causal_attention_blocked(heads(x @ lw["q"]), heads(x @ lw["k"]),
                                     heads(x @ lw["v"]))
        a = a.transpose(1, 0, 2).reshape(t, d_model) @ lw["o"]
        x = _layer_norm(x + a, lw["ln1_g"], lw["ln1_b"])
        f = jax.nn.relu(x @ lw["w1"] + lw["b1"]) @ lw["w2"] + lw["b2"]
        x = _layer_norm(x + f, lw["ln2_g"], lw["ln2_b"])
    logp = jax.nn.log_softmax(x @ params["out"], axis=-1)
    return -jnp.take_along_axis(logp, labels[:, None], axis=1).sum()


@functools.lru_cache(maxsize=None)
def _jitted(n_head):
    import jax

    def batch_loss(params, ids, labels):
        with jax.default_matmul_precision("highest"):
            per_seq = jax.lax.map(
                lambda xy: sequence_loss(params, xy[0], xy[1], n_head),
                (ids, labels))
        return per_seq.sum() / ids.size

    return jax.jit(batch_loss)


def loss(params, batch, config):
    """Mean cross-entropy of the batch (ids, labels), each [B, T, 1]."""
    import jax.numpy as jnp

    ids, labels = (jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                   for a in batch)
    return float(_jitted(config["n_head"])(params, ids, labels))

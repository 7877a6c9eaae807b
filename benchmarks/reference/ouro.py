"""Plain reference of the looped decoder `ouro` ("Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): forward pass,
the R passes' logits, the exit distribution and the loss in
straightforward jax.numpy, float32, matmul precision "highest", no
kernels, no AMP, a Python loop over passes and layers.  Shares no code
with paddle_tpu/ops or models/ouro.py.

Follows docs/OURO_BLOCK.md equation by equation (R = total_ut_steps,
the SAME layer weights in every pass):

* h^0 = E[ids]; for r = 1..R: x = h^(r-1); for every layer
      a = RMSNorm_1(x);  q, k, v = a Wq, a Wk, a Wv  (no bias)
      q, k <- RoPE(theta, split halves: (x[i], x[i + D/2]) turn together)
      o = softmax_causal(q k^T / sqrt(D)) v
      x <- x + RMSNorm_2(o Wo)
      m = RMSNorm_3(x);  f = (SiLU(m Wgate) * (m Wup)) Wdown
      x <- x + RMSNorm_4(f)
  h^r = RMSNorm_final(x): one final norm, after every pass;
* after every pass z^r = h^r Whead; after every pass but the last
  g^r = h^r wg + bg, lambda^r = sigmoid(g^r);
* exit distribution, a token: S^0 = 1; r < R: p^r = lambda^r S^(r-1),
  S^r = S^(r-1) (1 - lambda^r); p^R = S^(R-1);
* loss = mean over tokens of [ sum_r p^r CE(z^r, next id) - beta H(p) ],
  H(p) = - sum_r p^r ln p^r (0 ln 0 = 0).

Departures from the published description, each the configuration's and
stated there under `assumed`: none made here that the config does not
name.

Memory at 4,096 tokens and 49,152 ids: attention is computed one
sequence at a time in blocks of query rows (a whole 4096^2 score matrix
for 16 heads is 1.07 GB in f32), and a pass's head in blocks of tokens
that give their cross-entropy and are dropped (a pass's logits are
0.8 GB in f32).
"""

from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 512
TOKEN_BLOCK = 512


def param_names(config):
    p = config.get("param_prefix", "ouro")
    names = {"emb": p + "_emb.w", "final_norm": p + "_final_norm.w",
             "head": p + "_head.w", "layers": []}
    if config.get("total_ut_steps", 1) > 1:
        names["gate_w"] = p + "_exit_gate.w"
        names["gate_b"] = p + "_exit_gate.b"
    for i in range(config["num_hidden_layers"]):
        b = "%s_l%d_" % (p, i)
        names["layers"].append({k: b + k + ".w" for k in (
            "norm1", "norm2", "norm3", "norm4", "q", "k", "v", "o",
            "gate", "up", "down")})
    return names


def read_params(config, get):
    """The program's own weights as float32 arrays.  `get(name)` returns
    the array the scope holds under `name`.  No copy is made of an
    array that is float32 already: read them before a step donates
    them."""
    import jax
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda n: jnp.asarray(get(n), dtype=jnp.float32),
        param_names(config))


# -- pieces -----------------------------------------------------------------

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rope(x, theta):
    """x [T, H, D]: (x[i], x[i + D/2]) turn by position * theta^(-2i/D)."""
    import jax.numpy as jnp

    t, _, d = x.shape
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = jnp.asarray(np.arange(t, dtype=np.float64)[:, None] * inv[None],
                      jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(a, lw, config):
    """Causal self-attention of one sequence, a [T, C] -> [T, H*D],
    the scores a block of query rows at a time."""
    import jax
    import jax.numpy as jnp

    t = a.shape[0]
    heads, d = config["num_attention_heads"], config["head_dim"]
    theta = float(config["rope_theta"])
    q = rope((a @ lw["q"]).reshape(t, heads, d), theta)
    k = rope((a @ lw["k"]).reshape(t, heads, d), theta)
    v = (a @ lw["v"]).reshape(t, heads, d)
    block = min(QUERY_BLOCK, t)
    outs = []
    for start in range(0, t, block):
        s = jnp.einsum("qhd,khd->hqk", q[start:start + block], k) \
            / np.sqrt(d)
        rows = jnp.arange(start, min(start + block, t))[:, None]
        s = jnp.where(jnp.arange(t)[None, :] <= rows, s, -jnp.inf)
        outs.append(jnp.einsum("hqk,khd->qhd",
                               jax.nn.softmax(s, axis=-1), v))
    return jnp.concatenate(outs, axis=0).reshape(t, heads * d)


def swiglu(m, lw):
    import jax

    return (jax.nn.silu(m @ lw["gate"]) * (m @ lw["up"])) @ lw["down"]


def layer(x, lw, config):
    eps = config["rms_norm_eps"]
    a = rms_norm(x, lw["norm1"], eps)
    x = x + rms_norm(attention(a, lw, config) @ lw["o"], lw["norm2"], eps)
    m = rms_norm(x, lw["norm3"], eps)
    return x + rms_norm(swiglu(m, lw), lw["norm4"], eps)


def sequence_states(params, ids, config, layer_fn=layer):
    """[h^1, .., h^R], each [T, C], of ONE sequence, ids [T] int.
    `layer_fn`: `layer`, or its jitted form (`loss`)."""
    h, states = params["emb"][ids], []
    for _ in range(config.get("total_ut_steps", 1)):
        x = h
        for lw in params["layers"]:
            x = layer_fn(x, lw, config)
        h = rms_norm(x, params["final_norm"], config["rms_norm_eps"])
        states.append(h)
    return states


def sequence_logits(params, ids, config):
    """The R passes' logits [R, T, vocab] of one sequence."""
    import jax.numpy as jnp

    return jnp.stack([h @ params["head"]
                      for h in sequence_states(params, ids, config)])


def exit_distribution(gates):
    """gates [R-1, T] (g^r of every pass but the last) -> p [R, T]."""
    import jax
    import jax.numpy as jnp

    stay, p = jnp.ones(gates.shape[1:], jnp.float32), []
    for g in gates:
        lam = jax.nn.sigmoid(g)
        p.append(lam * stay)
        stay = stay * (1.0 - lam)
    return jnp.stack(p + [stay])


def sequence_exit_distribution(params, ids, config):
    """p [R, T] of one sequence."""
    import jax.numpy as jnp

    states = sequence_states(params, ids, config)
    if len(states) == 1:
        return jnp.ones((1, ids.shape[0]), jnp.float32)
    return exit_distribution(jnp.stack(
        [h @ params["gate_w"][:, 0] + params["gate_b"][0]
         for h in states[:-1]]))


def cross_entropy(h, head, labels):
    """Per-token cross-entropy [T] of logits h @ head, a block of
    tokens at a time."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    block = min(TOKEN_BLOCK, t)
    out = []
    for start in range(0, t, block):
        logp = jax.nn.log_softmax(h[start:start + block] @ head, axis=-1)
        out.append(-jnp.take_along_axis(
            logp, labels[start:start + block, None], axis=1)[:, 0])
    return jnp.concatenate(out)


def sequence_loss(params, ids, labels, config, layer_fn=layer,
                  ce_fn=cross_entropy):
    """Sum over one sequence's tokens of sum_r p^r l^r - beta H(p)."""
    import jax.numpy as jnp

    states = sequence_states(params, ids, config, layer_fn)
    ce = jnp.stack([ce_fn(h, params["head"], labels) for h in states])
    if len(states) == 1:
        return ce.sum()
    p = exit_distribution(jnp.stack(
        [h @ params["gate_w"][:, 0] + params["gate_b"][0]
         for h in states[:-1]]))
    p_ln_p = jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0)
    beta = config.get("exit_entropy_beta", 0.05)
    return ((p * ce).sum(0) + beta * p_ln_p.sum(0)).sum()


def batch_loss(params, ids, labels, config, **pieces):
    """The loss, ids and labels [B, T] int; a function of jax arrays
    that jax.grad differentiates (the tests' gradients)."""
    import jax

    with jax.default_matmul_precision("highest"):
        return sum(sequence_loss(params, i, y, config, **pieces)
                   for i, y in zip(ids, labels)) / ids.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: every
    execution of a layer has the same shapes, so the R x L of them
    compile ONCE (the whole loss as one jit is R x L copies of the
    layer in one module: 68 s of compile at the benchmark's size, on
    every run of the cell)."""
    import jax

    return {"layer_fn": jax.jit(layer, static_argnums=2),
            "ce_fn": jax.jit(cross_entropy)}


class _Static(dict):
    """A config dict as a static (hashable) jit argument."""

    def __hash__(self):
        import json

        return hash(json.dumps(self, sort_keys=True))


def _split(batch):
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                 for a in batch)


def loss(params, batch, config):
    """The loss of the batch (ids, labels), each [B, T, 1]: the same
    Python loop over passes and layers, its pieces jitted."""
    ids, labels = _split(batch)
    return float(batch_loss(params, ids, labels, _Static(config),
                            **_jitted_pieces()))

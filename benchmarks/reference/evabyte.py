"""Plain reference of EvaByte (model_type `evabyte`, attention_class
`eva`: EVA attention in every layer, a dense SwiGLU, norms with a unit
offset, num_pred_heads next-byte heads over one stream position):
forward pass, logits and loss in straightforward jax.numpy, float32,
matmul precision "highest", no kernels, no AMP, a Python loop over
layers and over windows.  Shares no code with paddle_tpu/ops or
models/evabyte.py: the two key sets are two score arrays side by side
under one softmax, the summaries are a reshape and two softmaxes, the
rotary frequencies are computed here.

Follows docs/EVABYTE_BLOCK.md equation by equation.  h the residual
stream [T, C], n(x, w) = x / rms(x) * (1 + w) where
`norm_add_unit_offset` (else * w), W = window_size, c = chunk_size:

* h = Emb[id]; every layer: h <- h + EVA(n(h)) W_o, then
  h <- h + SwiGLU(n(h)); logits = n(h_L) W_head as [T, P, V], head p of
  the P = num_pred_heads at position t predicting byte t + 1 + p;
  loss = mean over t and p of CE(logits[t, p], byte t + 1 + p).
* EVA (H heads of d = C / H), u = n(h):
      q, k, v = u W_q, u W_k, u W_v          no bias
      q, k turned over all of d, split halves (x[i], x[i + d/2]), by
          position * theta^(-2i/d)
      chunk j = positions c j .. c j + c - 1, per head with mu, phi:
          k~_j = sum_m softmax_m(mu . k_m) k_m      (no d^-1/2)
          v~_j = sum_m softmax_m(phi . k_m) v_m     (logits read k)
      query i, window w = i // W: keys = tokens t with t // W = w and
          t <= i, and chunks j with c j // W < w; ONE softmax over both
          at d^-1/2; o = sum of the weights times v_t and v~_j.

Every array up to the logits takes the dtype of the parameters it is
given, so that the same layers computed in bfloat16 say what a lower
precision does (`loss(..., dtype="bfloat16")`; the cross-entropy and
its mean stay float32).  `variant` computes a WRONG model on purpose,
for the controls: "mean_pool" weighs a chunk's 16 positions alike (mu
and phi unread), "no_chunks" drops the chunk keys (attention local to a
window), "sliding_window" gives a query the W tokens that END at its
own in place of its aligned window's (beside the same chunk keys),
"one_head" reads head 0's columns for all P heads.

Memory at 8,192 bytes: attention is computed one window of queries at a
time, QUERY_BLOCK rows at once against the window's tokens and the
earlier chunks.
"""

from __future__ import annotations

import functools

import numpy as np

QUERY_BLOCK = 256

_MATRICES = ("q", "k", "v", "o", "gate", "up", "down")
VARIANTS = ("mean_pool", "no_chunks", "sliding_window", "one_head")


def param_names(config):
    p = config.get("param_prefix", "evabyte")
    names = {"emb": p + "_emb.w", "head": p + "_head.w",
             "final_norm": p + "_final_norm.w", "layers": []}
    for i in range(config["num_hidden_layers"]):
        b = "%s_l%d" % (p, i)
        layer = {"attn_norm": b + "_attn_norm.w",
                 "ffn_norm": b + "_ffn_norm.w",
                 "mu": b + "_eva_mu.w", "phi": b + "_eva_phi.w"}
        layer.update({k: "%s_%s.w" % (b, k) for k in _MATRICES})
        names["layers"].append(layer)
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

def norm(x, w, config):
    import jax.numpy as jnp

    y = x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                     + config["rms_norm_eps"])
    return y * (1 + w) if config["norm_add_unit_offset"] else y * w


def rotate_halves(x, theta):
    """x [T, H, d]: the pairs (x[i], x[i + d/2]) turned by
    position * theta^(-2i/d)."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    freq = 1.0 / float(theta) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] * freq
    cos = jnp.asarray(np.cos(ang), x.dtype)[:, None]
    sin = jnp.asarray(np.sin(ang), x.dtype)[:, None]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def summaries(k, v, mu, phi, chunk, variant=""):
    """(k~, v~) [T/c, H, d] of k, v [T, H, d]: both softmaxes over a
    chunk's positions, their logits mu . k and phi . k."""
    import jax
    import jax.numpy as jnp

    t, h, d = k.shape
    kc, vc = (a.reshape(t // chunk, chunk, h, d) for a in (k, v))
    if variant == "mean_pool":
        return kc.mean(1), vc.mean(1)
    wk = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, mu), axis=1)
    wv = jax.nn.softmax(jnp.einsum("jmhd,hd->jmh", kc, phi), axis=1)
    return (wk[..., None] * kc).sum(1), (wv[..., None] * vc).sum(1)


def window_rows(q, k, v, ks, vs, first, first_key, scale, window,
                sliding):
    """The outputs of the queries q [H, n, d] at positions first ..
    first + n - 1 against the token keys k, v [H, m, d] at positions
    first_key .. and ALL the chunk keys ks, vs [H, r, d] given, one
    softmax; QUERY_BLOCK rows at a time.  A token key at position j is
    allowed for the query at i where j <= i and, `sliding`, j > i -
    window."""
    import jax
    import jax.numpy as jnp

    n, m = q.shape[1], k.shape[1]
    block = min(QUERY_BLOCK, n)
    if n % block:
        raise ValueError("%d queries are no multiple of the query block "
                         "%d" % (n, block))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        i = (first + start + jnp.arange(block))[:, None]
        j = (first_key + jnp.arange(m))[None, :]
        allowed = j <= i
        if sliding:
            allowed = allowed & (j > i - window)
        s = jnp.concatenate([
            jnp.where(allowed,
                      jnp.einsum("hqd,hkd->hqk", qb, k) * scale,
                      -jnp.inf),
            jnp.einsum("hqd,hkd->hqk", qb, ks) * scale], axis=-1)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p[..., :m], v) \
            + jnp.einsum("hqk,hkd->hqd", p[..., m:], vs)

    out = jax.lax.map(rows, jnp.arange(0, n, block))   # [nb, H, block, d]
    return jnp.moveaxis(out, 0, 1).reshape(q.shape[0], n, v.shape[-1])


def eva_mixer(u, lw, config, variant=""):
    """EVA attention of one sequence, u [T, C] -> [T, C]."""
    import jax.numpy as jnp

    t, c = u.shape
    heads = config["num_attention_heads"]
    d = c // heads
    w, chunk = config["window_size"], config["chunk_size"]
    q = rotate_halves((u @ lw["q"]).reshape(t, heads, d),
                      config["rope_theta"])
    k = rotate_halves((u @ lw["k"]).reshape(t, heads, d),
                      config["rope_theta"])
    v = (u @ lw["v"]).reshape(t, heads, d)
    ks, vs = summaries(k, v, lw["mu"], lw["phi"], chunk, variant)
    q, k, v, ks, vs = (a.transpose(1, 0, 2) for a in (q, k, v, ks, vs))
    sliding = variant == "sliding_window"
    outs = []
    for start in range(0, t, w):
        end = min(start + w, t)
        # the chunks of the windows BEFORE this one
        seen = 0 if variant == "no_chunks" else start // chunk
        first_key = max(start - w, 0) if sliding else start
        outs.append(window_rows(
            q[:, start:end], k[:, first_key:end], v[:, first_key:end],
            ks[:, :seen], vs[:, :seen], start, first_key, d ** -0.5, w,
            sliding))
    out = jnp.concatenate(outs, axis=1)
    return out.transpose(1, 0, 2).reshape(t, c) @ lw["o"]


def swiglu(u, lw):
    import jax

    return (jax.nn.silu(u @ lw["gate"]) * (u @ lw["up"])) @ lw["down"]


def layer(x, lw, config, variant=""):
    x = x + eva_mixer(norm(x, lw["attn_norm"], config), lw, config,
                      variant)
    return x + swiglu(norm(x, lw["ffn_norm"], config), lw)


def sequence_state(params, ids, config, layer_fn=layer, variant=""):
    """n(h_L) [T, C] of ONE sequence, ids [T] int."""
    x = params["emb"][ids]
    for lw in params["layers"]:
        x = layer_fn(x, lw, config, variant)
    return norm(x, params["final_norm"], config)


def head_logits(h, head, config, variant=""):
    """[T, P, V] float32: the P heads side by side in one matrix."""
    import jax.numpy as jnp

    p, v = config["num_pred_heads"], config["vocab_size"]
    out = (h @ head).astype(jnp.float32).reshape(h.shape[0], p, v)
    if variant == "one_head":
        out = jnp.broadcast_to(out[:, :1], out.shape)
    return out


def cross_entropy(h, head, labels, config, variant=""):
    """[T, P] cross-entropies, labels [T, P] int: float32 whatever the
    layers' dtype."""
    import jax
    import jax.numpy as jnp

    logp = jax.nn.log_softmax(head_logits(h, head, config, variant), -1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def batch_loss(params, ids, labels, config, layer_fn=layer,
               ce_fn=cross_entropy, variant="", precision="highest"):
    """The loss, ids [B, T] and labels [B, T, P] int; a function of jax
    arrays that jax.grad differentiates (the tests' gradients)."""
    import jax

    with jax.default_matmul_precision(precision or "default"):
        return sum(
            ce_fn(sequence_state(params, i, config, layer_fn, variant),
                  params["head"], y, config, variant).sum()
            for i, y in zip(ids, labels)) / labels.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: the layers
    have the same shapes and compile ONCE."""
    import jax

    return {"layer_fn": jax.jit(layer, static_argnums=(2, 3)),
            "ce_fn": jax.jit(cross_entropy, static_argnums=(3, 4))}


class _Static(dict):
    """A config dict as a static (hashable) jit argument."""

    def __hash__(self):
        import json

        return hash(json.dumps(self, sort_keys=True))


def _split(batch):
    """(ids [B, T], labels [B, T, P]) of the feeds [B, T, 1] and
    [B, T, P, 1]."""
    import jax.numpy as jnp

    return tuple(jnp.asarray(np.asarray(a)[..., 0].astype(np.int32))
                 for a in batch)


def _in_dtype(params, dtype):
    import jax
    import jax.numpy as jnp

    if not dtype:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.dtype(dtype)), params)


def loss(params, batch, config, variant="", dtype=None):
    """The loss of the batch (ids [B, T, 1], labels [B, T, P, 1]): the
    same Python loop over layers, its pieces jitted.  dtype "bfloat16":
    every parameter and so every array in that dtype, at the default
    matmul precision (the control: what a lower precision does)."""
    ids, labels = _split(batch)
    return float(batch_loss(_in_dtype(params, dtype), ids, labels,
                            _Static(config), variant=variant,
                            precision=None if dtype else "highest",
                            **_jitted_pieces()))


def logits(params, batch, config, variant="", dtype=None, every=1):
    """The logits of every `every`-th position of the batch, float32
    [B, T / every, P, V], by the same pieces as `loss` and under the
    same `variant` and `dtype` (tools/reference_controls.py
    --logits)."""
    import jax
    import jax.numpy as jnp

    ids, _ = _split(batch)
    params = _in_dtype(params, dtype)
    config = _Static(config)
    layer_fn = _jitted_pieces()["layer_fn"]
    with jax.default_matmul_precision("default" if dtype else "highest"):
        return jnp.stack([
            head_logits(sequence_state(params, i, config, layer_fn,
                                       variant)[::every],
                        params["head"], config, variant) for i in ids])

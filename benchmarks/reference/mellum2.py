"""Plain reference of Mellum2-12B-A2.5B (model_type `mellum`: sliding-
window and full-attention layers by `layer_types`, each kind with its
own rotary parameters, sparse experts in every layer, an untied head):
forward pass, logits and loss in straightforward jax.numpy, float32,
matmul precision "highest", no kernels, no AMP, a Python loop over
layers.  Shares no code with paddle_tpu/ops or models/mellum2.py: the
mask is the two inequalities written out, attention repeats K and V to
the query heads, the YaRN frequencies are computed here, and the router
marks its experts over all 64.

Follows docs/MELLUM2_BLOCK.md equation by equation.  h the residual
stream, C = hidden_size:

* h = Emb[id]; every layer: h <- h + Attention(RMSNorm(h)), then
  h <- h + Experts(RMSNorm(h)); logits = RMSNorm(h_L) W_head (untied);
  loss = mean over tokens of CE(logits, next id).  eps rms_norm_eps.
* The layers built are the published layers `kept_layers` (default
  0 .. num_hidden_layers - 1); the kind of published layer l is
  `layer_types[l]`.
* attention (H heads, H_kv KV heads of d = head_dim), u = RMSNorm(h):
      q, k, v = u W_q, u W_k, u W_v            no bias, no norm on q, k
      q, k turned over all of d, split halves (x[i], x[i + d/2]), by
          position * f_i, cos and sin times a, with (f, a) the layer
          kind's `rope_parameters`: "default" f_i = theta^(-2i/d),
          a = 1; "yarn" f_i between theta^(-2i/d) / factor (the pairs
          that turn fewer than beta_slow times over the original
          length) and theta^(-2i/d) (more than beta_fast times), a
          linear ramp between, a = attention_factor
      s_ij = q_i . k_j d^-1/2, query head h reading KV head
          h // (H / H_kv); allowed where j <= i and, in a
          "sliding_attention" layer, also j > i - sliding_window
      o = softmax over the allowed j of s, times v;   y = o W_o
* experts: r = softmax(u W_r) over ALL experts, float32; the
      num_experts_per_tok largest are selected;
      gate_e = r_e / sum of the selected r           (norm_topk_prob)
      y = sum_{e selected and held} gate_e W_down,e (SiLU(u W_gate,e)
          * u W_up,e)

Departures, each the configuration's and stated there under `assumed`:
a selected expert that this chip does not hold adds nothing (the
deployment's other chips would add it); ids, logits and loss are over
the vocabulary slice held here; no prediction (MTP) head is built (the
published config has no key for one).

Every array up to the logits takes the dtype of the parameters it is
given, so that the same layers computed in bfloat16 say what a lower
precision does (`loss(..., dtype="bfloat16")`; the router's logits, the
cross-entropy and its mean stay float32).  `variant` computes a WRONG
model on purpose, for the controls: "no_window" gives every layer the
full causal mask, "no_yarn" turns the full layers' q and k by the
default frequencies with factor 1, "gates_not_renormalised" weighs by
r_e as the softmax gave it.

Memory at 16,384 tokens: attention is computed one sequence at a time
in blocks of query rows against all keys, the experts as a loop over
the held ones with a mask over all tokens, and the head in blocks of
tokens that give their cross-entropy and are dropped.
"""

from __future__ import annotations

import functools
import math

import numpy as np

QUERY_BLOCK = 256
TOKEN_BLOCK = 512

_ATTN = ("q", "k", "v", "o")
_MLP = ("gate", "up", "down")
VARIANTS = ("no_window", "no_yarn", "gates_not_renormalised")


def layer_kinds(config):
    """"sliding_attention" or "full_attention" for each layer built."""
    kept = config.get("kept_layers")
    if kept is None:
        kept = range(config["num_hidden_layers"])
    return [config["layer_types"][i] for i in kept]


def held_experts(config):
    return list(config.get("held_experts") or range(config["num_experts"]))


def param_names(config):
    p = config.get("param_prefix", "mellum2")
    names = {"emb": p + "_emb.w", "head": p + "_head.w",
             "final_norm": p + "_final_norm.w", "layers": []}
    for i in range(len(layer_kinds(config))):
        b = "%s_l%d" % (p, i)
        layer = {"attn_norm": b + "_attn_norm.w",
                 "ffn_norm": b + "_ffn_norm.w",
                 "router": b + "_router.w"}
        layer.update({k: "%s_%s.w" % (b, k) for k in _ATTN})
        layer["experts"] = {k: "%s_experts_%s.w" % (b, k) for k in _MLP}
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

def rms_norm(x, scale, eps):
    import jax.numpy as jnp

    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def rotary_frequencies(rope, dim):
    """(f [dim/2] float64, a): the angle a position turns pair i by,
    and the factor on cos and sin."""
    base = float(rope["rope_theta"]) ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / base, 1.0
    factor = float(rope["factor"])
    original = rope["original_max_position_embeddings"]

    def pair_that_turns(times):
        # the pair index whose wavelength fits `times` into the
        # original context
        return dim * math.log(original / (times * 2 * math.pi)) \
            / (2 * math.log(rope["rope_theta"]))

    low = max(math.floor(pair_that_turns(rope["beta_fast"])), 0)
    high = min(math.ceil(pair_that_turns(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    # 0 up to `low` (kept as they are), 1 from `high` (divided by factor)
    scaled = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                     / (high - low), 0.0, 1.0)
    return (1.0 - scaled) / base + scaled / (factor * base), \
        float(rope["attention_factor"])


def rotate_halves(x, rope):
    """x [T, H, d]: the pairs (x[i], x[i + d/2]) turned by
    position * f_i, cos and sin times a."""
    import jax.numpy as jnp

    dim = x.shape[-1]
    freq, factor = rotary_frequencies(rope, dim)
    ang = np.arange(x.shape[0], dtype=np.float64)[:, None] * freq
    cos = jnp.asarray(np.cos(ang) * factor, x.dtype)[:, None]
    sin = jnp.asarray(np.sin(ang) * factor, x.dtype)[:, None]
    a, b = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def masked_attention(q, k, v, scale, window, block=QUERY_BLOCK):
    """softmax(scale q k^T over the allowed keys) v for one sequence,
    q, k, v [H, T, d]; `block` query rows at a time against all keys.
    Key j is allowed for query i where j <= i and, with a window (0:
    none), j > i - window."""
    import jax
    import jax.numpy as jnp

    h, t, _ = q.shape
    block = min(block, t)
    if t % block:
        raise ValueError("seq %d not a multiple of the query block %d"
                         % (t, block))

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, block, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qb, k) * scale
        i = (start + jnp.arange(block))[:, None]
        j = jnp.arange(t)[None, :]
        allowed = j <= i
        if window:
            allowed = allowed & (j > i - window)
        return jnp.einsum(
            "hqk,hkd->hqd",
            jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), -1), v)

    out = jax.lax.map(rows, jnp.arange(0, t, block))   # [nb, H, block, d]
    return jnp.moveaxis(out, 0, 1).reshape(h, t, v.shape[-1])


def attention_mixer(u, lw, config, kind, variant=""):
    """Grouped-query attention of one sequence, u [T, C] -> [T, C]."""
    import jax.numpy as jnp

    t = u.shape[0]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    d = config["head_dim"]
    rope = config["rope_parameters"][kind]
    if variant == "no_yarn":
        rope = {"rope_theta": rope["rope_theta"]}
    window = config["sliding_window"] if kind == "sliding_attention" else 0
    if variant == "no_window":
        window = 0
    q = rotate_halves((u @ lw["q"]).reshape(t, heads, d), rope)
    k = rotate_halves((u @ lw["k"]).reshape(t, kv, d), rope)
    v = (u @ lw["v"]).reshape(t, kv, d)
    # query head h reads KV head h // (heads / kv)
    k, v = (jnp.repeat(a, heads // kv, axis=1) for a in (k, v))
    out = masked_attention(*(a.transpose(1, 0, 2) for a in (q, k, v)),
                           d ** -0.5, window)
    return out.transpose(1, 0, 2).reshape(t, heads * d) @ lw["o"]


def swiglu(u, w):
    import jax

    return (jax.nn.silu(u @ w["gate"]) * (u @ w["up"])) @ w["down"]


def route(u, lw, config):
    """(selected [T, E] bool, r [T, E] float32) over ALL experts."""
    import jax
    import jax.numpy as jnp

    r = jax.nn.softmax(u.astype(jnp.float32)
                       @ lw["router"].astype(jnp.float32), axis=-1)
    ranked = jnp.argsort(-r, axis=-1, stable=True)
    t, e = r.shape
    selected = jnp.zeros((t, e), bool).at[
        jnp.arange(t)[:, None],
        ranked[:, :config["num_experts_per_tok"]]].set(True)
    return selected, r


def gates(selected, r, config, variant=""):
    """[T, E], zero where not selected."""
    import jax.numpy as jnp

    g = jnp.where(selected, r, 0.0)
    if config["norm_topk_prob"] and variant != "gates_not_renormalised":
        g = g / g.sum(-1, keepdims=True)
    return g


def expert_ffn(u, lw, config, held=None, variant=""):
    """The held routed experts' part; `held` defaults to the
    configuration's.  `lw["experts"]` stacks the held experts' weights
    in `held`'s order."""
    import jax.numpy as jnp

    held = held_experts(config) if held is None else held
    selected, r = route(u, lw, config)
    gate = gates(selected, r, config, variant).astype(u.dtype)
    y = jnp.zeros_like(u)
    for slot, e in enumerate(held):
        w = {k: v[slot] for k, v in lw["experts"].items()}
        y = y + jnp.where(selected[:, e, None],
                          gate[:, e, None] * swiglu(u, w), 0.0)
    return y


def layer(x, lw, config, kind, variant=""):
    eps = config["rms_norm_eps"]
    x = x + attention_mixer(rms_norm(x, lw["attn_norm"], eps), lw, config,
                            kind, variant)
    return x + expert_ffn(rms_norm(x, lw["ffn_norm"], eps), lw, config,
                          variant=variant)


def sequence_state(params, ids, config, layer_fn=layer, variant=""):
    """RMSNorm(h_L) [T, C] of ONE sequence, ids [T] int."""
    x = params["emb"][ids]
    for lw, kind in zip(params["layers"], layer_kinds(config)):
        x = layer_fn(x, lw, config, kind, variant)
    return rms_norm(x, params["final_norm"], config["rms_norm_eps"])


def sequence_logits(params, ids, config):
    return sequence_state(params, ids, config) @ params["head"]


def cross_entropy(h, head, labels):
    """Per-token cross-entropy [T] of logits h head, a block of tokens
    at a time; the softmax and what follows float32 whatever the
    layers' dtype (a bfloat16 loss lies on a grid 0.03-0.06 apart at
    ln V: PR 34's finding)."""
    import jax
    import jax.numpy as jnp

    t = h.shape[0]
    block = min(TOKEN_BLOCK, t)
    out = []
    for start in range(0, t, block):
        logp = jax.nn.log_softmax(
            (h[start:start + block] @ head).astype(jnp.float32), axis=-1)
        out.append(-jnp.take_along_axis(
            logp, labels[start:start + block, None], axis=1)[:, 0])
    return jnp.concatenate(out)


def batch_loss(params, ids, labels, config, layer_fn=layer,
               ce_fn=cross_entropy, variant="", precision="highest"):
    """The loss, ids and labels [B, T] int; a function of jax arrays
    that jax.grad differentiates (the tests' gradients)."""
    import jax

    with jax.default_matmul_precision(precision or "default"):
        return sum(
            ce_fn(sequence_state(params, i, config, layer_fn, variant),
                  params["head"], y).sum()
            for i, y in zip(ids, labels)) / ids.size


@functools.lru_cache(maxsize=None)
def _jitted_pieces():
    """`layer` and `cross_entropy` jitted each on its own: the layers of
    one kind have the same shapes and compile ONCE."""
    import jax

    return {"layer_fn": jax.jit(layer, static_argnums=(2, 3, 4)),
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


def _in_dtype(params, dtype):
    import jax
    import jax.numpy as jnp

    if not dtype:
        return params
    return jax.tree_util.tree_map(
        lambda a: a.astype(jnp.dtype(dtype)), params)


def loss(params, batch, config, variant="", dtype=None):
    """The loss of the batch (ids, labels), each [B, T, 1]: the same
    Python loop over layers, its pieces jitted.  dtype "bfloat16": every
    parameter and so every array in that dtype, at the default matmul
    precision (the control: what a lower precision does)."""
    ids, labels = _split(batch)
    return float(batch_loss(_in_dtype(params, dtype), ids, labels,
                            _Static(config), variant=variant,
                            precision=None if dtype else "highest",
                            **_jitted_pieces()))


def logits(params, batch, config, variant="", dtype=None, every=1):
    """The logits of every `every`-th token of the batch, float32
    [B, T / every, V], by the same pieces as `loss` and under the same
    `variant` and `dtype`: what the loss's mean over tokens that all
    sit near ln V hides (tools/reference_controls.py --logits)."""
    import jax
    import jax.numpy as jnp

    ids, _ = _split(batch)
    params = _in_dtype(params, dtype)
    layer_fn = _jitted_pieces()["layer_fn"]
    with jax.default_matmul_precision("default" if dtype else "highest"):
        return jnp.stack([
            (sequence_state(params, i, _Static(config), layer_fn,
                            variant)[::every]
             @ params["head"]).astype(jnp.float32) for i in ids])

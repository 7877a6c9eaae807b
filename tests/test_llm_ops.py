"""The ops of the 2024-26 decoder block (ops/llm_ops.py, ops/pallas_gmm.py,
the flash kernels at two head sizes), one at a time, against plain
numpy / jax.numpy written here.

Tolerances, and why.  Everything the ops state as float32 (norm
statistics, router scores and gates, the mixing coefficients and the
Sinkhorn) is held to 1e-5 or tighter: bfloat16 there would be off by
2^-8 = 3.9e-3, so a rounding to bf16 anywhere in those paths fails the
case.  Kernels in interpret mode run the same float32 arithmetic as
the XLA form in another order: 1e-5.  bf16 operands round once on the
way out: one bf16 ulp, 2^-7 relative at worst.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core.registry import get_op_def

F32_TOL = 1e-5
BF16_ULP = 2.0 ** -7


def _op(name, ins, **attrs):
    od = get_op_def(name)
    return od.compute(ins, od.canonical_attrs(attrs))


# -- RMSNorm, SwiGLU ---------------------------------------------------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_rms_norm_statistic_is_float32(dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 3, (4, 7, 96)), dtype)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 96), jnp.float32)
    y = _op("rms_norm", {"X": x, "Scale": scale}, epsilon=1e-6)["Y"]
    assert y.dtype == x.dtype
    xf = np.asarray(x, np.float64)
    want = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6) \
        * np.asarray(scale, np.float64)
    tol = F32_TOL if dtype == "float32" else BF16_ULP
    np.testing.assert_allclose(np.asarray(y, np.float64), want, rtol=tol,
                               atol=tol * 1e-2)


def test_swiglu():
    rng = np.random.default_rng(1)
    g, u = (jnp.asarray(rng.normal(0, 2, (5, 64)), jnp.float32)
            for _ in range(2))
    out = _op("swiglu", {"Gate": g, "Up": u})["Out"]
    gf = np.asarray(g, np.float64)
    np.testing.assert_allclose(
        out, gf / (1 + np.exp(-gf)) * np.asarray(u, np.float64),
        rtol=F32_TOL, atol=1e-6)


# -- rotary embedding with YaRN frequencies ----------------------------------

def _yarn_by_hand(dim, theta, factor, orig, beta_fast, beta_slow):
    """Peng et al. 2023 as deepseek_v3 computes it, dimension by
    dimension."""
    def correction(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(beta_fast)), 0)
    high = min(math.ceil(correction(beta_slow)), dim - 1)
    out = []
    for i in range(dim // 2):
        plain = theta ** (-2.0 * i / dim)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        out.append(plain * (1 - ramp) + plain / factor * ramp)
    return np.array(out), low, high


def test_yarn_frequencies():
    from paddle_tpu.ops.llm_ops import yarn_inv_freq

    got = yarn_inv_freq(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    want, low, high = _yarn_by_hand(64, 10000.0, 64.0, 4096, 32.0, 1.0)
    # the published numbers: the ramp runs from dimension 10 to 23
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    # fast dimensions are untouched, slow ones divided by the factor
    assert got[0] == pytest.approx(1.0)
    assert got[31] == pytest.approx(10000.0 ** (-62 / 64) / 64, rel=1e-6)
    # factor 1: the plain embedding
    np.testing.assert_allclose(
        yarn_inv_freq(64, 10000.0, 1.0, 4096, 32.0, 1.0),
        10000.0 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)


@pytest.mark.parametrize("rotary_dim", [0, 8])
def test_rotary_rotates_interleaved_pairs(rotary_dim):
    from paddle_tpu.ops.llm_ops import yarn_inv_freq

    rng = np.random.default_rng(2)
    b, t, h, d = 2, 12, 3, 24
    x = jnp.asarray(rng.normal(0, 1, (b, t, h, d)), jnp.float32)
    kw = dict(theta=10000.0, factor=64.0, original_max_position=16,
              beta_fast=32.0, beta_slow=1.0)
    out = np.asarray(_op("rotary_embedding", {"X": x},
                         rotary_dim=rotary_dim, **kw)["Out"])
    rd = rotary_dim or d
    inv = yarn_inv_freq(rd, 10000.0, 64.0, 16, 32.0, 1.0)
    xn = np.asarray(x)
    want = xn.copy()
    for pos in range(t):
        for i in range(rd // 2):
            a, c = xn[:, pos, :, d - rd + 2 * i], \
                xn[:, pos, :, d - rd + 2 * i + 1]
            ang = pos * inv[i]
            want[:, pos, :, d - rd + 2 * i] = \
                a * np.cos(ang) - c * np.sin(ang)
            want[:, pos, :, d - rd + 2 * i + 1] = \
                a * np.sin(ang) + c * np.cos(ang)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    # position 0 is the identity, the leading entries pass through
    np.testing.assert_array_equal(out[:, 0], xn[:, 0])
    np.testing.assert_array_equal(out[..., :d - rd], xn[..., :d - rd])
    # a rotation keeps the norm of every pair, and q.k depends on the
    # distance only
    np.testing.assert_allclose((out ** 2).sum(-1), (xn ** 2).sum(-1),
                               rtol=1e-5)


# pt_rotary (ops/pallas_rotary.py) in interpret mode against the XLA
# form.  (D, rotary_dim, H): 12 heads of 128 are two lane blocks of 768,
# 64 two heads a lane tile, 192 tables of 384 lanes; T 96 is three row
# tiles of 32, so a tile's positions are its own and not the first's
ROTARY_HEADS = {(128, 0): 12, (64, 0): 6, (192, 64): 4, (128, 64): 3}


def _rotary_case(pairing, d, rd, flat, dtype, seed=5):
    """(x, g, attrs of the op, the [B, T, H, D] shape)."""
    rng = np.random.default_rng(seed)
    shape = (2, 96, ROTARY_HEADS[d, rd], d)
    laid = shape[:2] + (shape[2] * d,) if flat else shape
    x, g = (jnp.asarray(rng.normal(0, 1, laid), dtype) for _ in range(2))
    attrs = dict(pairing=pairing, rotary_dim=rd, theta=10000.0,
                 n_head=shape[2] if flat else 0)
    if (d, rd) == (128, 64):        # YaRN's ramp and its factor on cos, sin
        attrs.update(factor=40.0, original_max_position=16, mscale=1.3)
    return x, g, attrs, shape


def _rotary_roundings(x, attrs, shape, back=False):
    """The three float32 values x cos + partner(x) sin may round to, by
    entry, over the kernel's own tables: both products rounded before
    the add (the chip), or either fused into it (this CPU's compiler,
    where its fusions let it; a float32 product is exact in float64)."""
    from paddle_tpu.ops import pallas_rotary
    from paddle_tpu.ops.llm_ops import yarn_inv_freq

    b, t, h, d = shape
    rd = attrs["rotary_dim"] or d
    inv = yarn_inv_freq(rd, attrs["theta"], attrs.get("factor", 1.0),
                        attrs.get("original_max_position", 4096), 32.0, 1.0)
    cos, sin = (np.tile(np.asarray(tab)[:, :d], (1, h))[None]
                for tab in pallas_rotary.tables(
                    t, d, rd, attrs["pairing"], inv,
                    attrs.get("mscale", 1.0), back=back))
    x = np.asarray(x, np.float32).reshape(b, t, h * d)
    period, cut, shift, _ = pallas_rotary.partner_rule(
        d, rd, attrs["pairing"])
    lane = np.arange(h * d)
    partner = x[..., np.where(lane % period < cut, lane + shift,
                              lane - shift) % (h * d)]
    x64, p64 = x.astype(np.float64), partner.astype(np.float64)
    return [x * cos + partner * sin,
            (x64 * cos + partner * sin).astype(np.float32),
            (x * cos + p64 * sin).astype(np.float32)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("flat", [False, True], ids=["4d", "flat"])
@pytest.mark.parametrize("d,rd", sorted(ROTARY_HEADS))
@pytest.mark.parametrize("pairing", ["halves", "interleaved"])
def test_rotary_kernel_is_the_xla_form(pairing, d, rd, flat, dtype):
    """Out and jax.vjp of the op, the kernel against the XLA graph: in
    float32 every entry of either is one of the roundings of the SAME
    two products (so on the chip, which fuses neither, the two are
    equal to the last bit: tools/rotary_price.py holds them to that),
    and the two are within a float32 ulp of each other; in bfloat16
    within one bf16 ulp.  The backward is the forward at the negative
    angle: nothing but Out@GRAD is read."""
    x, g, attrs, shape = _rotary_case(pairing, d, rd, flat, dtype)

    def run(impl):
        out, vjp = jax.vjp(lambda v: _op("rotary_embedding", {"X": v},
                                         impl=impl, **attrs)["Out"], x)
        assert out.shape == x.shape and out.dtype == x.dtype
        return [np.asarray(a.astype(jnp.float32)).reshape(
            shape[:2] + (-1,)) for a in (out, vjp(g)[0])]

    got, want = run("interpret"), run("xla")
    for which, (a, b, of) in enumerate(zip(got, want, (x, g))):
        if dtype == "float32":
            fits = _rotary_roundings(of, attrs, shape, back=bool(which))
            for form in (a, b):
                assert np.any([form == f for f in fits], axis=0).all()
            np.testing.assert_allclose(a, b, rtol=2.0 ** -23, atol=1e-7)
        else:
            np.testing.assert_allclose(a, b, rtol=BF16_ULP, atol=1e-6)
    # the leading entries pass through, position 0 is the identity
    out = got[0].reshape(shape)
    xn = np.asarray(x.astype(jnp.float32)).reshape(shape)
    keep = d - (rd or d)
    np.testing.assert_array_equal(out[..., :keep], xn[..., :keep])
    scale = attrs.get("mscale", 1.0)
    np.testing.assert_allclose(out[:, 0, :, keep:], scale * xn[:, 0, :, keep:],
                               rtol=BF16_ULP if dtype == "bfloat16"
                               else 1e-6)


def test_rotary_falls_back_where_heads_fill_no_lane_tile():
    """The latent attention's one shared key [B, T, 1, 64]: H D is no
    multiple of 128, no chunk of whole lane tiles holds whole heads,
    and the XLA form runs whatever impl was asked for, counted so."""
    from paddle_tpu.ops import pallas_kernels as pk
    from paddle_tpu.ops import pallas_rotary

    def counts():
        return {lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
                if lbl["kernel"] == "rotary"}

    assert pallas_rotary.blocks(64, 64, 64) is None
    assert pallas_rotary.blocks(64, 128, 64) == (64, 128)
    assert pallas_rotary.blocks(40, 128, 64) is None       # no row tile
    x = jnp.asarray(np.random.default_rng(6).normal(0, 1, (2, 64, 1, 64)),
                    jnp.float32)
    before = counts()
    got = _op("rotary_embedding", {"X": x}, impl="pallas")["Out"]
    after = counts()
    assert after.get("xla", 0) == before.get("xla", 0) + 1
    assert after.get("pallas", 0) == before.get("pallas", 0)
    np.testing.assert_array_equal(
        got, _op("rotary_embedding", {"X": x}, impl="xla")["Out"])
    # two heads of 64 fill a lane tile: the kernel
    _op("rotary_embedding", {"X": jnp.tile(x, (1, 1, 2, 1))},
        impl="interpret")
    assert counts().get("interpret", 0) == after.get("interpret", 0) + 1
    with pytest.raises(ValueError, match="n_head"):
        _op("rotary_embedding", {"X": x.reshape(2, 64, 64)})
    with pytest.raises(ValueError, match="n_head"):
        _op("rotary_embedding", {"X": x}, n_head=1)


# -- router ------------------------------------------------------------------

def _route(x, w, bias, **kw):
    out = _op("moe_route", {"X": x, "W": w, "Bias": bias}, **kw)
    return np.asarray(out["TopkIdx"]), np.asarray(out["TopkWeight"])


def test_router_bias_selects_and_does_not_weigh():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(0, 1, (64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (32, 16)), jnp.float32)
    zero = jnp.zeros(16, jnp.float32)
    kw = dict(k=4, routed_scaling_factor=2.0, norm_topk_prob=True)
    idx0, wt0 = _route(x, w, zero, **kw)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w)))
    # selection: the 4 largest scores; gates: the scores, normalised
    want_idx = np.argsort(-s, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.sort(idx0, -1), np.sort(want_idx, -1))
    picked = np.take_along_axis(s, idx0, -1)
    np.testing.assert_allclose(wt0, 2.0 * picked / picked.sum(-1,
                                                              keepdims=True),
                               rtol=F32_TOL)
    # the gates of a token sum to routed_scaling_factor, in float32
    np.testing.assert_allclose(wt0.sum(-1), 2.0, rtol=1e-6)
    assert wt0.dtype == np.float32 and idx0.dtype == np.int32
    # a bias that lifts expert 5 above everything selects it everywhere
    bias = zero.at[5].set(10.0)
    idx1, wt1 = _route(x, w, bias, **kw)
    assert (idx1 == 5).any(-1).all()
    # and weighs nothing: the gates are still the raw scores normalised
    picked1 = np.take_along_axis(s, idx1, -1)
    np.testing.assert_allclose(
        wt1, 2.0 * picked1 / picked1.sum(-1, keepdims=True), rtol=F32_TOL)
    # without normalisation the gate is scaling x score
    _, wt2 = _route(x, w, zero, k=4, routed_scaling_factor=2.0,
                    norm_topk_prob=False)
    np.testing.assert_allclose(wt2, 2.0 * picked, rtol=F32_TOL)


def _group_limited(s, bias, k, n_group, topk_group):
    """The selection by hand, in numpy float64 over ALL experts: a
    group's score the sum of its two largest s + bias, the best
    topk_group groups kept, the k largest s + bias among theirs."""
    r = s + bias
    n, e = r.shape
    per = r.reshape(n, n_group, e // n_group)
    score = np.sort(per, -1)[..., -2:].sum(-1)
    alive = np.zeros((n, n_group), bool)
    np.put_along_axis(alive, np.argsort(-score, -1, kind="stable")
                      [:, :topk_group], True, -1)
    r = np.where(np.repeat(alive, e // n_group, -1), r, -np.inf)
    return np.argsort(-r, -1, kind="stable")[:, :k], alive


@pytest.mark.parametrize("n_group,topk_group,k", [(4, 2, 4), (4, 1, 3),
                                                  (8, 4, 8), (2, 2, 4)])
def test_group_limited_router_against_the_selection_by_hand(
        n_group, topk_group, k):
    rng = np.random.default_rng(7)
    e = 32
    x = jnp.asarray(rng.normal(0, 1, (96, 24)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.4, (24, e)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.2, e), jnp.float32)
    kw = dict(k=k, routed_scaling_factor=2.5, norm_topk_prob=True)
    before = _impl_counts()
    idx, wt = _route(x, w, bias, n_group=n_group, topk_group=topk_group,
                     **kw)
    assert _impl_since(before) == {
        ("moe_route_scoring", "sigmoid"): 1,
        ("moe_route_groups", "%dof%d" % (topk_group, n_group)): 1}
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w)))
    want_idx, alive = _group_limited(s, np.asarray(bias, np.float64), k,
                                     n_group, topk_group)
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(want_idx, -1))
    # every selected expert lies in a kept group, and with fewer groups
    # kept than there are the limit binds: the selection over all
    # experts is another for some token
    assert np.take_along_axis(alive, idx // (e // n_group), -1).all()
    free, _ = _route(x, w, bias, **kw)
    differs = (np.sort(free, -1) != np.sort(idx, -1)).any(-1)
    assert differs.any() == (topk_group < n_group)
    # the gates are the raw scores of the selected, normalised: the
    # bias and the groups select and do not weigh
    picked = np.take_along_axis(s, idx, -1)
    np.testing.assert_allclose(
        wt, 2.5 * picked / picked.sum(-1, keepdims=True), rtol=F32_TOL)


def test_router_without_groups_is_the_router_of_before():
    """n_group 1 (the default, and what `xing4` and `deepseek-v2-lite`
    pass): the outputs of a call that names no group, bit for bit, and
    no `moe_route_groups` count."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 1, (64, 32)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (32, 16)), jnp.float32)
    bias = jnp.asarray(rng.normal(0, 0.1, 16), jnp.float32)
    for scoring in ("sigmoid", "softmax"):
        kw = dict(k=4, routed_scaling_factor=2.0, norm_topk_prob=True,
                  scoring_func=scoring)
        plain = _op("moe_route", {"X": x, "W": w, "Bias": bias}, **kw)
        before = _impl_counts()
        named = _op("moe_route", {"X": x, "W": w, "Bias": bias},
                    n_group=1, topk_group=1, **kw)
        assert _impl_since(before) == {("moe_route_scoring", scoring): 1}
        for slot in ("TopkIdx", "TopkWeight", "Scores"):
            assert np.asarray(plain[slot]).tobytes() \
                == np.asarray(named[slot]).tobytes()
    # the lowered computation holds no group step
    from paddle_tpu.core.registry import get_op_def

    op = get_op_def("moe_route")
    attrs = op.canonical_attrs(dict(k=4))
    text = jax.jit(lambda x, w, b: op.compute(
        {"X": x, "W": w, "Bias": b}, attrs)).lower(x, w, bias).as_text(
            debug_info=True)
    assert "pt_moe_route" in text and "pt_moe_route_groups" not in text


@pytest.mark.parametrize("bad", [dict(n_group=3), dict(n_group=4,
                                                       topk_group=5),
                                 dict(n_group=4, topk_group=1, k=5),
                                 dict(n_group=16, topk_group=4)])
def test_group_limits_that_cannot_give_k_raise(bad):
    x, w = jnp.zeros((4, 8)), jnp.zeros((8, 16))
    kw = dict(dict(k=4, n_group=1, topk_group=1), **bad)
    with pytest.raises(ValueError, match="cannot give k"):
        _route(x, w, jnp.zeros(16), **kw)


def _impl_counts():
    from paddle_tpu.ops import pallas_kernels as pk

    return {(lbl["kernel"], lbl["impl"]): v
            for lbl, v in pk._M_KERNEL_IMPL.items()}


def _impl_since(before):
    return {k: v - before.get(k, 0) for k, v in _impl_counts().items()
            if v - before.get(k, 0)}


def test_router_scores_ignore_the_activation_dtype():
    """Under AMP the router's input arrives rounded to bf16; the scores
    of that input are still float32: the gates sum to the scaling
    factor to 1e-6, which bf16 scores (2^-8) would not."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.normal(0, 1, (32, 64)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(0, 0.1, (64, 8)), jnp.float32)
    _, wt = _route(x, w, jnp.zeros(8), k=2, routed_scaling_factor=2.0,
                   norm_topk_prob=True)
    np.testing.assert_allclose(wt.sum(-1), 2.0, rtol=1e-6)


# -- the held experts ----------------------------------------------------------

def test_softmax_router_by_hand():
    """scoring_func "softmax" (DeepSeek-V2): logits ln 1, ln 2, ln 3,
    ln 4 give scores 0.1, 0.2, 0.3, 0.4.  The two largest are taken as
    they are: the gates sum to 0.7, not to 1, times the scaling
    factor; `Scores` holds all four and sums to 1; no bias is read."""
    x = jnp.asarray([[1.0, 0.0], [0.0, 1.0]], jnp.float32)
    w = jnp.log(jnp.asarray([[1.0, 2.0, 3.0, 4.0],
                             [4.0, 1.0, 1.0, 4.0]], jnp.float32))
    out = _op("moe_route", {"X": x, "W": w}, k=2, norm_topk_prob=False,
              routed_scaling_factor=1.0, scoring_func="softmax")
    np.testing.assert_allclose(out["Scores"],
                               [[0.1, 0.2, 0.3, 0.4], [0.4, 0.1, 0.1, 0.4]],
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(out["Scores"]).sum(-1), 1.0,
                               rtol=1e-6)
    np.testing.assert_array_equal(np.sort(out["TopkIdx"], -1),
                                  [[2, 3], [0, 3]])
    np.testing.assert_allclose(np.sort(out["TopkWeight"], -1),
                               [[0.3, 0.4], [0.4, 0.4]], rtol=1e-6)
    assert float(np.asarray(out["TopkWeight"]).sum(-1).max()) < 1.0
    assert out["Scores"].dtype == jnp.float32
    # scaled, and renormalised only where the config asks for it
    scaled = _op("moe_route", {"X": x, "W": w}, k=2, norm_topk_prob=False,
                 routed_scaling_factor=2.5, scoring_func="softmax")
    np.testing.assert_allclose(np.sort(scaled["TopkWeight"], -1),
                               [[0.75, 1.0], [1.0, 1.0]], rtol=1e-6)
    normed = _op("moe_route", {"X": x, "W": w}, k=2, norm_topk_prob=True,
                 scoring_func="softmax")
    np.testing.assert_allclose(np.asarray(normed["TopkWeight"]).sum(-1),
                               1.0, rtol=1e-6)
    # a bias, if one is bound, selects nothing under softmax
    biased = _op("moe_route",
                 {"X": x, "W": w, "Bias": jnp.asarray([9.0, 0, 0, 0])},
                 k=2, norm_topk_prob=False, scoring_func="softmax")
    np.testing.assert_array_equal(biased["TopkIdx"], out["TopkIdx"])
    with pytest.raises(ValueError, match="scoring_func"):
        _op("moe_route", {"X": x, "W": w}, k=2, scoring_func="tanh")


def test_sigmoid_router_scores_are_the_sigmoids():
    """The default scoring keeps its selection and gates and gains the
    `Scores` output: the sigmoids of all experts, which do not sum to
    1."""
    rng = np.random.default_rng(8)
    x = jnp.asarray(rng.normal(0, 1, (16, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (8, 6)), jnp.float32)
    out = _op("moe_route", {"X": x, "W": w, "Bias": jnp.zeros(6)}, k=2)
    s = 1 / (1 + np.exp(-np.asarray(x, np.float64) @ np.asarray(w)))
    np.testing.assert_allclose(out["Scores"], s, rtol=F32_TOL)
    idx, wt = _route(x, w, jnp.zeros(6), k=2)
    np.testing.assert_array_equal(idx, out["TopkIdx"])
    np.testing.assert_array_equal(wt, out["TopkWeight"])


def test_softmax_router_gradient_reaches_unselected_experts_by_scores():
    """`TopkWeight` carries the scores of the k selected experts and
    nothing of the others: its gradient is that of the selected scores
    summed.  The scores of the experts a token did NOT select reach a
    loss only through `Scores`: a cotangent on those entries alone
    gives the gradient of the unselected scores summed.  Both against
    jax.grad of the softmax written out."""
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(0, 1, (12, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.5, (8, 6)), jnp.float32)

    def route(w):
        return _op("moe_route", {"X": x, "W": w}, k=2,
                   norm_topk_prob=False, scoring_func="softmax")

    chosen = np.zeros((12, 6), bool)
    np.put_along_axis(chosen, np.asarray(route(w)["TopkIdx"]), True, -1)
    rest = jnp.asarray(~chosen, jnp.float32)

    def by_hand(w, weights):
        return jnp.sum(jax.nn.softmax(x @ w, -1) * weights)

    with jax.default_matmul_precision("highest"):
        g_gates = jax.grad(lambda w: jnp.sum(route(w)["TopkWeight"]))(w)
        want_gates = jax.grad(by_hand)(w, 1.0 - rest)
        g_rest = jax.grad(lambda w: jnp.sum(route(w)["Scores"] * rest))(w)
        want_rest = jax.grad(by_hand)(w, rest)
    np.testing.assert_allclose(g_gates, want_gates, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(g_rest, want_rest, rtol=1e-4, atol=1e-6)
    # the scores sum to 1, so the two parts cancel: neither is zero
    np.testing.assert_allclose(g_gates + g_rest, 0.0, atol=1e-5)
    assert float(jnp.abs(g_rest).max()) > 1e-2


def _experts_case(seed, n=96, c=128, w=256, e=8, k=2, held=(2, 5, 7),
                  empty=None, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    idx = np.stack([rng.permutation(e)[:k] for _ in range(n)]) \
        .astype(np.int32)
    if empty is not None:          # an expert nobody is routed to
        idx = np.where(idx == empty, (empty + 1) % e, idx)
    g = len(held)
    return {
        "X": jnp.asarray(rng.normal(0, 1, (n, c)), dtype),
        "TopkIdx": jnp.asarray(idx),
        "TopkWeight": jnp.asarray(rng.uniform(0.2, 1, (n, k)), jnp.float32),
        "WGate": jnp.asarray(rng.normal(0, 0.1, (g, c, w)), dtype),
        "WUp": jnp.asarray(rng.normal(0, 0.1, (g, c, w)), dtype),
        "WDown": jnp.asarray(rng.normal(0, 0.1, (g, w, c)), dtype)}


def _dense_experts(ins, held):
    """A loop over the held experts with a mask over all tokens."""
    x = ins["X"].astype(jnp.float32)
    y = jnp.zeros_like(x)
    for slot, e in enumerate(held):
        gate = jnp.where(ins["TopkIdx"] == e, ins["TopkWeight"], 0).sum(-1)
        h = jax.nn.silu(x @ ins["WGate"][slot].astype(jnp.float32)) \
            * (x @ ins["WUp"][slot].astype(jnp.float32))
        y = y + gate[:, None] * (h @ ins["WDown"][slot].astype(jnp.float32))
    return y


DIFF = ("X", "TopkWeight", "WGate", "WUp", "WDown")


@pytest.mark.parametrize("impl,block_m,empty", [
    ("xla", 32, None), ("interpret", 32, None), ("interpret", 128, None),
    ("interpret", 32, 5), ("xla", 64, 5)])
def test_moe_experts_forward_and_gradients(impl, block_m, empty):
    held = (2, 5, 7)
    ins = _experts_case(1, held=held, empty=empty)

    def run(diff):
        out = _op("moe_experts", {**ins, **diff}, held=list(held),
                  block_m=block_m, impl=impl)["Out"]
        return (out * jnp.sin(out)).sum(), out

    def ref(diff):
        out = _dense_experts({**ins, **diff}, held)
        return (out * jnp.sin(out)).sum(), out

    diff = {k: ins[k] for k in DIFF}
    (_, out), grads = jax.value_and_grad(run, has_aux=True)(diff)
    (_, want), want_grads = jax.value_and_grad(ref, has_aux=True)(diff)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    for k in DIFF:
        scale = float(jnp.abs(want_grads[k]).max())
        np.testing.assert_allclose(grads[k], want_grads[k], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=k)
    # a token none of whose experts is held gets exactly nothing
    mine = np.isin(np.asarray(ins["TopkIdx"]), held).any(-1)
    assert (~mine).any()
    assert not np.asarray(out)[~mine].any()


def test_moe_experts_drops_no_token_when_all_go_to_one_expert():
    """The worst case the static row count covers: every pair routed
    to ONE held expert."""
    ins = _experts_case(2, n=64, k=2, e=4, held=(0, 1))
    ins["TopkIdx"] = jnp.zeros((64, 2), jnp.int32).at[:, 1].set(3)
    out = _op("moe_experts", ins, held=[0, 1], block_m=32,
              impl="interpret")["Out"]
    np.testing.assert_allclose(out, _dense_experts(ins, (0, 1)),
                               rtol=1e-4, atol=1e-5)


def test_moe_experts_bf16_operands():
    ins = _experts_case(3, dtype=jnp.bfloat16)
    out = _op("moe_experts", ins, held=[2, 5, 7], block_m=32,
              impl="interpret")["Out"]
    assert out.dtype == jnp.bfloat16
    want = _dense_experts(ins, (2, 5, 7))
    # three bf16 roundings between the products (h, act, y)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=4 * BF16_ULP,
                               atol=BF16_ULP * float(jnp.abs(want).max()))


def _idx_with_sizes(sizes, k):
    """[N, k] expert ids, k distinct a token, that route exactly
    sizes[e] pairs to expert e (sum(sizes) = N k)."""
    left = np.asarray(sizes).copy()
    idx = []
    while left.sum():
        picks = np.argsort(-left, kind="stable")[:k]
        assert (left[picks] > 0).all(), "no such routing"
        left[picks] -= 1
        idx.append(picks)
    return np.asarray(idx, np.int32)


def _layout_case(layout, dtype=jnp.float32):
    """(ins, held) at block_m 32.  one_tile: one held expert and a
    tile's worth of pairs; sparse: 34 tiles, at most 6 live; full: all
    experts held and every group one row into a fresh tile, 9 of 10
    tiles live: with a chunk of 8 tiles the loop runs to M, and its
    second chunk starts early not to pass it."""
    if layout == "sparse":
        held = (2, 5)
        return _experts_case(7, n=256, e=16, k=4, held=held,
                             dtype=dtype), held
    if layout == "one_tile":
        held = (2,)
        ins = _experts_case(11, n=96, e=8, k=2, held=held, dtype=dtype)
        assert 0 < int((np.asarray(ins["TopkIdx"]) == 2).sum()) <= 32
        return ins, held
    held = (0, 1, 2, 3)
    idx = _idx_with_sizes((65, 33, 33, 33), 2)
    ins = _experts_case(12, n=len(idx), e=4, k=2, held=held, dtype=dtype)
    ins["TopkIdx"] = jnp.asarray(idx)
    return ins, held


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("layout", ["one_tile", "sparse", "full"])
def test_moe_experts_at_three_layouts(layout, impl, dtype):
    """Output and all five gradients against the dense loop, from one
    live tile to (all but) every tile live: the row work between the
    kernels runs as many chunks as the layout has live rows."""
    from paddle_tpu.ops.llm_ops import _CHUNK_TILES, _group_layout

    ins, held = _layout_case(layout, jnp.dtype(dtype))
    lay = _group_layout(ins["TopkIdx"], held, 32)
    tiles, live = lay["tile_group"].shape[0], int(lay["n_active"][0])
    want_tiles = {"one_tile": (7, 1), "sparse": (34, live),
                  "full": (10, 9)}[layout]
    assert (tiles, live) == want_tiles and live <= 9
    if layout == "full":        # the last chunk is the clamped one
        assert tiles % _CHUNK_TILES and live > _CHUNK_TILES

    def run(diff):
        out = _op("moe_experts", {**ins, **diff}, held=list(held),
                  block_m=32, impl=impl)["Out"].astype(jnp.float32)
        return (out * jnp.sin(out)).sum(), out

    def ref(diff):
        out = _dense_experts({**ins, **diff}, held)
        return (out * jnp.sin(out)).sum(), out

    diff = {k: ins[k] for k in DIFF}
    (_, out), grads = jax.value_and_grad(run, has_aux=True)(diff)
    (_, want), want_grads = jax.value_and_grad(ref, has_aux=True)(diff)
    # float32: the products' order; bf16: the roundings between the
    # products (three forward, five more backward)
    rtol, atol = (1e-4, 1e-5) if dtype == "float32" \
        else (4 * BF16_ULP, 2 * BF16_ULP)
    np.testing.assert_allclose(out, want, rtol=rtol,
                               atol=atol * float(jnp.abs(want).max()))
    for k in DIFF:
        got = np.asarray(grads[k], np.float32)
        assert np.isfinite(got).all(), k
        scale = float(jnp.abs(want_grads[k]).max())
        np.testing.assert_allclose(
            got, np.asarray(want_grads[k], np.float32), rtol=rtol,
            atol=atol * scale * (1 if dtype == "float32" else 4),
            err_msg=k)


def _routed_experts_over_all_rows():
    """The routed experts as they were before their row work followed
    n_active (PR 36's tree): every gather, SwiGLU and sum over all M
    rows of the worst-case layout, the pairs gathered as one float32
    [N, k, C].  What the chunked form is held to, bit for bit."""
    from paddle_tpu.ops.llm_ops import _F32
    from paddle_tpu.ops.pallas_gmm import _silu_and_grad, gmm, tgmm

    def gather_rows(x, index, live):
        return jnp.where(live[:, None], jnp.take(x, index, axis=0), 0)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
    def routed(x, gate, wg, wu, wd, lay, k, tm, impl):
        return fwd(x, gate, wg, wu, wd, lay, k, tm, impl)[0]

    def fwd(x, gate, wg, wu, wd, lay, k, tm, impl):
        tg, na = lay["tile_group"], lay["n_active"]
        xs = gather_rows(x, lay["row_pair"] // k, lay["row_live"])
        hg = gmm(xs, wg, tg, na, tm, impl)
        hu = gmm(xs, wu, tg, na, tm, impl)
        act = (_silu_and_grad(hg.astype(_F32))[0] * hu.astype(_F32)) \
            .astype(x.dtype)
        ys = gmm(act, wd, tg, na, tm, impl)
        picked = jnp.where(
            lay["mine"][..., None],
            jnp.take(ys, jnp.where(lay["mine"], lay["dest"], 0),
                     axis=0).astype(_F32), 0.0)
        out = jnp.sum(picked * gate[..., None], axis=1).astype(x.dtype)
        return out, (x, gate, wg, wu, wd, lay, xs, hg, hu, ys)

    def bwd(k, tm, impl, res, g_out):
        x, gate, wg, wu, wd, lay, xs, hg, hu, ys = res
        tg, na = lay["tile_group"], lay["n_active"]
        n_groups = wg.shape[0]
        mine, dest = lay["mine"], jnp.where(lay["mine"], lay["dest"], 0)
        gf = g_out.astype(_F32)
        picked = jnp.where(mine[..., None],
                           jnp.take(ys, dest, axis=0).astype(_F32), 0.0)
        d_gate = jnp.sum(picked * gf[:, None, :], axis=-1)
        row_gate = jnp.where(lay["row_live"], jnp.take(
            gate.reshape(-1), lay["row_pair"]), 0.0)
        g_ys = (gather_rows(gf, lay["row_pair"] // k, lay["row_live"])
                * row_gate[:, None]).astype(x.dtype)
        silu, dsilu = _silu_and_grad(hg.astype(_F32))
        act = (silu * hu.astype(_F32)).astype(x.dtype)
        d_wd = tgmm(act, g_ys, tg, na, tm, n_groups, impl)
        g_act = gmm(g_ys, wd, tg, na, tm, impl, transpose_rhs=True) \
            .astype(_F32)
        g_hu = (g_act * silu).astype(x.dtype)
        g_hg = (g_act * hu.astype(_F32) * dsilu).astype(x.dtype)
        d_wg = tgmm(xs, g_hg, tg, na, tm, n_groups, impl)
        d_wu = tgmm(xs, g_hu, tg, na, tm, n_groups, impl)
        g_xs = gmm(g_hg, wg, tg, na, tm, impl, transpose_rhs=True) \
            .astype(_F32) + gmm(g_hu, wu, tg, na, tm, impl,
                                transpose_rhs=True).astype(_F32)
        d_x = jnp.sum(jnp.where(mine[..., None],
                                jnp.take(g_xs, dest, axis=0), 0.0),
                      axis=1).astype(x.dtype)
        return d_x, d_gate.astype(gate.dtype), d_wg, d_wu, d_wd, None

    routed.defvjp(fwd, bwd)
    return routed


@pytest.mark.parametrize("layout", ["sparse", "full"])
def test_moe_experts_row_chunks_keep_the_arithmetic(layout):
    """Float32 on the CPU: Out, d x and the three weight gradients are
    the all-rows form's to the last bit (the same rows, products and
    sums in the same order); d gate, whose sum over C moved from the
    pairs to the rows, to 1e-6.  One primitive at a time
    (disable_jit): inside a jit the CPU compiler contracts a product
    and a sum into one rounding where a fusion lets it, and the two
    forms fuse differently (d W_gate moved by 7e-8 of its largest
    entry under jit)."""
    from paddle_tpu.ops.llm_ops import _group_layout, _routed_experts

    ins, held = _layout_case(layout)
    k = ins["TopkIdx"].shape[1]
    lay = _group_layout(ins["TopkIdx"], held, 32)
    args = (ins["X"], ins["TopkWeight"], ins["WGate"], ins["WUp"],
            ins["WDown"])

    def run(routed):
        def loss(*a):
            out = routed(*a, lay, k, 32, "xla")
            return (out * jnp.sin(out)).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return (out,) + grads

    with jax.disable_jit():
        got = run(_routed_experts)
        want = run(_routed_experts_over_all_rows())
    for name, a, b in zip(("Out",) + DIFF, got, want):
        if name == "TopkWeight":
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6 * float(
                jnp.abs(b).max()), err_msg=name)
        else:
            np.testing.assert_array_equal(a, b, err_msg=name)


def _eqns_outside_loops(jaxpr, derived):
    """(equation, derived) for every equation of a jaxpr that is not in
    a `while` body, looking through call-like equations (pjit,
    custom_vjp_call, ...); `derived(var)` says whether a variable
    depends on the marked inputs."""
    known = set(v for v in jaxpr.invars if derived(v))

    def dep(v):
        return not isinstance(v, jax.extend.core.Literal) and v in known

    for eqn in jaxpr.eqns:
        if any(dep(v) for v in eqn.invars):
            known.update(eqn.outvars)
        subs = [] if eqn.primitive.name in ("while", "pallas_call") \
            else list(jax.core.jaxprs_in_params(eqn.params))
        if not subs:
            yield eqn, dep
        for sub in subs:
            marked = {b for a, b in zip(eqn.invars, sub.invars) if dep(a)} \
                if len(sub.invars) == len(eqn.invars) else \
                set(sub.invars if any(dep(v) for v in eqn.invars) else ())
            yield from _eqns_outside_loops(sub, marked.__contains__)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _all_eqns(sub)


@pytest.mark.parametrize("pass_", ["forward", "backward"])
def test_moe_experts_row_work_is_bounded_by_n_active(pass_):
    """Beside test_gmm_grid_is_bounded_by_n_active: at a sparse layout
    (34 tiles, at most 6 live) nothing outside a loop yields an array
    of M rows but a kernel, an unwritten buffer (a kernel too) and the
    loops themselves, and every loop's condition reads a value derived
    from n_active.  Code that goes back to walking the worst case over
    all rows fails here.  And no [N, k, C] array is formed."""
    from paddle_tpu.ops.llm_ops import _group_layout, _routed_experts
    from paddle_tpu.ops.pallas_moe_combine import combine_plan

    ins, held = _layout_case("sparse")
    n, k = ins["TopkIdx"].shape
    c = ins["X"].shape[1]
    lay = _group_layout(ins["TopkIdx"], held, 32)
    m = lay["row_pair"].shape[0]
    assert m == 34 * 32 and m not in (n, n * k, c, ins["WGate"].shape[2])
    # as moe_experts hands it on: with the combine kernel's plan
    lay["combine"] = combine_plan(lay["dest"], lay["slot"], len(held), c, m)
    args = (ins["X"], ins["TopkWeight"], ins["WGate"], ins["WUp"],
            ins["WDown"])

    def forward(n_active, *a):
        return _routed_experts(*a, {**lay, "n_active": n_active}, k, 32,
                               "interpret")

    def backward(n_active, *a):
        out, vjp = jax.vjp(lambda *a: forward(n_active, *a), *a)
        return vjp(out)

    jaxpr = jax.make_jaxpr(forward if pass_ == "forward" else backward)(
        lay["n_active"], *args).jaxpr
    n_active = jaxpr.invars[0]
    loops = kernels = 0
    for eqn, derived in _eqns_outside_loops(jaxpr, lambda v: v is n_active):
        name = eqn.primitive.name
        if name == "while":
            loops += 1
            # the loop's condition reads the trip count, and that is
            # computed from n_active
            n_cond = eqn.params["cond_nconsts"]
            n_body = eqn.params["body_nconsts"]
            cond = eqn.params["cond_jaxpr"].jaxpr
            read = {v for e in cond.eqns for v in e.invars
                    if not isinstance(v, jax.extend.core.Literal)}
            operands = eqn.invars[:n_cond] + eqn.invars[n_cond + n_body:]
            assert any(derived(a) for a, b in zip(operands, cond.invars)
                       if b in read), eqn
            continue
        kernels += name == "pallas_call"
        for v in eqn.outvars:
            shape = getattr(v.aval, "shape", ())
            assert name == "pallas_call" or not shape or shape[0] != m, \
                "%s yields %s outside a loop" % (name, v.aval)
    # forward: the gather's loop, its buffer, 3 grouped matmuls, the
    # combine; backward: those again (the vjp runs the forward), then
    # the cotangent's loop over 2 buffers, d x's sum over 1, 6 grouped
    # matmuls and d x's combine.  SwiGLU and its gradient are inside
    # kernels
    assert (loops, kernels) == ((1, 5) if pass_ == "forward" else (3, 15))
    assert [e.params["name"] for e in _pallas_calls(jaxpr)].count(
        "pt_moe_combine") == (1 if pass_ == "forward" else 2)
    for eqn in _all_eqns(jaxpr):
        for v in eqn.outvars:
            assert getattr(v.aval, "shape", ()) != (n, k, c), eqn


# (tile_group, n_active): a layout with a dead tail, and a sparse one,
# 2 of 40 tiles live.  As _group_layout makes them: every group has a
# live tile, and the tail carries the last group's id.
GMM_LAYOUTS = {
    "4_of_6": ([0, 0, 1, 2, 2, 2], 4),
    "5_of_6": ([0, 0, 1, 2, 2, 2], 5),
    "2_of_40": ([0, 1] + [1] * 38, 2),
    # an expert owns several consecutive live tiles (its weight block
    # stays where it is from one to the next), and n_active ends in the
    # middle of the last group
    "9_of_12": ([0, 0, 0, 0, 1, 1, 1, 2, 2, 2, 2, 2], 9)}

# (k, n) of a call: the depth and the output width.  1,408 = 11 x 128 is
# dsv2's expert width, which no block but 128 and the whole divides.
GMM_WIDTHS = {"256x128": (256, 128), "1408_as_n": (256, 1408),
              "1408_as_k": (1408, 256)}


def _small_budget(monkeypatch, pg, nbytes, kernel):
    """The rule under a VMEM budget a tile of 16 rows can exceed, so
    that the interpreted call splits an axis as a call of 256 rows does
    on the chip.  Returns the kernel's entry without its jit: no cached
    trace from another budget is read, and none is left behind."""
    monkeypatch.setattr(pg, "_VMEM_BUDGET", nbytes)
    return getattr(pg, kernel).__wrapped__


@pytest.mark.parametrize("layout,widths,budget", [
    ("4_of_6", "256x128", None), ("2_of_40", "256x128", None),
    ("9_of_12", "1408_as_n", None), ("9_of_12", "1408_as_k", None),
    ("4_of_6", "1408_as_n", None), ("2_of_40", "1408_as_k", None),
    # 1,408 whole and the other axis split in two: (1408, 128) blocks of
    # a [256, 1408] weight, and (128, 1408) of a [1408, 256] one
    ("9_of_12", "1408_as_n", 2 << 20), ("9_of_12", "1408_as_k", 2 << 20)])
@pytest.mark.parametrize("transpose_rhs", [False, True])
def test_gmm_kernel_skips_inactive_tiles(transpose_rhs, layout, widths,
                                         budget, monkeypatch):
    from paddle_tpu.ops import pallas_gmm as pg

    rng = np.random.default_rng(5)
    groups, live_tiles = GMM_LAYOUTS[layout]
    (k, n), tm, tiles, g = GMM_WIDTHS[widths], 16, len(groups), \
        groups[-1] + 1
    call = pg.gmm_pallas
    if budget:
        call = _small_budget(monkeypatch, pg, budget, "gmm_pallas")
        tn, tk = pg._tiles("gmm", k, n, tm, 4)
        assert 1408 in (tn, tk) and (n // tn) * (k // tk) == 2
    lhs = jnp.asarray(rng.normal(0, 1, (tiles * tm, k)), jnp.float32)
    rhs = jnp.asarray(rng.normal(0, 1, (g, n, k) if transpose_rhs
                                 else (g, k, n)), jnp.float32)
    tile_group = jnp.asarray(groups, jnp.int32)
    n_active = jnp.asarray([live_tiles], jnp.int32)
    got = call(lhs, rhs, tile_group, n_active, tm,
               transpose_rhs=transpose_rhs, interpret=True)
    want = pg.gmm_xla(lhs, rhs, tile_group, n_active, tm, transpose_rhs)
    live = live_tiles * tm
    np.testing.assert_allclose(got[:live], want[:live], rtol=1e-5,
                               atol=3e-4)
    t = live_tiles - 1                          # the last live tile
    w = np.asarray(rhs[groups[t]])
    by_hand = np.asarray(lhs[t * tm:live]) @ (w.T if transpose_rhs else w)
    np.testing.assert_allclose(got[t * tm:live], by_hand, rtol=1e-4,
                               atol=3e-4)


@pytest.mark.parametrize("layout,widths,budget", [
    ("5_of_6", "128x256", None), ("2_of_40", "128x256", None),
    ("9_of_12", "1408_as_n", None), ("9_of_12", "1408_as_k", None),
    ("5_of_6", "1408_as_k", None), ("2_of_40", "1408_as_n", None),
    # the [256, 1408] and [1408, 256] gradients in two blocks each,
    # 1,408 whole: what a tile of 256 rows gets at [2048, 1408]
    ("9_of_12", "1408_as_n", 4 << 20), ("9_of_12", "1408_as_k", 4 << 20)])
def test_tgmm_kernel_sums_a_groups_tiles(layout, widths, budget,
                                         monkeypatch):
    from paddle_tpu.ops import pallas_gmm as pg

    rng = np.random.default_rng(6)
    groups, live_tiles = GMM_LAYOUTS[layout]
    (k, n), tm, tiles, g = {**GMM_WIDTHS, "128x256": (128, 256)}[widths], \
        16, len(groups), groups[-1] + 1
    call = pg.tgmm_pallas
    if budget:
        call = _small_budget(monkeypatch, pg, budget, "tgmm_pallas")
        tn, tk = pg._tiles("tgmm", k, n, tm, 4)
        assert 1408 in (tn, tk) and (n // tn) * (k // tk) == 2
    lhs = jnp.asarray(rng.normal(0, 1, (tiles * tm, k)), jnp.float32)
    grad = jnp.asarray(rng.normal(0, 1, (tiles * tm, n)), jnp.float32)
    tile_group = jnp.asarray(groups, jnp.int32)
    n_active = jnp.asarray([live_tiles], jnp.int32)   # the tail is skipped
    got = call(lhs, grad, tile_group, n_active, tm, g, interpret=True)
    want = pg.tgmm_xla(lhs, grad, tile_group, n_active, tm, g)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    # the last group's active tiles, and none of the tail it shares a
    # group id with
    first = groups.index(groups[live_tiles - 1])
    rows = slice(first * tm, live_tiles * tm)
    np.testing.assert_allclose(
        got[groups[first]],
        np.asarray(lhs[rows]).T @ np.asarray(grad[rows]),
        rtol=1e-4, atol=1e-4)


# what the kernels form on their own blocks (ISSUE 50): the form, and
# which of an expert layer's hidden width C and expert width W is the
# call's k (a gmm's contraction, a tgmm's lhs axis) and which its n
GMM_FORMS = {"gmm_swiglu": "wc", "tgmm_swiglu": "wc",
             "gmm_swiglu_grad": "cw"}


def _form_case(form, k, n, groups, seed=8, dtype=jnp.float32):
    """(operands of pallas_gmm's entry for `form`, as keywords) at tiles
    of 16 rows: the row arrays hold values everywhere, live or not."""
    rng = np.random.default_rng(seed)
    tm, g = 16, groups[-1] + 1
    m = len(groups) * tm

    def rows(width):
        return jnp.asarray(rng.normal(0, 1, (m, width)), dtype)

    def weights(*shape):
        return jnp.asarray(rng.normal(0, 0.1, (g,) + shape), dtype)

    if form == "gmm_swiglu":            # ys = silu(hg) hu @ wd
        return dict(lhs=(rows(k), rows(k)), rhs=weights(k, n))
    if form == "tgmm_swiglu":           # d wd = (silu(hg) hu)^T @ d ys
        return dict(lhs=(rows(k), rows(k)), grad=rows(n))
    # d act = d ys @ wd^T -> d hg, d hu
    return dict(lhs=rows(k), rhs=weights(n, k), transpose_rhs=True,
                gated=(rows(n), rows(n)))


def _run_form(pg, case, tile_group, n_active, impl, g):
    case = dict(case)
    if "grad" in case:
        return pg.tgmm(case["lhs"], case["grad"], tile_group, n_active, 16,
                       g, impl)
    return pg.gmm(case.pop("lhs"), case.pop("rhs"), tile_group, n_active,
                  16, impl, **case)


@pytest.mark.parametrize("layout,widths", [
    # W = 1,408 = 11 x 128 whole, beside C = 256
    ("9_of_12", "1408_whole"),
    # n_active 1 (one group, one live tile) and every tile live
    ("1_of_6", "1408_whole"), ("6_of_6", "1408_whole"),
    # the axis SwiGLU's blocks lie along in two blocks (a gmm's
    # contraction: the prologue, and the epilogue's accumulator, over
    # two grid steps; tgmm_swiglu's output rows)
    ("9_of_12", "split"), ("1_of_6", "split"), ("6_of_6", "split")])
@pytest.mark.parametrize("form", sorted(GMM_FORMS))
def test_gmm_kernels_form_swiglu_and_its_gradient(
        form, layout, widths, monkeypatch):
    """Interpret mode against the XLA form, over the live rows: the
    kernels' prologue (SwiGLU of a pair of blocks as the left operand)
    and epilogue (SwiGLU's gradient from the float32 accumulator, two
    outputs)."""
    from paddle_tpu.ops import pallas_gmm as pg

    groups, live_tiles = {**GMM_LAYOUTS, "1_of_6": ([0] * 6, 1),
                          "6_of_6": ([0, 0, 1, 2, 2, 2], 6)}[layout]
    if widths == "split":
        k, n = 512, 128
        # a budget that holds the call with k in halves and not whole
        monkeypatch.setattr(pg, "_VMEM_BUDGET",
                            pg._vmem_bytes(form, 16, 256, 128, 4))
        assert pg._tiles(form, k, n, 16, 4) == (128, 256)
    else:
        c, w = 256, 1408
        k, n = (w, c) if GMM_FORMS[form] == "wc" else (c, w)
        assert pg._tiles(form, k, n, 16, 4) == (n, k)
    g = groups[-1] + 1
    case = _form_case(form, k, n, groups)
    tile_group = jnp.asarray(groups, jnp.int32)
    n_active = jnp.asarray([live_tiles], jnp.int32)
    with jax.disable_jit():     # the patched budget, not a cached trace
        got = _run_form(pg, case, tile_group, n_active, "interpret", g)
    want = _run_form(pg, case, tile_group, n_active, "xla", g)
    live = slice(None) if form == "tgmm_swiglu" \
        else slice(0, live_tiles * 16)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a[live], b[live], rtol=1e-5, atol=3e-4)


@pytest.mark.parametrize("impl", ["interpret", "xla"])
@pytest.mark.parametrize("form", sorted(GMM_FORMS))
def test_swiglu_in_the_kernels_is_no_further_from_float32_in_bf16(
        form, impl):
    """From bfloat16 operands, against the same products in float32
    with nothing rounded on the way: each form is at least as close as
    the array code between two kernels was (before ISSUE 50: SwiGLU
    rounded to bfloat16 and then multiplied, which the kernels do too;
    d act rounded to bfloat16 and then SwiGLU's gradient, where the
    kernel keeps its float32 accumulator)."""
    from paddle_tpu.ops import pallas_gmm as pg

    groups, live_tiles = GMM_LAYOUTS["9_of_12"]
    c, w = 256, 1408
    k, n = (w, c) if GMM_FORMS[form] == "wc" else (c, w)
    g = groups[-1] + 1
    case = _form_case(form, k, n, groups, dtype=jnp.bfloat16)
    tile_group = jnp.asarray(groups, jnp.int32)
    n_active = jnp.asarray([live_tiles], jnp.int32)
    live = slice(None) if form == "tgmm_swiglu" \
        else slice(0, live_tiles * 16)
    exact = _run_form(pg, jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32) if hasattr(a, "astype") else a,
        case), tile_group, n_active, "xla", g)

    def kernel_alone(lhs, rhs_or_grad, **kw):
        if form == "tgmm_swiglu":
            return pg.tgmm_pallas(lhs, rhs_or_grad, tile_group, n_active,
                                  16, g, interpret=True)
        return pg.gmm_pallas(lhs, rhs_or_grad, tile_group, n_active, 16,
                             interpret=True, **kw)

    if form == "gmm_swiglu_grad":
        g_act = kernel_alone(case["lhs"], case["rhs"], transpose_rhs=True)
        assert g_act.dtype == jnp.bfloat16
        before = pg._swiglu_grad(*case["gated"], g_act.astype(jnp.float32))
    else:
        before = kernel_alone(pg._swiglu(*case["lhs"]),
                              case.get("rhs", case.get("grad")))
    got = _run_form(pg, case, tile_group, n_active, impl, g)
    for a, was, want in zip(*map(jax.tree_util.tree_leaves,
                                 (got, before, exact))):
        assert a.dtype == jnp.bfloat16 and want.dtype == jnp.float32
        err = jnp.abs(a.astype(jnp.float32) - want)[live]
        err_before = jnp.abs(was.astype(jnp.float32) - want)[live]
        scale = float(jnp.abs(want[live]).max())
        assert float(err.max()) <= 2 ** -7 * scale
        assert float(err.max()) <= float(err_before.max())
        assert float(err.mean()) <= float(err_before.mean())
        if form == "gmm_swiglu_grad":   # one rounding where two were
            assert float(err.mean()) < 0.9 * float(err_before.mean())


@pytest.mark.parametrize("impl,counted", [("interpret", "in_kernel"),
                                          ("xla", "xla")])
def test_moe_experts_with_swiglu_in_the_kernels_against_the_dense_loop(
        impl, counted):
    """At an expert width of 1,408: experts that are not held, a held
    expert nobody is routed to (its tile is live and all padding), Out
    and every gradient against the dense loop over the held experts;
    and the series that says where SwiGLU was formed."""
    held = (2, 5, 7)
    ins = _experts_case(13, w=1408, held=held, empty=5)
    before = _impl_counts()

    def run(diff):
        out = _op("moe_experts", {**ins, **diff}, held=list(held),
                  block_m=32, impl=impl)["Out"]
        return (out * jnp.sin(out)).sum(), out

    def ref(diff):
        out = _dense_experts({**ins, **diff}, held)
        return (out * jnp.sin(out)).sum(), out

    diff = {k: ins[k] for k in DIFF}
    (_, out), grads = jax.value_and_grad(run, has_aux=True)(diff)
    since = _impl_since(before)
    assert since[("moe_swiglu", counted)] == 1
    assert [k for k in since if k[0] == "moe_swiglu"] \
        == [("moe_swiglu", counted)]
    (_, want), want_grads = jax.value_and_grad(ref, has_aux=True)(diff)
    np.testing.assert_allclose(out, want, rtol=1e-4,
                               atol=1e-5 * float(jnp.abs(want).max()))
    for k in DIFF:
        scale = float(jnp.abs(want_grads[k]).max())
        np.testing.assert_allclose(grads[k], want_grads[k], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=k)
    assert not np.asarray(grads["WGate"])[1].any()      # the empty expert


def _pallas_calls(jaxpr):
    """Every pallas_call equation of a jaxpr, the nested ones too."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


@pytest.mark.parametrize("kernel", ["pt_gmm_fwd", "pt_gmm_bwd_dx",
                                    "pt_gmm_bwd_dw"])
def test_gmm_grid_is_bounded_by_n_active(kernel):
    """The row-tile axis of each grouped-matmul grid is the traced
    n_active, not the static number of tiles: a grid that went back to
    the worst case would run a grid step for every tile without rows."""
    from paddle_tpu.ops import pallas_gmm as pg

    tm, tiles, g, k, n = 16, 40, 3, 256, 128
    rows, maps = tiles * tm, (jnp.zeros((tiles,), jnp.int32),
                              jnp.ones((1,), jnp.int32))
    if kernel == "pt_gmm_bwd_dw":
        jaxpr = jax.make_jaxpr(lambda x, dy, tg, na: pg.tgmm_pallas(
            x, dy, tg, na, tm, g, interpret=True))(
                jnp.zeros((rows, k)), jnp.zeros((rows, n)), *maps)
    else:
        t = kernel == "pt_gmm_bwd_dx"
        jaxpr = jax.make_jaxpr(lambda x, w, tg, na: pg.gmm_pallas(
            x, w, tg, na, tm, transpose_rhs=t, interpret=True))(
                jnp.zeros((rows, k)),
                jnp.zeros((g, n, k) if t else (g, k, n)), *maps)
    call, = _pallas_calls(jaxpr.jaxpr)
    assert call.params["name"] == kernel
    mapping = call.params["grid_mapping"]
    assert mapping.num_dynamic_grid_bounds == 1
    # and it is the row tiles' axis: second of gmm's, last of tgmm's
    axis = 2 if kernel == "pt_gmm_bwd_dw" else 1
    assert not isinstance(mapping.grid[axis], int)


def _plus_one_and_row_sums(rows, k, lay, a):
    return rows(a) + 1, rows(a).sum(-1)


def test_moe_experts_reads_no_row_past_the_live_tiles():
    """Far more tiles than live ones (34 for about 4): in interpret mode
    the rows past the grid's bound come back NaN, so finite outputs and
    gradients that agree with the XLA form say no caller reads them."""
    held = (2, 5)
    ins = _experts_case(7, n=256, e=16, k=4, held=held)
    diff = {k: ins[k] for k in DIFF}

    def run(diff, impl):
        out = _op("moe_experts", {**ins, **diff}, held=list(held),
                  block_m=32, impl=impl)["Out"]
        return (out * jnp.sin(out)).sum(), out

    (_, out), grads = jax.value_and_grad(run, has_aux=True)(
        diff, "interpret")
    (_, want), want_grads = jax.value_and_grad(run, has_aux=True)(
        diff, "xla")
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    for k in DIFF:
        assert np.isfinite(np.asarray(grads[k])).all(), k
        scale = float(jnp.abs(want_grads[k]).max())
        np.testing.assert_allclose(grads[k], want_grads[k], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=k)
    # the layout is as sparse as the docstring says, and the kernel did
    # leave the tail unwritten (else this test would prove nothing)
    from paddle_tpu.ops import pallas_gmm as pg
    from paddle_tpu.ops.llm_ops import _group_layout

    lay = _group_layout(ins["TopkIdx"], held, 32)
    tiles, live = lay["tile_group"].shape[0], int(lay["n_active"][0])
    assert tiles == 34 and live <= 6
    xs = jnp.ones((tiles * 32, 128), jnp.float32)
    tail = pg.gmm_pallas(xs, ins["WGate"], lay["tile_group"],
                         lay["n_active"], 32, interpret=True)[live * 32:]
    assert np.isnan(np.asarray(tail)).all()
    # and so are the op's own row arrays: made unwritten, written one
    # chunk of row tiles (all the live rows fit it), the rest left
    from paddle_tpu.ops.llm_ops import _CHUNK_TILES, _over_live_rows

    assert np.isnan(np.asarray(
        pg.row_buffer((4, 8), jnp.bfloat16, "interpret"), np.float32)).all()
    rows, dots = _over_live_rows(
        _plus_one_and_row_sums, ((128, jnp.float32), (None, jnp.float32)),
        4, 32, "interpret", lay, xs)
    written = _CHUNK_TILES * 32
    assert live * 32 <= written < tiles * 32
    assert (np.asarray(rows[:written]) == 2).all()
    assert (np.asarray(dots[:written]) == 128).all()
    assert np.isnan(np.asarray(rows[written:])).all()
    assert np.isnan(np.asarray(dots[written:])).all()


# -- the combine by token -----------------------------------------------------

COMBINE_SHARES = ("none", "one_pair", "eighth", "all")
# _layout_case's three at row tiles of 32, and a few tokens under the
# default row tile of 256: 32 tokens of 2 pairs, four experts held,
# every group a tile of its own, so the groups start at rows 0, 256,
# 512, 768 with 64 pairs in all (REVIEW of PR 43: a first row past
# twice the pairs)
COMBINE_LAYOUTS = ("one_tile", "sparse", "full", "few_tokens")


def _combine_case(layout, share, c, seed=21):
    """A layout of one of COMBINE_LAYOUTS' sizes with a chosen share
    of its pairs held, and a row array that is NaN in every row no held
    pair points at: (lay, plan, rows, gate, n_groups).  The router has
    one more expert than the layout's, which nobody holds; at `all`
    every pair goes to a held expert (twice to one where k > G)."""
    from paddle_tpu.ops import pallas_moe_combine as pc
    from paddle_tpu.ops.llm_ops import _group_layout

    if layout == "few_tokens":
        held, tm = (1, 3, 4, 6), 256
        ins = _experts_case(14, n=32, e=8, k=2, held=held)
    else:
        (ins, held), tm = _layout_case(layout), 32
    n, k = ins["TopkIdx"].shape
    nobody = int(np.asarray(ins["TopkIdx"]).max()) + 1
    rng = np.random.default_rng(seed)
    to_held = rng.choice(held, (n, k))
    if share == "none":
        idx = np.full((n, k), nobody)
    elif share == "one_pair":
        idx = np.full((n, k), nobody)
        idx[n // 2, k - 1] = held[-1]
    elif share == "eighth":
        idx = np.where(rng.uniform(size=(n, k)) < 1 / 8, to_held, nobody)
    else:
        idx = to_held
    lay = _group_layout(jnp.asarray(idx, jnp.int32), held, tm)
    m = lay["row_pair"].shape[0]
    plan = pc.combine_plan(lay["dest"], lay["slot"], len(held), c, m)
    assert plan is not None
    mine = np.asarray(lay["mine"])
    if share in ("none", "one_pair"):
        assert mine.sum() == (share == "one_pair")
    rows = np.full((m, c), np.nan, np.float32)
    pointed = np.asarray(lay["dest"])[mine]
    rows[pointed] = rng.normal(0, 1, (len(pointed), c))
    # gates that are powers of two: a product with one is exact, so a
    # compiler that fuses the product into the sum (the CPU's does, in
    # the jitted XLA form and in the interpreted kernel alike, each
    # where its fusions let it) changes no bit, and the comparison
    # holds what the kernel must keep: the ORDER of a token's sums
    gate = jnp.asarray(2.0 ** rng.integers(-2, 2, (n, k)), jnp.float32)
    return lay, plan, rows, gate, len(held)


@pytest.mark.parametrize("c", [128, 384])
@pytest.mark.parametrize("share", COMBINE_SHARES)
@pytest.mark.parametrize("layout", COMBINE_LAYOUTS)
def test_moe_combine_kernel_is_tokens_of_rows_bit_for_bit(layout, share, c):
    """pt_moe_combine in interpret mode against XLA's by-pair gathers:
    bf16 rows with a gate (the forward's combine) and float32 rows
    without (d x), every element equal; the rows no held pair points at
    (the padding of a group, the tiles past n_active) are NaN and none
    reaches the output."""
    from paddle_tpu.ops.llm_ops import _tokens_of_rows
    from paddle_tpu.ops.pallas_moe_combine import moe_combine_pallas

    lay, plan, rows, gate, _ = _combine_case(layout, share, c)
    for a, g in ((jnp.asarray(rows, jnp.bfloat16), gate),
                 (jnp.asarray(rows), None)):
        got = moe_combine_pallas(a, plan, g, out_dtype=jnp.float32,
                                 interpret=True)
        want = _tokens_of_rows(lay, a, g)
        assert np.isfinite(np.asarray(want)).all()
        np.testing.assert_array_equal(got, want, err_msg=str(a.dtype))
    # and any gate, to a rounding of the float32 sum
    any_gate = jnp.asarray(np.random.default_rng(3).uniform(
        0.2, 1, gate.shape), jnp.float32)
    a = jnp.asarray(rows, jnp.bfloat16)
    got = moe_combine_pallas(a, plan, any_gate, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(_tokens_of_rows(lay, a, any_gate)), rtol=2 * BF16_ULP,
        atol=1e-6)


@pytest.mark.parametrize("rows_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("layout", COMBINE_LAYOUTS)
def test_moe_combine_adds_any_gates_products_in_pair_order(layout,
                                                           rows_dtype):
    """Gates that are no powers of two, float32 out, every pair held
    (k terms a token): every element is what a numpy loop gives that
    adds gate * row to 0 in PAIR ORDER, each product either rounded to
    float32 before its add (the chip: tools/moe_combine_price.py holds
    the kernel to XLA's form there, every element) or fused into it
    (this CPU's compiler, where its fusions let it).  Another order of
    a token's adds is none of these."""
    import itertools

    from paddle_tpu.ops.pallas_moe_combine import moe_combine_pallas

    lay, plan, rows, gate, _ = _combine_case(layout, "all", 128)
    n, k = gate.shape
    gate = np.random.default_rng(4).uniform(0.2, 1, (n, k)) \
        .astype(np.float32)
    a = jnp.asarray(rows, jnp.dtype(rows_dtype))
    got = np.asarray(moe_combine_pallas(
        a, plan, jnp.asarray(gate), out_dtype=jnp.float32, interpret=True))
    a = np.asarray(a.astype(jnp.float32))
    terms = [(gate[:, j:j + 1], a[np.asarray(lay["dest"])[:, j]])
             for j in range(k)]

    def total(order, fused):
        out = np.zeros_like(got)
        for j, one_rounding in zip(order, fused):
            g, row = terms[j]
            # a float32 product is exact in float64
            out = (g.astype(np.float64) * row + out).astype(np.float32) \
                if one_rounding else out + g * row
        return out

    def explained(order):
        return np.any([got == total(order, (False,) + fused) for fused in
                       itertools.product((False, True), repeat=k - 1)], 0)

    assert explained(range(k)).all()
    assert not explained(range(k)[::-1]).all()


@pytest.mark.parametrize("share", COMBINE_SHARES)
@pytest.mark.parametrize("layout", COMBINE_LAYOUTS)
def test_combine_plan_against_a_compaction_by_hand(layout, share):
    """cnt, the compacted gates and the place of every held pair's row
    against loops in numpy: the copies of a block, laid end to end as
    the kernel lays them, hold the row of a token's s-th held pair at
    its block's pos[s tn + t] and fit the kernel's buffer; a run is
    covered by the fewest units of 16 rows."""
    from paddle_tpu.ops.pallas_moe_combine import (_UNIT, _buffer_rows,
                                                   _by_block, _compact)

    lay, plan, _, gate, g = _combine_case(layout, share, 128)
    dest, mine = np.asarray(lay["dest"]), np.asarray(lay["mine"])
    slot = np.asarray(lay["slot"])
    n, k = dest.shape
    blocks, _, tn = plan["cnt"].shape
    first, units = np.asarray(plan["runs"]).reshape(blocks, 2, g) \
        .transpose(1, 0, 2)

    def by_token(x):        # [blocks, 1, s tn + t] -> [s, n]
        return np.asarray(x).reshape(blocks, -1, tn).transpose(1, 0, 2) \
            .reshape(-1, n)

    pos = by_token(plan["pos"])
    gate_c = by_token(_by_block(_compact(gate.T, plan["order"]), tn))
    np.testing.assert_array_equal(by_token(plan["cnt"])[0], mine.sum(1))
    for b in range(blocks):
        buffer = np.concatenate([
            first[b, e] + np.arange(units[b, e] * _UNIT)
            for e in range(g)] + [np.zeros(0, int)])
        assert (first[b] % _UNIT == 0).all()
        assert (buffer < lay["row_pair"].shape[0]).all()
        assert len(buffer) <= _buffer_rows(tn, k, g)
        for e in range(g):
            run = dest[b * tn:(b + 1) * tn][slot[b * tn:(b + 1) * tn] == e]
            want = 0 if not len(run) else \
                run.max() // _UNIT - run.min() // _UNIT + 1
            assert units[b, e] == want, (b, e)
        for t in range(b * tn, (b + 1) * tn):
            held = np.nonzero(mine[t])[0]
            for s, j in enumerate(held):
                assert buffer[pos[s, t]] == dest[t, j], (t, s)
                assert gate_c[s, t] == gate[t, j]
            assert not gate_c[len(held):, t].any()


@pytest.mark.parametrize("layout", ["sparse", "full"])
def test_moe_experts_with_the_combine_kernel_keeps_the_bits(layout):
    """The routed experts with the combine through pt_moe_combine
    against the same kernels round XLA's by-pair gathers (a layout
    without a plan), float32, gates powers of two: Out and all five
    gradients equal in every element."""
    from paddle_tpu.ops.llm_ops import _group_layout, _routed_experts
    from paddle_tpu.ops.pallas_moe_combine import combine_plan

    ins, held = _layout_case(layout)
    n, k = ins["TopkIdx"].shape
    lay = _group_layout(ins["TopkIdx"], held, 32)
    planned = {**lay, "combine": combine_plan(
        lay["dest"], lay["slot"], len(held), 128, lay["row_pair"].shape[0])}
    gate = jnp.asarray(2.0 ** np.random.default_rng(5).integers(
        -2, 2, (n, k)), jnp.float32)
    args = (ins["X"], gate, ins["WGate"], ins["WUp"], ins["WDown"])

    def run(lay):
        def loss(*a):
            out = _routed_experts(*a, lay, k, 32, "interpret")
            return (out * jnp.sin(out)).sum(), out
        (_, out), grads = jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
        return (out,) + grads

    for name, a, b in zip(("Out",) + DIFF, run(planned), run(lay)):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("c,block_m,impl,counted", [
    (128, 32, "interpret", "interpret"), (128, 32, "xla", "xla"),
    # a width that is not whole lanes, row tiles that are not whole
    # units of the kernel's copies: XLA's gathers under a Pallas impl
    (192, 32, "interpret", "xla"), (128, 8, "interpret", "xla")])
def test_moe_combine_impl_is_counted_and_falls_back(c, block_m, impl,
                                                    counted):
    held = (2, 5, 7)
    ins = _experts_case(13, c=c, held=held)
    before = _impl_counts()
    out = _op("moe_experts", ins, held=list(held), block_m=block_m,
              impl=impl)["Out"]
    since = _impl_since(before)
    assert since[("moe_combine", counted)] == 1
    assert [k for k in since if k[0] == "moe_combine"] == [
        ("moe_combine", counted)]
    assert since[("moe_gmm", impl)] == 1
    np.testing.assert_allclose(out, _dense_experts(ins, held), rtol=1e-4,
                               atol=1e-5)


def test_moe_combine_block_is_a_function_of_the_shapes():
    """The three cells' blocks, the same whatever the tokens (a grid
    step reads its own block of the plan), and the calls the kernel
    leaves to XLA: a width that is not whole lanes, rows that are not
    whole units of its copies."""
    from paddle_tpu.ops.pallas_moe_combine import _token_block

    assert _token_block(4096, 8, 2560, 8, 34816) == 128       # ling3
    assert _token_block(4096, 4, 3584, 8, 18432) == 128       # xing4
    assert _token_block(8192, 6, 2048, 8, 51200) == 256       # dsv2
    assert _token_block(16384, 8, 2560, 8, 133120) == 128
    assert _token_block(1 << 20, 6, 2048, 8, 6293504) == 256
    assert _token_block(4096, 8, 2560 + 64, 8, 34816) is None
    assert _token_block(4096, 8, 2560, 8, 34816 + 8) is None
    assert _token_block(82, 2, 128, 4, 320) == 82


# -- hyper-connections ---------------------------------------------------------

@pytest.mark.parametrize("iters", [5, 20])
def test_sinkhorn_is_doubly_stochastic(iters):
    from paddle_tpu.ops.llm_ops import sinkhorn

    rng = np.random.default_rng(7)
    a = jnp.asarray(rng.normal(0, 1, (50, 4, 4)), jnp.float32)
    m = np.asarray(sinkhorn(a, iters, 1e-6))
    # columns are normalised last: exact in float32 (bf16 would be off
    # by 4e-3); rows converge geometrically: 20 rounds reach float32's
    # resolution on a generic matrix, 5 do not
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-5)
    np.testing.assert_allclose(m.sum(-1), 1.0,
                               atol=1e-5 if iters == 20 else 5e-2)
    # as a layer starts (8 I plus a small perturbation) the matrix is
    # the identity to 1e-3 and doubly stochastic to 1e-4: the diagonal
    # dominance that keeps it there also slows the iteration down
    near = np.asarray(sinkhorn(
        jnp.asarray(rng.normal(0, 0.01, (50, 4, 4)), jnp.float32)
        + 8 * jnp.eye(4), iters, 1e-6))
    np.testing.assert_allclose(near.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(near, np.broadcast_to(np.eye(4), near.shape),
                               atol=2e-3)
    assert (m > 0).all()


def _mhc_by_hand(x, norm, phi, alpha, bias, iters, eps, lo, hi):
    """x [B, T, n, C] token-major, as the equations are written;
    returns u [B, T, C], h_post [B, T, n], h_res [B, T, n, n]."""
    x = np.asarray(x, np.float64)
    b, t, n, c = x.shape
    flat = x.reshape(b, t, n * c)
    flat = flat / np.sqrt((flat ** 2).mean(-1, keepdims=True) + eps) * norm
    p = flat @ phi
    sig = lambda z: 1 / (1 + np.exp(-z))                      # noqa: E731
    h_pre = sig(alpha[0] * p[..., :n] + bias[:n])
    h_post = 2 * sig(alpha[1] * p[..., n:2 * n] + bias[n:2 * n])
    m = np.exp(np.clip(alpha[2] * p[..., 2 * n:].reshape(b, t, n, n)
                       + bias[2 * n:].reshape(n, n), lo, hi))
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + eps)
        m = m / (m.sum(-2, keepdims=True) + eps)
    return np.einsum("btn,btnc->btc", h_pre, x), h_post, m


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_mhc_pre_and_post(dtype):
    rng = np.random.default_rng(8)
    b, t, n, c = 2, 6, 4, 32
    width = 2 * n + n * n
    # the ops take the streams stream-major, [B, n, T, C]
    x = jnp.asarray(rng.normal(0, 1, (b, n, t, c)), dtype)
    token_major = jnp.transpose(x.astype(jnp.float32), (0, 2, 1, 3))
    norm = rng.uniform(0.5, 1.5, n * c).astype(np.float32)
    phi = rng.normal(0, 0.5, (n * c, width)).astype(np.float32)
    alpha = np.array([0.3, 0.2, 0.5], np.float32)
    bias = np.concatenate([rng.normal(0, 1, 2 * n),
                           3 * np.eye(n).reshape(-1)]).astype(np.float32)
    out = _op("mhc_pre", {"X": x, "NormScale": jnp.asarray(norm),
                          "Phi": jnp.asarray(phi),
                          "Alpha": jnp.asarray(alpha),
                          "Bias": jnp.asarray(bias)},
              sinkhorn_iters=20, eps=1e-6, clamp_min=-30.0,
              clamp_max=30.0)
    u, h_post, h_res = _mhc_by_hand(token_major, norm, phi, alpha, bias,
                                    20, 1e-6, -30, 30)
    # the coefficients are float32 whatever the streams' dtype, tokens
    # last: HPost [B, n, T], HRes [B, i, j, T]
    assert out["HPost"].dtype == out["HRes"].dtype == jnp.float32
    assert out["HPost"].shape == (b, n, t)
    assert out["HRes"].shape == (b, n, n, t) and out["U"].dtype == x.dtype
    got_post = np.transpose(np.asarray(out["HPost"]), (0, 2, 1))
    got_res = np.transpose(np.asarray(out["HRes"]), (0, 3, 1, 2))
    np.testing.assert_allclose(got_post, h_post, rtol=F32_TOL)
    np.testing.assert_allclose(got_res, h_res, rtol=1e-4, atol=1e-7)
    # columns sum to 1 in float32; bf16 coefficients would miss by 4e-3
    np.testing.assert_allclose(got_res.sum(-2), 1.0, atol=1e-5)
    tol = F32_TOL if dtype == "float32" else BF16_ULP
    np.testing.assert_allclose(np.asarray(out["U"], np.float64), u,
                               rtol=tol, atol=tol)
    y = jnp.asarray(rng.normal(0, 1, (b, t, c)), dtype)
    mixed = _op("mhc_post", {"X": x, "Y": y, "HPost": out["HPost"],
                             "HRes": out["HRes"]})["Out"]
    xf, yf = np.asarray(token_major, np.float64), np.asarray(y, np.float64)
    want = np.einsum("btij,btjc->btic", h_res, xf) \
        + h_post[..., None] * yf[:, :, None, :]
    assert mixed.dtype == x.dtype and mixed.shape == x.shape
    np.testing.assert_allclose(
        np.transpose(np.asarray(mixed, np.float64), (0, 2, 1, 3)), want,
        rtol=tol, atol=tol)


def test_mhc_clamp_acts_before_exp():
    """Pre-activations of +-1000 would overflow exp; clamped to +-30
    they give a finite doubly stochastic matrix."""
    n, c = 4, 8
    x = jnp.ones((1, n, 1, c), jnp.float32)
    bias = np.zeros(2 * n + n * n, np.float32)
    bias[2 * n:] = (2000 * np.eye(n) - 1000).reshape(-1)
    out = _op("mhc_pre", {"X": x, "NormScale": jnp.ones(n * c),
                          "Phi": jnp.zeros((n * c, 2 * n + n * n)),
                          "Alpha": jnp.ones(3), "Bias": jnp.asarray(bias)},
              sinkhorn_iters=20, eps=1e-6, clamp_min=-30.0,
              clamp_max=30.0)
    h = np.asarray(out["HRes"])
    assert np.isfinite(h).all()
    np.testing.assert_allclose(h[0, :, :, 0], np.eye(n), atol=1e-6)


# -- the hyper-connection kernels (ops/pallas_mhc.py) in interpret mode ------

MHC_ATTRS = {"eps": 1e-6, "clamp_min": -2.0, "clamp_max": 2.5}
# B, T, C at n = 4: one and several chunks of tokens, one and several
# lane tiles, several blocks of a batch, the cell's width (at T 16
# only: the interpreter walks 28 lane tiles a chunk)
MHC_SHAPES = [(1, 16, 128), (2, 16, 384), (2, 256, 128), (1, 256, 384),
              (1, 16, 3584)]


def _mhc_operands(b, t, c, dtype, n=4, seed=0):
    """X, Y, mhc_pre's parameters with pre-activations of the size a
    trained block has (|Alpha p| about 1; a third of H_res's beyond
    MHC_ATTRS' clamp) and a cotangent for every output."""
    rng = np.random.default_rng(seed + 31 * c + t)
    width = 2 * n + n * n

    def normal(shape, scale=1.0, dtype=jnp.float32):
        return jnp.asarray(scale * rng.standard_normal(shape), dtype)

    ops = {
        "x": normal((b, n, t, c), dtype=dtype),
        "y": normal((b, t, c), dtype=dtype),
        "norm_scale": 1 + normal((n * c,), 0.2),
        "phi": normal((n * c, width), (n * c) ** -0.5),
        "alpha": jnp.array([0.8, 1.2, 2.0], jnp.float32),
        "bias": jnp.concatenate([normal((2 * n,), 0.5),
                                 normal((n * n,), 1.5)]),
        "d_out": normal((b, n, t, c), dtype=dtype),
        "d_u": normal((b, t, c), dtype=dtype),
        "d_post": normal((b, n, t)),
        "d_res": normal((b, n, n, t)),
    }
    return ops


@functools.partial(jax.jit, static_argnames=("impl", "iters"))
def _mhc_passes(ops, impl, iters):
    """Every output and every gradient of the two ops' functions under
    `impl`, by name; one program."""
    from paddle_tpu.ops import llm_ops

    attrs = dict(MHC_ATTRS, sinkhorn_iters=iters)
    pre_in = [ops[k] for k in ("x", "norm_scale", "phi", "alpha", "bias")]
    (u, h_post, h_res), pre_vjp = jax.vjp(
        lambda *a: llm_ops._mhc_pre(*a, attrs, impl), *pre_in)
    # mhc_post on the XLA form's coefficients under both impls
    coefs = llm_ops._mhc_pre(*pre_in, attrs, "xla")[1:]
    out, post_vjp = jax.vjp(
        lambda *a: llm_ops._mhc_post(*a, impl), ops["x"], ops["y"], *coefs)
    got = {"U": u, "HPost": h_post, "HRes": h_res, "Out": out}
    got.update(zip(("pre.dX", "dNormScale", "dPhi", "dAlpha", "dBias"),
                   pre_vjp((ops["d_u"], ops["d_post"], ops["d_res"]))))
    got.update(zip(("post.dX", "dY", "dHPost", "dHRes"),
                   post_vjp(ops["d_out"])))
    return got


# gradients that are sums over every token of the batch
MHC_PARAM_GRADS = ("dNormScale", "dPhi", "dAlpha", "dBias")


def _assert_mhc_close(got, want, f32_tol):
    for name, ref in want.items():
        a, r = (np.asarray(v, np.float64) for v in (got[name], ref))
        assert got[name].dtype == ref.dtype, name
        scale = np.abs(r).max()
        if ref.dtype == jnp.bfloat16:
            # the same float32 sum rounded once: one bfloat16 ulp of
            # the element where the two forms' sums straddle a tie;
            # where a sum's terms cancel, 2^-16 of the largest entry
            # (what pt_mhc_pre_bwd's one-pass product leaves out of
            # dX, 2^-7 of a bfloat16 ulp there)
            np.testing.assert_allclose(a, r, rtol=BF16_ULP,
                                       atol=2.0 ** -16 * scale,
                                       err_msg=name)
        else:
            # float32 sums in another order, against the largest entry;
            # a parameter's gradient sums B T times the terms
            tol = f32_tol * (4 if name in MHC_PARAM_GRADS else 1)
            np.testing.assert_allclose(a, r, rtol=0, atol=tol * scale,
                                       err_msg=name)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize(
    "shape,iters",
    # 5 rounds where the blocks are one and many: a case compiles the
    # rounds and their gradient, 3.5 s
    [(s, 20) for s in MHC_SHAPES] + [(MHC_SHAPES[0], 5), (MHC_SHAPES[3], 5)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_mhc_kernels_against_the_xla_composition(shape, iters, dtype):
    """pt_mhc_pre_fwd / pt_mhc_pre_bwd / pt_mhc_post_fwd /
    pt_mhc_post_bwd in interpret mode: U, HPost, HRes, Out and the
    gradients of X (either op's), Y, NormScale, Phi, Alpha, Bias, and
    of HPost and HRes, with the clamp active."""
    ops = _mhc_operands(*shape, jnp.dtype(dtype))
    before = _impl_counts()
    got = _mhc_passes(ops, "interpret", iters)
    assert _impl_since(before) == {("mhc", "interpret"): 2, ("mhc", "xla"): 1}
    want = _mhc_passes(ops, "xla", iters)
    raw = np.asarray(want["HRes"])
    assert 0 < (raw == raw.max()).mean() < 1    # not one constant matrix
    # float32 operands: 1e-6 of the largest entry a term, the two
    # forms' sums in another order 2e-6, and twice that for the sums of
    # n C = 14,336 terms at the cell's width
    _assert_mhc_close(got, want, 4e-6 if shape[2] > 384 else 2e-6)


@pytest.mark.parametrize("shape", [(1, 16, 96), (1, 24, 128)],
                         ids=["C96", "T24"])
def test_mhc_takes_the_xla_form_where_the_kernels_cannot_tile(shape):
    """C no whole lane tiles, T no whole chunks of 16 tokens: asked for
    the kernels, the ops run the XLA composition, say so in the
    counter, and give its results bit for bit."""
    from paddle_tpu.ops import pallas_mhc

    assert all(pallas_mhc.token_block(k, 4, *shape[1:], 2) is None
               for k in pallas_mhc._BLOCK_UNITS)
    ops = _mhc_operands(*shape, jnp.bfloat16)
    before = _impl_counts()
    got = _mhc_passes(ops, "interpret", 5)
    assert _impl_since(before) == {("mhc", "xla"): 3}
    for name, ref in _mhc_passes(ops, "xla", 5).items():
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(ref, np.float32), name)


def test_mhc_token_blocks_at_the_cell():
    """The blocks the four kernels take of xing4_29b_train_s4k's
    streams (1 x 4 x 4,096 x 3,584): whole lane tiles of tokens for the
    kernels that write coefficients tokens-last, all under the 40 MiB
    a kernel allows itself; float32 streams leave mhc_pre's backward to
    XLA (its blocks and the weight's gradient pass it)."""
    from paddle_tpu.ops import pallas_mhc as pm

    def blocks(size):
        return {k: pm.token_block(k, 4, 4096, 3584, size)
                for k in pm._BLOCK_UNITS}

    assert blocks(2) == {"post_fwd": 256, "post_bwd": 128, "pre_fwd": 256,
                         "pre_bwd": 128}
    assert blocks(4) == {"post_fwd": 128, "post_bwd": 64, "pre_fwd": 128,
                         "pre_bwd": None}
    for kernel, tt in blocks(2).items():
        assert pm._vmem(kernel, 4, tt, 3584, 2) <= 40 << 20
    # n = 6: 3 x 48 products pass a lane tile
    assert pm.token_block("post_fwd", 6, 4096, 3584, 2) is None


# -- flash attention at latent attention's head sizes --------------------------

def _mla_qkv(seed, b=1, h=2, t=256, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, h, t, 192), dtype)
    k = jax.random.normal(ks[1], (b, h, t, 192), dtype)
    v = jax.random.normal(ks[2], (b, h, t, 128), dtype)
    g = jax.random.normal(ks[3], (b, h, t, 128), dtype)
    return q, k, v, g


SCALE = 192 ** -0.5 * 1.4159 ** 2


def test_flash_192_128_forward_interpret():
    from paddle_tpu.ops import pallas_kernels as pk

    q, k, v, _ = _mla_qkv(0)
    out, lse = pk._flash_attention_fwd(q, k, v, causal=True, scale=SCALE,
                                       impl="interpret", block_q=128,
                                       block_k=128)
    want, want_lse = pk._plain_attention(q, k, v, True, SCALE,
                                         with_lse=True)
    assert out.shape == (1, 2, 256, 128) and lse.shape == (1, 2, 256)
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(lse, want_lse, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("blocks", [(128, 128), (64, 256)])
def test_flash_192_128_saved_residual_backward(blocks):
    """The grad op's saved path: the two backward kernels on the
    forward's Out and LSE, against jax's gradient of plain attention."""
    from paddle_tpu.ops import pallas_kernels as pk

    q, k, v, g = _mla_qkv(1)
    call = dict(causal=True, scale=SCALE, impl="interpret",
                block_q=blocks[0], block_k=blocks[1])
    out, lse = pk._flash_attention_fwd(q, k, v, **call)
    dq, dk, dv = pk._flash_attention_bwd(q, k, v, out, lse, g, **call)
    _, vjp = jax.vjp(lambda q, k, v: pk._plain_attention(
        q, k, v, True, SCALE), q, k, v)
    assert dq.shape == q.shape and dk.shape == k.shape \
        and dv.shape == v.shape
    for got, want in zip((dq, dk, dv), vjp(g)):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)


def test_flash_op_and_grad_op_at_two_head_sizes():
    """Through the registered ops, as a program runs them: the grad op
    with Out and LSE bound equals the grad op without (jax.vjp over the
    forward), off the chip both in XLA."""
    q, k, v, g = _mla_qkv(2, t=64)
    attrs = dict(causal=True, scale=SCALE)
    fwd = _op("flash_attention", {"Q": q, "K": k, "V": v}, **attrs)
    assert fwd["Out"].shape == (1, 2, 64, 128)
    ins = {"Q": q, "K": k, "V": v, "Out@GRAD": g}
    saved = _op("flash_attention_grad",
                {**ins, "Out": fwd["Out"], "LSE": fwd["LSE"]}, **attrs)
    again = _op("flash_attention_grad", ins, **attrs)
    for slot in ("Q@GRAD", "K@GRAD", "V@GRAD"):
        np.testing.assert_allclose(saved[slot], again[slot], rtol=1e-5,
                                   atol=1e-6)


# -- every new op through the IR: shape rules and the verifier ----------------

def test_new_ops_pass_shape_check_and_verifier():
    import paddle_tpu as fluid
    from paddle_tpu import layers
    from paddle_tpu.analysis import verifier
    from paddle_tpu.analysis.shape_check import infer_program_shapes

    x = layers.data("x", shape=[4, 16, 32], dtype="float32")     # streams
    u, h_post, h_res = layers.mhc_pre(x, sinkhorn_iters=5, name="hc")
    u = layers.rms_norm(u, name="norm")
    q = layers.rotary_embedding(layers.reshape(u, [-1, 16, 4, 8]),
                                rotary_dim=4, factor=64.0,
                                original_max_position=8)
    idx, gate = layers.moe_route(u, 8, 2, routed_scaling_factor=2.0,
                                 name="router")
    y = layers.moe_experts(u, idx, gate, held=[1, 3], width=16,
                           name="experts")
    y = layers.elementwise_add(y, layers.swiglu(u, u))
    out = layers.mhc_post(x, y, h_post, h_res)
    block = fluid.default_main_program().global_block()
    assert out.shape == (-1, 4, 16, 32) and u.shape == (-1, 16, 32)
    assert tuple(q.shape) == (-1, 16, 4, 8)
    assert tuple(idx.shape) == (-1, 16, 2) and idx.dtype == "int32"
    assert tuple(h_res.shape) == (-1, 4, 4, 16)
    types = [op.type for op in block.ops]
    for name in ("mhc_pre", "rms_norm", "rotary_embedding", "moe_route",
                 "moe_experts", "swiglu", "mhc_post"):
        assert name in types
    _, diags = infer_program_shapes(fluid.default_main_program())
    assert not diags, [str(d) for d in diags]
    verifier.verify(fluid.default_main_program())
    # the selection bias gets no gradient, the router weight does
    assert block.var("router_bias.w").stop_gradient
    assert not block.var("router.w").stop_gradient


def test_amp_lists_keep_the_float32_parts():
    from paddle_tpu.contrib.mixed_precision import fp16_lists, fp16_utils

    assert "moe_route" in fp16_lists.black_list
    assert "rms_norm" in fp16_lists.follow_x_list
    assert {"moe_experts", "mhc_pre", "mhc_post"} <= fp16_lists.white_list
    keep = fp16_utils._WHITE_KEEP_FP32
    assert keep["moe_experts"] == {"TopkWeight"}
    assert keep["mhc_pre"] == {"NormScale", "Phi", "Alpha", "Bias"}
    assert keep["mhc_post"] == {"HPost", "HRes"}
    assert fp16_utils._WHITE_LOWP_OUT["mhc_pre"] == {"U"}

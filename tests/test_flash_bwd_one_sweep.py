"""The flash backward in one sweep (ISSUE 29).

One kernel (`_bwd_dkv_kernel` with_dq, named `pt_flash_bwd_dkv`) forms P and
dS once a block pair and writes dq, dk and dv; a head's dq stays in
VMEM over the kv axis.  Where it cannot (a shape whose dq passes the
VMEM the kernel may ask for) the dq sweep and the dk/dv sweep run as
before.  All on the CPU, the kernels in interpret mode.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.flags import set_flags
from paddle_tpu.ops import pallas_kernels as pk
from paddle_tpu.parallel import env as penv

from test_flash_saved_residuals import (  # noqa: F401  (a fixture)
    _attention_net, _feed, _kernel_calls, _pallas_calls, interpret)


def _operands(b, h, tq, tk, d, dv, dtype, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(*s), dtype) for s in (
        (b, h, tq, d), (b, h, tk, d), (b, h, tk, dv), (b, h, tq, dv)))


def _static(d, causal, bq, bk):
    return dict(causal=causal, scale=d ** -0.5, block_q=bq, block_k=bk,
                interpret=True)


def _bwd_counts():
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == "flash_attention_bwd"})


# b, h, tq, tk, d, dv, causal, block_q, block_k, dtype
CASES = {
    "full_one_block": (2, 2, 32, 32, 8, 8, False, 32, 32, "float32"),
    "full_blocks": (2, 2, 32, 32, 8, 8, False, 16, 16, "float32"),
    "causal_blocks": (2, 2, 64, 64, 8, 8, True, 16, 16, "float32"),
    "causal_tq_lt_tk": (1, 2, 16, 48, 8, 8, True, 8, 16, "float32"),
    "full_tq_gt_tk": (1, 2, 48, 16, 8, 8, False, 16, 8, "float32"),
    "pad_q": (1, 2, 40, 32, 8, 8, False, 16, 16, "float32"),
    "pad_k": (1, 2, 32, 40, 8, 8, False, 16, 16, "float32"),
    "pad_both_causal": (1, 2, 40, 40, 8, 8, True, 16, 16, "float32"),
    "one_q_block_many_kv": (1, 2, 16, 64, 8, 8, False, 16, 16, "float32"),
    "many_q_blocks_one_kv": (1, 2, 64, 16, 8, 8, False, 16, 16, "float32"),
    "d_ne_dv": (1, 2, 32, 32, 24, 16, True, 16, 16, "float32"),
    "latent_192_128": (1, 1, 32, 32, 192, 128, True, 16, 16, "float32"),
    "bf16": (1, 2, 64, 64, 16, 16, True, 16, 16, "bfloat16"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_sweep_equals_two_sweeps_and_the_reference(case):
    b, h, tq, tk, d, dv, causal, bq, bk, dtype = CASES[case]
    q, k, v, g = _operands(b, h, tq, tk, d, dv, dtype)
    kw = _static(d, causal, bq, bk)
    o, lse = pk._flash_fwd_pallas(q, k, v, **kw)
    before = _bwd_counts()
    one = pk._flash_bwd(q, k, v, o, lse, g, **kw)
    assert _bwd_counts() - before == {"fused": 1}
    two = pk._flash_bwd_pallas(q, k, v, o, lse, g, one_sweep_vmem=None,
                               **kw)
    want = jax.grad(
        lambda q, k, v: (pk._plain_attention(
            q, k, v, causal, kw["scale"]).astype(jnp.float32)
            * g.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)
    tol = 2e-5 if dtype == "float32" else 5e-2
    for name, a, b_, w in zip(("dq", "dk", "dv"), one, two, want):
        assert a.shape == w.shape and a.dtype == w.dtype, name
        assert np.array_equal(np.asarray(a), np.asarray(b_)), name
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(w, np.float32),
            atol=tol * max(1.0, float(jnp.abs(w).max())), err_msg=name)
        assert np.abs(np.asarray(a, np.float32)).max() > 0, name


# -- token-major operands: [B, T, H*d] as the projections leave them ---------

def _layout_counts():
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == "flash_attention_layout"})


# b, h, tq, tk, d, causal, block_q, block_k, dtype
TOKEN_MAJOR = {
    "h8_d64_causal": (1, 8, 32, 32, 64, True, 16, 16, "float32"),
    "h8_d64_full": (2, 8, 32, 32, 64, False, 16, 16, "float32"),
    "h2_d128_causal": (2, 2, 32, 32, 128, True, 16, 16, "float32"),
    "h2_d128_full": (1, 2, 32, 32, 128, False, 16, 16, "float32"),
    "h3_d128_odd_heads": (1, 3, 32, 32, 128, True, 16, 16, "float32"),
    "tq_lt_tk_q_off": (1, 8, 16, 48, 64, True, 8, 16, "float32"),
    "tq_gt_tk_full": (1, 2, 48, 16, 128, False, 16, 8, "float32"),
    "not_a_block_multiple": (1, 8, 40, 56, 64, True, 16, 16, "float32"),
    "bf16": (1, 8, 32, 32, 64, True, 16, 16, "bfloat16"),
}


@pytest.mark.parametrize("sweeps", ["one_sweep", "two_sweeps"])
@pytest.mark.parametrize("case", sorted(TOKEN_MAJOR))
def test_token_major_equals_head_major_and_the_reference(case, sweeps):
    """The same kernels on the same heads, addressed in place: out and
    lse bit for bit what the head-major call gives on the transposed
    operands.  The gradients to a few ulps: delta = rowsum(dO * O) is a
    product with the heads' lane indicator here and a reduce there, the
    same 64 or 128 float32 terms a row summed in another order, and dS
    = P * (dP - delta) carries that last bit."""
    b, h, tq, tk, d, causal, bq, bk, dtype = TOKEN_MAJOR[case]
    rng = np.random.RandomState(0)
    q, k, v, g = (jnp.asarray(rng.randn(b, t, h * d), dtype)
                  for t in (tq, tk, tk, tq))
    heads_first = [pk._split_heads(x, h) for x in (q, k, v, g)]
    call = dict(causal=causal, block_q=bq, block_k=bk, impl="interpret")

    def run(q, k, v, g, heads):
        out, lse = pk._flash_attention_fwd(q, k, v, heads=heads, **call)
        if sweeps == "one_sweep":
            before = _bwd_counts()
            grads = pk._flash_attention_bwd(q, k, v, out, lse, g,
                                            heads=heads, **call)
            assert _bwd_counts() - before == {"fused": 1}
        else:
            grads = pk._flash_bwd_pallas(
                q, k, v, out, lse.reshape(b * h, tq), g,
                one_sweep_vmem=None,
                **pk._call_args(q, k, heads=heads, **call)[1])
        return (out, *grads), lse

    before = _layout_counts()
    got, lse = run(q, k, v, g, h)
    assert set(_layout_counts() - before) == {"token_major"}
    want, want_lse = run(*heads_first, None)
    assert lse.shape == (b, h, tq) and lse.dtype == jnp.float32
    assert np.array_equal(np.asarray(lse), np.asarray(want_lse))
    plain = jax.grad(
        lambda q, k, v: (pk._plain_attention(
            q, k, v, causal, d ** -0.5).astype(jnp.float32)
            * heads_first[3].astype(jnp.float32)).sum(),
        argnums=(0, 1, 2))(*heads_first[:3])
    tol = 2e-5 if dtype == "float32" else 5e-2
    for name, a, w, ref in zip(("out", "dq", "dk", "dv"), got, want,
                               (None, *plain)):
        w = pk._merge_heads(w)
        assert a.shape == w.shape and a.dtype == w.dtype, name
        a, w = np.asarray(a, np.float32), np.asarray(w, np.float32)
        if name == "out":
            assert np.array_equal(a, w), name
            continue
        # float32: 2e-6 of the largest entry; bfloat16 rounds the few
        # ulps of float32 away or to one of its own
        np.testing.assert_allclose(
            a, w, atol=(2e-6 if dtype == "float32" else 2 ** -7)
            * np.abs(w).max(), err_msg=name)
        np.testing.assert_allclose(
            a, np.asarray(pk._merge_heads(ref), np.float32),
            atol=tol * max(1.0, np.abs(w).max()), err_msg=name)
        assert np.abs(a).max() > 0, name


@pytest.mark.parametrize("h,d", [(4, 32), (3, 64), (2, 192)],
                         ids=["d32", "three_heads_d64", "d192"])
def test_token_major_operands_the_blocks_cannot_serve_are_transposed(
        h, d):
    """A head size that is no lane block or half of one, or heads that
    do not pair up: the entry transposes inside, says `head_major`, and
    gives what the head-major call gives, bit for bit."""
    rng = np.random.RandomState(1)
    q, k, v, g = (jnp.asarray(rng.randn(2, 32, h * d), jnp.float32)
                  for _ in range(4))
    call = dict(causal=True, block_q=16, block_k=16, impl="interpret")
    before = _layout_counts()
    out, lse = pk._flash_attention_fwd(q, k, v, heads=h, **call)
    grads = pk._flash_attention_bwd(q, k, v, out, lse, g, heads=h,
                                    **call)
    assert _layout_counts() - before == {"head_major": 2}
    qh, kh, vh, gh = (pk._split_heads(x, h) for x in (q, k, v, g))
    want, want_lse = pk._flash_attention_fwd(qh, kh, vh, **call)
    want_grads = pk._flash_attention_bwd(qh, kh, vh, want, want_lse, gh,
                                         **call)
    assert out.shape == q.shape
    assert np.array_equal(np.asarray(lse), np.asarray(want_lse))
    for a, w in zip((out, *grads), (want, *want_grads)):
        assert np.array_equal(np.asarray(a),
                              np.asarray(pk._merge_heads(w)))


def test_token_major_off_the_chip_is_plain_attention():
    """The XLA impl has no blocks to address: `head_major`, and the
    public entry differentiates through the transposes."""
    rng = np.random.RandomState(2)
    q, k, v = (jnp.asarray(rng.randn(1, 16, 2 * 64), jnp.float32)
               for _ in range(3))
    before = _layout_counts()
    got = jax.grad(lambda *a: pk.flash_attention(
        *a, causal=True, heads=2, impl="xla").sum(),
        argnums=(0, 1, 2))(q, k, v)
    assert _layout_counts() - before == {"head_major": 1}
    want = jax.grad(lambda *a: pk._plain_attention(
        *(pk._split_heads(x, 2) for x in a), True, 64 ** -0.5).sum(),
        argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=1e-6)


def test_token_major_public_entry_differentiates_through_the_kernels():
    """`flash_attention(heads=)` under jax.grad: the custom_vjp on
    token-major operands (what a recompute segment and an op without
    its saved slots run)."""
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(1, 32, 2 * 64), jnp.float32)
               for _ in range(3))

    def loss(attend):
        return jax.grad(lambda *a: (attend(*a) ** 2).sum(),
                        argnums=(0, 1, 2))

    got = loss(lambda *a: pk.flash_attention(
        *a, causal=True, heads=2, impl="interpret", block_q=16,
        block_k=16))
    names = _pallas_calls(got, q, k, v)
    assert names == {"pt_flash_fwd": 1, "pt_flash_bwd_dkv": 1}
    want = loss(lambda *a: pk._merge_heads(pk._plain_attention(
        *(pk._split_heads(x, 2) for x in a), True, 64 ** -0.5)))
    for a, w in zip(got(q, k, v), want(q, k, v)):
        np.testing.assert_allclose(a, w, atol=2e-5 * np.abs(w).max())


def test_token_major_shape_rule_and_vmem():
    """Two heads share a lane block's tiles and accumulators: the
    resident dq of the pair is what one head's is padded to 128 lanes.
    Their score tiles are a head's each, so at head size 64 the one
    sweep ends at 56k rows (74k head-major, one head a step; 74k at 128
    on both).  The forward asks for the VMEM two heads' score tiles
    need at 1,024-row blocks (it compiled at neither layout before it
    asked)."""
    one = pk._bwd_fused_vmem_bytes(1, 8192, 1024, 1024, 64, 64, 2)
    pair = pk._bwd_fused_vmem_bytes(2, 8192, 1024, 1024, 64, 64, 2)
    assert one < pair < 2 * one
    # MiB Mosaic took, compiled for a described v5e (PERF.md, PR 31):
    # (hpb, block, d, dv, token_major); 16.2 is what passed the 16 MiB
    # a kernel is scoped to unless it asks
    took = {(2, 1024, 64, 64, True): 16.2, (2, 512, 64, 64, True): 4.8,
            (1, 1024, 128, 128, True): 11.7,
            (1, 1024, 64, 64, False): 12.2, (1, 512, 64, 64, False): 2.3,
            (1, 1024, 128, 128, False): 12.7,
            (1, 1024, 192, 128, False): 11.4}
    for (hpb, blk, d, dv, tm), mib in took.items():
        est = pk._fwd_vmem_bytes(hpb, blk, blk, d, dv, 2) / 2 ** 20
        assert mib < est < 3 * mib, (hpb, blk, d, tm, est)
    # and for the token-major backward: `_s8k`, `_s512`, 16k x 128
    for (hpb, t, blk, d), mib in {(2, 8192, 1024, 64): 27.6,
                                  (2, 512, 512, 64): 6.0,
                                  (1, 16384, 1024, 128): 28.5}.items():
        est = pk._bwd_fused_vmem_bytes(hpb, t, blk, blk, d, d,
                                       2) / 2 ** 20
        assert mib < est < 3 * mib, (hpb, t, d, est)

    def trace(t, heads=2):
        q = jax.ShapeDtypeStruct((1, t, 128), jnp.bfloat16)
        call = dict(pk._call_args(q, q, causal=True, impl="pallas",
                                  heads=heads)[1])
        return sorted(_pallas_calls(
            lambda q, k, v, o, lse, g: pk._flash_bwd(q, k, v, o, lse, g,
                                                     **call),
            q, q, q, q, jax.ShapeDtypeStruct((heads, t), jnp.float32),
            q))

    assert trace(8192) == ["pt_flash_bwd_dkv"]
    assert trace(56 * 1024) == ["pt_flash_bwd_dkv"]
    assert len(trace(57 * 1024)) == 2
    assert trace(74 * 1024, heads=1) == ["pt_flash_bwd_dkv"]
    assert len(trace(75 * 1024, heads=1)) == 2


@pytest.mark.parametrize("causal", [False, True])
def test_lse_cotangent_folds_into_delta(causal):
    """Ring attention's merge reads lse: its cotangent rides the one
    sweep as the two (delta - dlse)."""
    q, k, v, g = _operands(1, 2, 40, 48, 8, 8, "float32")
    kw = _static(8, causal, 16, 16)
    w = jnp.asarray(np.random.RandomState(1).randn(2, 40), jnp.float32)

    def loss(attend):
        def f(q, k, v):
            out, lse = attend(q, k, v)
            return (out * g).sum() + (lse[:, :40] * w).sum()
        return jax.grad(f, argnums=(0, 1, 2))

    one = loss(lambda q, k, v: pk._flash_lse(q, k, v, *kw.values()))(
        q, k, v)

    def plain(q, k, v):
        out, lse = pk._plain_attention(q, k, v, causal, kw["scale"],
                                       with_lse=True)
        return out, lse.reshape(2, 40)

    for a, want in zip(one, loss(plain)(q, k, v)):
        np.testing.assert_allclose(a, want, atol=2e-5)
    o, lse = pk._flash_fwd_pallas(q, k, v, **kw)
    dlse = jnp.pad(w, ((0, 0), (0, lse.shape[1] - 40)))
    two = pk._flash_bwd_pallas(q, k, v, o, lse, g, dlse=dlse,
                               one_sweep_vmem=None, **kw)
    for a, b in zip(one, two):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def _trace_bwd(t, d=64, dtype=jnp.bfloat16):
    """Trace, not run: the counter counts where `_flash_bwd` decides."""
    q = jax.ShapeDtypeStruct((1, 1, t, d), dtype)
    call = dict(pk._call_args(q, q, causal=True, impl="pallas")[1])
    return sorted(_pallas_calls(
        lambda q, k, v, o, lse, g: pk._flash_bwd(q, k, v, o, lse, g,
                                                 **call),
        q, q, q, q, jax.ShapeDtypeStruct((1, t), jnp.float32), q))


def test_the_shape_alone_picks_the_sweep():
    before = _bwd_counts()
    assert _trace_bwd(8192) == ["pt_flash_bwd_dkv"]
    assert _bwd_counts() - before == {"fused": 1}
    # float32 dq of one head at 128k x 64 and its output block: 128 MiB
    assert _trace_bwd(131072) == ["pt_flash_bwd_dkv", "pt_flash_bwd_dq"]
    assert _bwd_counts() - before == {"fused": 1, "two_sweep": 1}
    # the last length of one sweep at head size 64 in bfloat16, and the
    # first of two (1024-row blocks); half that at 192
    assert _trace_bwd(74 * 1024) == ["pt_flash_bwd_dkv"]
    assert len(_trace_bwd(75 * 1024)) == 2
    assert _trace_bwd(35 * 1024, d=192) == ["pt_flash_bwd_dkv"]
    assert len(_trace_bwd(36 * 1024, d=192)) == 2


def test_vmem_asked_for_covers_what_the_chip_compiler_took():
    """MiB Mosaic allocated for the one sweep, compiled for a described
    v5e (PERF.md, PR 29), against the estimate the kernel asks with."""
    took = {(1, 8192, 1024, 64, 64, 2): 22.1, (1, 512, 512, 64, 64, 2): 4.5,
            (1, 4096, 1024, 192, 128, 2): 24.4,
            (1, 32768, 1024, 128, 128, 2): 46.2,
            (1, 8192, 1024, 64, 64, 4): 32.4}
    for (hpb, t, blk, d, dv, itemsize), mib in took.items():
        est = pk._bwd_fused_vmem_bytes(hpb, t, blk, blk, d, dv,
                                       itemsize) / 2 ** 20
        assert mib < est < 3 * mib, (hpb, t, d, itemsize, est)


def test_six_layers_one_backward_kernel_each(interpret):
    feed = _feed()
    prog = _attention_net(feed, n_layers=6, causal=True)
    assert _kernel_calls(prog, feed) == {"pt_flash_fwd": 6,
                                         "pt_flash_bwd_dkv": 6}


def test_recompute_segment_replays_the_forward_not_the_dq_sweep():
    q, k, v, _ = _operands(1, 2, 32, 32, 8, 8, "float32")

    @jax.checkpoint
    def layer(q, k, v):
        return pk.flash_attention(q, k, v, causal=True, impl="interpret")

    names = _pallas_calls(jax.grad(
        lambda q, k, v: layer(q, k, v).sum(), argnums=(0, 1, 2)), q, k, v)
    assert names == {"pt_flash_fwd": 2, "pt_flash_bwd_dkv": 1}


def test_one_backward_kernel_under_shard_map(interpret):
    from paddle_tpu.parallel.gspmd import MeshPlan
    from paddle_tpu.transpiler import shard_program

    feed = _feed(b=4, h=4, tq=32, tk=32)
    prog = _attention_net(feed, n_layers=2, causal=True)
    try:
        set_flags({"gspmd": True})
        shard_program(fluid.CompiledProgram(prog), MeshPlan(dp=2, tp=2),
                      devices=jax.devices()[:4])
        before = pk._M_KERNEL_IMPL.value(kernel="flash_attention_gspmd",
                                         impl="shard_map")
        assert _kernel_calls(prog, feed) == {"pt_flash_fwd": 2,
                                             "pt_flash_bwd_dkv": 2}
        assert pk._M_KERNEL_IMPL.value(
            kernel="flash_attention_gspmd", impl="shard_map") - before == 4
    finally:
        set_flags({"gspmd": False})
        penv.reset()

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


def _static(d, causal, bq, bk, packed_stats=False, head_pack=False):
    return dict(causal=causal, scale=d ** -0.5, block_q=bq, block_k=bk,
                interpret=True, packed_stats=packed_stats,
                head_pack=head_pack)


def _bwd_counts():
    return collections.Counter({
        lbl["impl"]: int(n) for lbl, n in pk._M_KERNEL_IMPL.items()
        if lbl["kernel"] == "flash_attention_bwd"})


# b, h, tq, tk, d, dv, causal, block_q, block_k, dtype, variants
CASES = {
    "full_one_block": (2, 2, 32, 32, 8, 8, False, 32, 32, "float32", {}),
    "full_blocks": (2, 2, 32, 32, 8, 8, False, 16, 16, "float32", {}),
    "causal_blocks": (2, 2, 64, 64, 8, 8, True, 16, 16, "float32", {}),
    "causal_tq_lt_tk": (1, 2, 16, 48, 8, 8, True, 8, 16, "float32", {}),
    "full_tq_gt_tk": (1, 2, 48, 16, 8, 8, False, 16, 8, "float32", {}),
    "pad_q": (1, 2, 40, 32, 8, 8, False, 16, 16, "float32", {}),
    "pad_k": (1, 2, 32, 40, 8, 8, False, 16, 16, "float32", {}),
    "pad_both_causal": (1, 2, 40, 40, 8, 8, True, 16, 16, "float32", {}),
    "one_q_block_many_kv": (1, 2, 16, 64, 8, 8, False, 16, 16, "float32",
                            {}),
    "many_q_blocks_one_kv": (1, 2, 64, 16, 8, 8, False, 16, 16, "float32",
                             {}),
    "d_ne_dv": (1, 2, 32, 32, 24, 16, True, 16, 16, "float32", {}),
    "latent_192_128": (1, 1, 32, 32, 192, 128, True, 16, 16, "float32",
                       {}),
    "head_pack": (1, 4, 32, 32, 64, 64, True, 16, 16, "float32",
                  {"head_pack": True}),
    "head_pack_padded": (1, 2, 24, 40, 16, 16, True, 16, 16, "float32",
                         {"head_pack": True}),
    "packed_stats": (1, 1, 2048, 2048, 8, 8, True, 1024, 1024, "float32",
                     {"packed_stats": True}),
    "bf16": (1, 2, 64, 64, 16, 16, True, 16, 16, "bfloat16", {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_one_sweep_equals_two_sweeps_and_the_reference(case):
    b, h, tq, tk, d, dv, causal, bq, bk, dtype, variants = CASES[case]
    q, k, v, g = _operands(b, h, tq, tk, d, dv, dtype)
    kw = _static(d, causal, bq, bk, **variants)
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
            (2, 8192, 1024, 64, 64, 2): 39.3,
            (1, 8192, 1024, 64, 64, 4): 32.4}
    for (hpb, t, blk, d, dv, itemsize), mib in took.items():
        est = pk._bwd_fused_vmem_bytes(hpb, t, blk, blk, d, dv, itemsize,
                                       False) / 2 ** 20
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

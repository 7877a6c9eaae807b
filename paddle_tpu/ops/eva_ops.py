"""Ops of an EVA attention mixer: the chunk summariser `eva_pool` and
the aggregation `eva_attention`, each with its registered grad op.

Equations: docs/EVABYTE_BLOCK.md (arXiv:2302.04542 in the deterministic
form of the EvaByte release).  models/evabyte.py builds its mixer from
these through layers/eva.py; the kernels are ops/pallas_eva.py and, for
the window part, the flash kernels of ops/pallas_kernels.py.

Precision under AMP (contrib/mixed_precision): Q, K, V and the
summaries are bfloat16 on the MXU; Mu and Phi, the pooling logits and
their softmaxes, every running softmax statistic and LSE are float32
(fp16_utils._WHITE_KEEP_FP32, _WHITE_LOWP_OUT).

Every compute runs under a jax.named_scope (pt_eva_pool,
pt_eva_attention); the kernels are the Mosaic calls pt_eva_pool_fwd,
pt_eva_pool_bwd, pt_eva_chunk_fwd, pt_eva_chunk_bwd and, for a window's
own tokens, pt_flash_fwd and pt_flash_bwd_dkv.
"""

from __future__ import annotations

import math

import jax

from paddle_tpu.core.registry import REQUIRED, register_op
from paddle_tpu.ops import pallas_eva
from paddle_tpu.ops import pallas_kernels as pk

_ATTRS = {"heads": REQUIRED, "window": REQUIRED, "chunk": REQUIRED,
          "impl": ""}
_POOL_INPUTS = ("K", "V", "Mu", "Phi")
_ATTN_INPUTS = ("Q", "K", "V", "KSum", "VSum")


def _impl(x, attrs):
    """The impl an EVA op resolves to: the one asked for, else pallas
    on a TPU and xla elsewhere; xla too where the kernels cannot tile
    the sizes (pallas_eva.kernel_geom_ok).  x: a token-major [B, T,
    H*D] operand.  Raises what does not fit."""
    _, t, d = pallas_eva.check_shapes(x, attrs["heads"], attrs["window"],
                                      attrs["chunk"])
    impl = attrs["impl"] or pk._auto_impl()
    if impl != "xla" and not pallas_eva.kernel_geom_ok(
            t, d, attrs["window"], attrs["chunk"]):
        impl = "xla"
    return impl


def _scale(q, attrs):
    return 1.0 / math.sqrt(q.shape[-1] // attrs["heads"])


@register_op("eva_pool", inputs=_POOL_INPUTS, outputs=("KSum", "VSum"),
             attrs=_ATTRS)
def eva_pool(ins, attrs):
    """One learned summary a chunk of `chunk` tokens and head: K, V
    token-major [B, T, H*D] (K rotated), Mu, Phi [H, D] ->

        KSum_j = sum_m softmax_m(Mu . K_{cj+m}) K_{cj+m}
        VSum_j = sum_m softmax_m(Phi . K_{cj+m}) V_{cj+m}

    [B, T/chunk, H*D] in K's and V's dtypes, float32 inside.  impl: ""
    (pallas on a TPU, xla elsewhere), "pallas", "interpret", "xla"."""
    impl = _impl(ins["K"], attrs)
    pk._count_impl("eva_pool", impl)
    args = tuple(ins[s] for s in _POOL_INPUTS)
    with jax.named_scope("pt_eva_pool"):
        if impl == "xla":
            ks, vs = pallas_eva.eva_pool_xla(*args, attrs["heads"],
                                             attrs["chunk"])
        else:
            # see pallas_kernels._flash_attention_fwd: one call line
            with pk._obs_device.annotate("eva_pool"), pk._kernel_scope():
                ks, vs = pallas_eva.eva_pool_kernels(
                    *args, attrs["heads"], attrs["chunk"],
                    impl == "interpret")
    return {"KSum": ks, "VSum": vs}


def _pool_grad_reads_saved(ins, attrs):
    """Whether a recompute segment may take eva_pool's outputs from the
    forward pass in place of the op's replay: bound and the impl a
    kernel (OpDef.reads_saved).  The grad op itself reads neither: the
    backward kernel forms the chunks' softmaxes again from K."""
    return "KSum" in ins and "VSum" in ins \
        and _impl(ins["K"], attrs) != "xla"


@register_op("eva_pool_grad",
             inputs=_POOL_INPUTS + ("KSum", "VSum", "KSum@GRAD",
                                    "VSum@GRAD"),
             outputs=tuple(s + "@GRAD" for s in _POOL_INPUTS),
             optional=("KSum", "VSum", "KSum@GRAD", "VSum@GRAD"),
             attrs=_ATTRS, differentiable=False,
             reads_saved=_pool_grad_reads_saved)
def eva_pool_grad(ins, attrs):
    """Hand-written, as flash_attention_grad is and for its reason: the
    generic jax.vjp grad op would run pt_eva_pool_fwd a second time in
    every layer, and a recompute segment's replay a third.  A kernel
    impl runs pt_eva_pool_bwd and nothing else; the xla impl is jax.vjp
    over the forward op's compute."""
    args = tuple(ins[s] for s in _POOL_INPUTS)
    impl = _impl(ins["K"], attrs)

    def ct(slot, like):
        g = ins.get(slot + "@GRAD")
        return jax.numpy.zeros_like(like) if g is None else g

    if impl == "xla":
        outs, vjp = jax.vjp(
            lambda *a: pallas_eva.eva_pool_xla(*a, attrs["heads"],
                                               attrs["chunk"]), *args)
        grads = vjp((ct("KSum", outs[0]), ct("VSum", outs[1])))
    else:
        k, v = ins["K"], ins["V"]
        t = k.shape[1] // attrs["chunk"]
        like_k, like_v = (jax.ShapeDtypeStruct(
            (x.shape[0], t, x.shape[2]), x.dtype) for x in (k, v))
        with jax.named_scope("pt_eva_pool"), \
                pk._obs_device.annotate("eva_pool_grad"), \
                pk._kernel_scope():
            grads = pallas_eva.eva_pool_bwd_pallas(
                *args, ct("KSum", like_k), ct("VSum", like_v),
                heads=attrs["heads"], chunk=attrs["chunk"],
                interpret=impl == "interpret")
    return {s + "@GRAD": g for s, g in zip(_POOL_INPUTS, grads)}


@register_op("eva_attention", inputs=_ATTN_INPUTS,
             outputs=("Out", "LSE"), attrs=_ATTRS)
def eva_attention(ins, attrs):
    """Query i, in window w = i // window, against the tokens t of its
    own window with t <= i and the summaries j of earlier windows
    (chunk j // window < w), ONE softmax at the scale D^-1/2
    over both.  Q, K, V token-major [B, T, H*D], KSum, VSum [B,
    T/chunk, H*D] (eva_pool) -> Out [B, T, H*D] and LSE, the softmax's
    log-sum-exp a row, float32 [B T/window, H, window] (a window a row,
    as the window part's flash call keeps it; [B, H, T] where T <=
    window): with Out the residual eva_attention_grad reads.  impl: as
    eva_pool."""
    impl = _impl(ins["Q"], attrs)
    pk._count_impl("eva_attention", impl)
    args = tuple(ins[s] for s in _ATTN_INPUTS)
    geometry = (attrs["heads"], attrs["window"], attrs["chunk"],
                _scale(ins["Q"], attrs))
    with jax.named_scope("pt_eva_attention"):
        if impl == "xla":
            out, lse = pallas_eva.eva_attention_xla(*args, *geometry)
        else:
            with pk._obs_device.annotate("eva_attention"):
                out, lse = pallas_eva.eva_attention_kernels(
                    *args, *geometry, impl == "interpret")
    return {"Out": out, "LSE": lse}


def _attn_grad_reads_saved(ins, attrs):
    """Whether eva_attention_grad runs the backward kernels on the
    forward's Out and LSE: both bound and the impl a kernel."""
    return "Out" in ins and "LSE" in ins \
        and _impl(ins["Q"], attrs) != "xla"


@register_op("eva_attention_grad",
             inputs=_ATTN_INPUTS + ("Out", "LSE", "Out@GRAD"),
             outputs=tuple(s + "@GRAD" for s in _ATTN_INPUTS),
             optional=("Out", "LSE"), attrs=_ATTRS, differentiable=False,
             reads_saved=_attn_grad_reads_saved)
def eva_attention_grad(ins, attrs):
    """Hand-written, as flash_attention_grad is:

      * Out and LSE bound and the impl a kernel: the window part's
        flash backward and pt_eva_chunk_bwd on the saved Out
        and LSE; no forward kernel runs again;
      * unbound (a hand-built op) or the xla impl: jax.vjp over the
        forward op's compute.

    paddle_tpu_kernel_impl_total{kernel="eva_attention_grad"} says
    which: impl="saved" | "recompute"."""
    args = tuple(ins[s] for s in _ATTN_INPUTS)
    g = ins["Out@GRAD"]
    saved = _attn_grad_reads_saved(ins, attrs)
    pk._count_impl("eva_attention_grad", "saved" if saved else "recompute")
    if not saved:
        _, vjp = jax.vjp(
            lambda *a: eva_attention(dict(zip(_ATTN_INPUTS, a)),
                                     attrs)["Out"], *args)
        grads = vjp(g)
    else:
        with jax.named_scope("pt_eva_attention"), \
                pk._obs_device.annotate("eva_attention_grad"):
            grads = pallas_eva._aggregate_bwd(
                *args, ins["Out"], ins["LSE"], g, attrs["heads"],
                attrs["window"], attrs["chunk"], _scale(ins["Q"], attrs),
                _impl(ins["Q"], attrs) == "interpret")
    return {s + "@GRAD": v for s, v in zip(_ATTN_INPUTS, grads)}

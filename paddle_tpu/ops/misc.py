"""Misc op wave: tensor aliases, CTR helpers, accumulators and the
SelectedRows plumbing ops.

Reference parity (/root/reference/paddle/fluid/operators/):
  sign_op.cc, diag_op.cc, size_op.cc, fill_op.cc, minus_op.cc,
  is_empty_op.cc, flatten_op.cc (flatten), reshape_op.cc (reshape),
  squeeze_op.cc / unsqueeze_op.cc (non-2 variants), transpose_op.cc,
  fill_zeros_like_op.cc (fill_zeros_like2), cross_entropy_op.cc
  (cross_entropy2), multiplex_op.cc, mean_iou_op.h,
  bilinear_tensor_product_op.h, cvm_op.h, sampling_id_op.cc,
  uniform_random_batch_size_like_op.cc,
  gaussian_random_batch_size_like_op.cc, average_accumulates_op.h,
  lod_reset_op.cc, get_tensor_from_selected_rows_op.cc,
  merge_selected_rows_op.cc.

The non-"2" shape ops (flatten/reshape/squeeze/unsqueeze/transpose)
are the legacy single-output forms; the *2 forms with XShape side
outputs live in ops/basic.py.  Both exist in the reference registry,
so both are registered here for program-level parity.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from paddle_tpu.core.registry import REQUIRED, register_op
from paddle_tpu.core.scope import SelectedRows


# ---------------------------------------------------------------------------
# tiny tensor ops
# ---------------------------------------------------------------------------

@register_op("sign", inputs=("X",), outputs=("Out",))
def sign(ins, attrs):
    return {"Out": jnp.sign(ins["X"])}


@register_op("diag", inputs=("Diagonal",), outputs=("Out",),
             differentiable=False)
def diag(ins, attrs):
    """diag_op.cc: vector [N] -> diagonal matrix [N, N]."""
    return {"Out": jnp.diag(ins["Diagonal"])}


@register_op("size", inputs=("Input",), outputs=("Out",),
             differentiable=False)
def size(ins, attrs):
    return {"Out": jnp.asarray(
        int(np.prod(ins["Input"].shape) if ins["Input"].shape else 1),
        jax.dtypes.canonicalize_dtype(jnp.int64)).reshape(1)}


@register_op("fill", inputs=(), outputs=("Out",), differentiable=False,
             attrs={"value": REQUIRED, "shape": REQUIRED,
                    "dtype": "float32", "force_cpu": False})
def fill(ins, attrs):
    """fill_op.cc: fill Out with the explicit per-element value list."""
    vals = np.asarray(attrs["value"], np.dtype(attrs["dtype"]))
    return {"Out": jnp.asarray(vals.reshape(
        [int(s) for s in attrs["shape"]]))}


@register_op("minus", inputs=("X", "Y"), outputs=("Out",))
def minus(ins, attrs):
    return {"Out": ins["X"] - ins["Y"]}


@register_op("is_empty", inputs=("X",), outputs=("Out",),
             differentiable=False)
def is_empty(ins, attrs):
    return {"Out": jnp.asarray(
        int(np.prod(ins["X"].shape)) == 0).reshape(())}


# legacy single-output shape ops ------------------------------------------

@register_op("flatten", inputs=("X",), outputs=("Out",),
             attrs={"axis": 1})
def flatten(ins, attrs):
    x = ins["X"]
    ax = int(attrs["axis"])
    lead = int(np.prod(x.shape[:ax])) if ax else 1
    return {"Out": x.reshape(lead, -1)}


@register_op("reshape", inputs=("X", "Shape"), outputs=("Out",),
             optional=("Shape",), attrs={"shape": REQUIRED})
def reshape(ins, attrs):
    return {"Out": ins["X"].reshape(
        [int(s) for s in attrs["shape"]])}


@register_op("squeeze", inputs=("X",), outputs=("Out",),
             attrs={"axes": []})
def squeeze(ins, attrs):
    x = ins["X"]
    axes = [int(a) for a in attrs["axes"]]
    if not axes:
        axes = [i for i, s in enumerate(x.shape) if s == 1]
    axes = [a for a in axes if x.shape[a] == 1]
    return {"Out": jnp.squeeze(x, axis=tuple(axes))}


@register_op("unsqueeze", inputs=("X",), outputs=("Out",),
             attrs={"axes": REQUIRED})
def unsqueeze(ins, attrs):
    x = ins["X"]
    for a in sorted(int(a) for a in attrs["axes"]):
        x = jnp.expand_dims(x, a)
    return {"Out": x}


@register_op("transpose", inputs=("X",), outputs=("Out",),
             attrs={"axis": REQUIRED})
def transpose(ins, attrs):
    return {"Out": jnp.transpose(ins["X"],
                                 [int(a) for a in attrs["axis"]])}


@register_op("fill_zeros_like2", inputs=("X",), outputs=("Out",),
             differentiable=False, attrs={"dtype": -1})
def fill_zeros_like2(ins, attrs):
    return {"Out": jnp.zeros_like(ins["X"])}


@register_op("cross_entropy2", inputs=("X", "Label"),
             outputs=("Y", "MatchX"),
             attrs={"ignore_index": -100})
def cross_entropy2(ins, attrs):
    """cross_entropy_op.cc CrossEntropyOp2: hard-label CE over
    probabilities; MatchX caches the picked probability for the
    backward."""
    x, label = ins["X"], ins["Label"]
    n = x.shape[0]
    lbl = label.reshape(n).astype(jnp.int32)
    picked = jnp.take_along_axis(
        x.reshape(n, -1), lbl[:, None], axis=1)
    ignore = (lbl == attrs["ignore_index"])[:, None]
    y = jnp.where(ignore, 0.0,
                  -jnp.log(jnp.maximum(picked, 1e-20)))
    return {"Y": y, "MatchX": picked}


# ---------------------------------------------------------------------------
# selection / metrics / CTR
# ---------------------------------------------------------------------------

@register_op("multiplex", inputs=("X", "Ids"), outputs=("Out",),
             duplicable=("X",))
def multiplex(ins, attrs):
    """multiplex_op.cc: Ids [N,1] picks, per row n, row n of candidate
    X[ids[n]]."""
    xs = ins["X"]
    ids = ins["Ids"].reshape(-1).astype(jnp.int32)
    stacked = jnp.stack(xs, axis=0)          # [K, N, ...]
    n = stacked.shape[1]
    return {"Out": stacked[ids, jnp.arange(n)]}


@register_op("mean_iou",
             inputs=("Predictions", "Labels", "InWrongs", "InCorrects",
                     "InMeanIou"),
             outputs=("OutMeanIou", "OutWrong", "OutCorrect"),
             duplicable=("InWrongs", "InCorrects", "InMeanIou"),
             optional=("InWrongs", "InCorrects", "InMeanIou"),
             differentiable=False,
             attrs={"num_classes": REQUIRED})
def mean_iou(ins, attrs):
    """mean_iou_op.h: per-class correct/wrong counts; iou_c =
    correct_c/(correct_c+wrong_c); mean over classes present."""
    nc = int(attrs["num_classes"])
    pred = ins["Predictions"].reshape(-1).astype(jnp.int32)
    lbl = ins["Labels"].reshape(-1).astype(jnp.int32)
    hit = pred == lbl
    correct = jnp.zeros(nc, jnp.int32).at[lbl].add(
        hit.astype(jnp.int32), mode="drop")
    wrong = jnp.zeros(nc, jnp.int32).at[lbl].add(
        (~hit).astype(jnp.int32), mode="drop")
    wrong = wrong.at[pred].add((~hit).astype(jnp.int32), mode="drop")
    for w in ins.get("InWrongs") or []:
        wrong = wrong + w
    for c in ins.get("InCorrects") or []:
        correct = correct + c
    denom = wrong + correct
    valid = denom > 0
    iou = jnp.where(valid, correct / jnp.maximum(denom, 1), 0.0)
    miou = iou.sum() / jnp.maximum(valid.sum(), 1)
    for m in ins.get("InMeanIou") or []:
        miou = miou + m.reshape(())
    return {"OutMeanIou": miou.reshape(1).astype(jnp.float32),
            "OutWrong": wrong, "OutCorrect": correct}


@register_op("bilinear_tensor_product",
             inputs=("X", "Y", "Weight", "Bias"), outputs=("Out",),
             optional=("Bias",))
def bilinear_tensor_product(ins, attrs):
    """bilinear_tensor_product_op.h: out[n,k] = x[n] @ W[k] @ y[n]."""
    x, y, w = ins["X"], ins["Y"], ins["Weight"]
    out = jnp.einsum("ni,kij,nj->nk", x, w, y)
    if ins.get("Bias") is not None:
        out = out + ins["Bias"]
    return {"Out": out}


@register_op("cvm", inputs=("X", "CVM"), outputs=("Y",),
             optional=("CVM",), attrs={"use_cvm": True})
def cvm(ins, attrs):
    """cvm_op.h: first two features are show/click counters; use_cvm
    log-transforms them in place, else they are dropped."""
    x = ins["X"]
    if attrs["use_cvm"]:
        f0 = jnp.log(x[:, 0:1] + 1.0)
        f1 = jnp.log(x[:, 1:2] + 1.0) - f0
        return {"Y": jnp.concatenate([f0, f1, x[:, 2:]], axis=1)}
    return {"Y": x[:, 2:]}


@register_op("sampling_id", inputs=("X", "SeedOffset"),
             outputs=("Out",), optional=("SeedOffset",),
             differentiable=False,
             attrs={"min": 0.0, "max": 1.0, "seed": 0})
def sampling_id(ins, attrs):
    """sampling_id_op.cc: sample a column index per row of the prob
    matrix X (categorical draw).  Optional SeedOffset tensor is folded
    into the key (the dropout-op pattern) so draws inside a lax.scan
    vary per step — a bare attr seed is traced once and would repeat
    the same draw every iteration.

    SeedOffset contract: a small non-negative integer scalar (a step
    position).  With jax x64 disabled an int64 offset silently narrows
    to int32, so a negative value would wrap differently per x64 mode;
    the clamp below pins the behavior (negatives fold as 0)."""
    x = ins["X"]
    key = jax.random.PRNGKey(attrs["seed"] or 0)
    off = ins.get("SeedOffset")
    if off is not None:
        from paddle_tpu.ops.rng import fold_seed_offset

        key = fold_seed_offset(key, off)
    u = jax.random.uniform(key, (x.shape[0], 1), x.dtype,
                           attrs["min"], attrs["max"])
    cdf = jnp.cumsum(x, axis=1)
    idx = jnp.sum((cdf < u).astype(jnp.int64), axis=1)
    return {"Out": jnp.clip(idx, 0, x.shape[1] - 1)}


@register_op("uniform_random_batch_size_like", inputs=("Input",),
             outputs=("Out",), differentiable=False, host_only=True,
             attrs={"shape": REQUIRED, "input_dim_idx": 0,
                    "output_dim_idx": 0, "min": -1.0, "max": 1.0,
                    "seed": 0, "dtype": "float32"})
def uniform_random_batch_size_like(ins, attrs):
    """uniform_random_batch_size_like_op.cc: host-side init (like
    uniform_random) with the batch dim copied from Input."""
    shape = [int(s) for s in attrs["shape"]]
    shape[int(attrs["output_dim_idx"])] = \
        ins["Input"].shape[int(attrs["input_dim_idx"])]
    rng = np.random.RandomState(attrs["seed"] or None)
    return {"Out": jnp.asarray(rng.uniform(
        attrs["min"], attrs["max"], shape).astype(attrs["dtype"]))}


@register_op("gaussian_random_batch_size_like", inputs=("Input",),
             outputs=("Out",), differentiable=False, host_only=True,
             attrs={"shape": REQUIRED, "input_dim_idx": 0,
                    "output_dim_idx": 0, "mean": 0.0, "std": 1.0,
                    "seed": 0, "dtype": "float32"})
def gaussian_random_batch_size_like(ins, attrs):
    shape = [int(s) for s in attrs["shape"]]
    shape[int(attrs["output_dim_idx"])] = \
        ins["Input"].shape[int(attrs["input_dim_idx"])]
    rng = np.random.RandomState(attrs["seed"] or None)
    return {"Out": jnp.asarray(
        (rng.randn(*shape) * attrs["std"] + attrs["mean"]).astype(
            attrs["dtype"]))}


@register_op("average_accumulates",
             inputs=("param", "in_sum_1", "in_sum_2", "in_sum_3",
                     "in_num_accumulates", "in_old_num_accumulates",
                     "in_num_updates"),
             outputs=("out_sum_1", "out_sum_2", "out_sum_3",
                      "out_num_accumulates", "out_old_num_accumulates",
                      "out_num_updates"),
             differentiable=False,
             in_place={"out_sum_1": "in_sum_1",
                       "out_sum_2": "in_sum_2",
                       "out_sum_3": "in_sum_3",
                       "out_num_accumulates": "in_num_accumulates",
                       "out_old_num_accumulates":
                           "in_old_num_accumulates",
                       "out_num_updates": "in_num_updates"},
             attrs={"average_window": 0.0,
                    "max_average_window": REQUIRED,
                    "min_average_window": 10000})
def average_accumulates(ins, attrs):
    """average_accumulates_op.h: ModelAverage accumulator rotation with
    the 16384-step precision spill and window-restart conditions,
    expressed as where-selects so it jits."""
    k_max = 16384
    p = ins["param"]
    s1 = ins["in_sum_1"] + p
    s2 = ins["in_sum_2"]
    s3 = ins["in_sum_3"]
    num_acc = ins["in_num_accumulates"].reshape(()) + 1
    old_acc = ins["in_old_num_accumulates"].reshape(())
    num_upd = ins["in_num_updates"].reshape(()) + 1
    spill = (num_upd % k_max) == 0
    s2 = jnp.where(spill, s2 + s1, s2)
    s1 = jnp.where(spill, jnp.zeros_like(s1), s1)
    window = jnp.minimum(
        jnp.asarray(float(attrs["max_average_window"])),
        num_upd.astype(jnp.float32) * attrs["average_window"])
    restart = ((num_acc >= int(attrs["min_average_window"]))
               & (num_acc.astype(jnp.float32) >= window))
    s3 = jnp.where(restart, s1 + s2, s3)
    s1 = jnp.where(restart, jnp.zeros_like(s1), s1)
    s2 = jnp.where(restart, jnp.zeros_like(s2), s2)
    old_acc = jnp.where(restart, num_acc, old_acc)
    num_acc = jnp.where(restart, jnp.zeros_like(num_acc), num_acc)
    return {"out_sum_1": s1, "out_sum_2": s2, "out_sum_3": s3,
            "out_num_accumulates": num_acc.reshape(
                ins["in_num_accumulates"].shape),
            "out_old_num_accumulates": old_acc.reshape(
                ins["in_old_num_accumulates"].shape),
            "out_num_updates": num_upd.reshape(
                ins["in_num_updates"].shape)}


@register_op("lod_reset", inputs=("X", "Y"), outputs=("Out",),
             optional=("Y",), attrs={"target_lod": []})
def lod_reset(ins, attrs):
    """lod_reset_op.cc re-spec: under the padded [B,T,...]+Length
    representation the values are unchanged — sequence re-segmentation
    is carried by the explicit Length tensors produced by the sequence
    layers, so this is the identity on values (parity shim)."""
    return {"Out": ins["X"]}


# -- SelectedRows plumbing (host/interpreter path) -------------------------

@register_op("get_tensor_from_selected_rows", inputs=("X",),
             outputs=("Out",), differentiable=False, host_only=True)
def get_tensor_from_selected_rows(ins, attrs):
    """get_tensor_from_selected_rows_op.cc: expose the value tensor of
    a SelectedRows variable."""
    x = ins["X"]
    if isinstance(x, SelectedRows):
        return {"Out": x.values}
    return {"Out": x}


@register_op("merge_selected_rows", inputs=("X",), outputs=("Out",),
             differentiable=False, host_only=True)
def merge_selected_rows(ins, attrs):
    """merge_selected_rows_op.cc: sum duplicate rows so each row id
    appears once."""
    x = ins["X"]
    if not isinstance(x, SelectedRows):
        return {"Out": x}
    rows = np.asarray(x.rows)
    uniq, inv = np.unique(rows, return_inverse=True)
    vals = jnp.zeros((len(uniq),) + tuple(x.values.shape[1:]),
                     x.values.dtype).at[jnp.asarray(inv)].add(x.values)
    return {"Out": SelectedRows(jnp.asarray(uniq), vals, x.height)}


def _zero_ct(x):
    # integer/bool values take float0 cotangents (jax's symbolic zero
    # type): an int zeros_like breaks vjp tree matching
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(x.shape, dtype=jax.dtypes.float0)


def _outputs_from_saved(grad_def, attrs, op_ins, saved):
    """An op's outputs inside a recompute segment, where the forward
    pass kept them: `saved` ({slot: value}) as they are, no compute;
    differentiated by the op's registered grad op on the replayed
    inputs, the saved outputs and the outputs' cotangents.  A cotangent
    the grad op declares no slot for (flash_attention's LSE) is
    dropped, as the plain backward drops it."""
    from paddle_tpu.core.registry import GRAD_SUFFIX

    @jax.custom_vjp
    def outputs(op_ins, saved):
        return saved

    def fwd(op_ins, saved):
        return saved, (op_ins, saved)

    def bwd(res, cts):
        op_ins, saved = res
        grad_ins = {**op_ins, **saved}
        for slot, g in cts.items():
            if slot + GRAD_SUFFIX in grad_def.inputs:
                grad_ins[slot + GRAD_SUFFIX] = g
        grads = grad_def.compute(grad_ins, attrs)
        return ({slot: grads[slot + GRAD_SUFFIX] for slot in op_ins},
                jax.tree_util.tree_map(_zero_ct, saved))

    outputs.defvjp(fwd, bwd)
    return outputs(op_ins, saved)


@register_op("recompute_segment_grad",
             inputs=("X", "OutGrad", "Saved"), outputs=("XGrad",),
             duplicable=("X", "OutGrad", "XGrad", "Saved"),
             optional=("Saved",),
             attrs={"ops": REQUIRED, "in_names": REQUIRED,
                    "out_names": REQUIRED, "grad_in_names": REQUIRED,
                    "saved_names": []},
             differentiable=False)
def recompute_segment_grad(ins, attrs):
    """Backward of one recompute segment (reference incubate
    RecomputeOptimizer; see backward.py _append_backward_recompute).

    Replays the serialized forward ops from the segment's boundary
    inputs inside jax.checkpoint and vjps the replay: residuals are the
    BOUNDARY values only, and the checkpoint's optimization barrier
    stops XLA from CSE-ing the replay against the forward pass — the
    intra-segment activations are genuinely not kept live between
    forward and backward.

    But for `Saved` (names in `saved_names`): outputs of segment ops
    whose registered grad op reads them (flash_attention's Out and
    LSE), bound from the forward pass.  Such an op is not replayed for
    its outputs: they are the bound values, arguments of the
    checkpointed function and so residuals, and its backward is the
    registered grad op (`_outputs_from_saved`).  XLA does not CSE a
    Mosaic call, so the replay ran the forward kernel a second time in
    every segment (PERF.md, PR 33).  The grad op says whether it will
    read them (`OpDef.reads_saved`: flash says no on the XLA impl);
    where it will not, and in a desc from before the slot, the op is
    replayed and differentiated by jax.vjp like every other."""
    from paddle_tpu.core.program import OpDesc, op_scope
    from paddle_tpu.core.registry import get_op_def

    ops = [OpDesc.from_dict(d) for d in attrs["ops"]]
    in_names = list(attrs["in_names"])
    out_names = list(attrs["out_names"])
    grad_in = list(attrs["grad_in_names"])
    xs = dict(zip(in_names, ins["X"]))
    gs = dict(zip(out_names, ins["OutGrad"]))
    diff = {k: xs[k] for k in grad_in}
    nondiff = {k: v for k, v in xs.items() if k not in diff}
    bound = dict(zip(attrs.get("saved_names", ()), ins.get("Saved", ())))

    def replay(d, bound):
        env = dict(nondiff)
        env.update(d)
        for op in ops:
            od = get_op_def(op.type)
            op_ins = {}
            for slot, names in op.inputs.items():
                vals = [env.get(n) for n in names]
                if slot in od.duplicable:
                    op_ins[slot] = vals
                elif vals and vals[0] is not None:
                    op_ins[slot] = vals[0]
            saved = {slot: bound[names[0]]
                     for slot, names in op.outputs.items()
                     if names and names[0] in bound}
            grad_def = get_op_def(op.type + "_grad") if saved else None
            with op_scope(op):
                if saved and grad_def.reads_saved and \
                        grad_def.reads_saved({**op_ins, **saved},
                                             op.attrs):
                    outs = _outputs_from_saved(grad_def, op.attrs,
                                               op_ins, saved)
                else:
                    outs = od.compute(op_ins, op.attrs) or {}
            for slot, names in op.outputs.items():
                if slot not in outs:
                    continue
                vals = outs[slot]
                if not isinstance(vals, (list, tuple)):
                    vals = [vals]
                for n, v in zip(names, vals):
                    env[n] = v
        return {n: env[n] for n in out_names}

    replay = jax.checkpoint(replay)
    primal, vjp = jax.vjp(lambda d: replay(d, bound), diff)

    cts = {}
    for n in out_names:
        g = gs.get(n)
        p = primal[n]
        if g is None:
            cts[n] = _zero_ct(p)
        else:
            if g.shape != p.shape and tuple(
                    d for d in g.shape if d != 1) == tuple(
                    d for d in p.shape if d != 1):
                g = jnp.reshape(g, p.shape)
            cts[n] = g
    (din,) = vjp(cts)
    return {"XGrad": [din[k] for k in grad_in]}


@register_op("fill_any_like", inputs=("X",), outputs=("Out",),
             attrs={"value": 0.0, "dtype": -1}, differentiable=False)
def fill_any_like(ins, attrs):
    """fill_any_like_op.cc: constant tensor with X's shape (dtype -1
    keeps X's dtype, like the reference's VarType -1 sentinel)."""
    x = ins["X"]
    dt = attrs.get("dtype", -1)
    if dt in (-1, None):
        dtype = x.dtype
    else:
        try:
            dtype = np.dtype(dt)
        except TypeError:
            raise ValueError(
                f"fill_any_like: unsupported dtype attr {dt!r} (use a "
                "numpy dtype name or -1 to keep X's dtype)") from None
    return {"Out": jnp.full(x.shape, attrs["value"], dtype)}


def _splitmix64(v):
    """Deterministic 64-bit mix (the role XXH64 plays in hash_op.h:40 —
    bucketing, not cryptography)."""
    v = (v + np.uint64(0x9E3779B97F4A7C15)) & np.uint64(0xFFFFFFFFFFFFFFFF)
    v = ((v ^ (v >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)) \
        & np.uint64(0xFFFFFFFFFFFFFFFF)
    v = ((v ^ (v >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)) \
        & np.uint64(0xFFFFFFFFFFFFFFFF)
    return v ^ (v >> np.uint64(31))


@register_op("hash", inputs=("X",), outputs=("Out",),
             attrs={"num_hash": 1, "mod_by": 100000},
             differentiable=False, host_only=True)
def hash_op(ins, attrs):
    """hash_op.cc: each row's ids hash to num_hash buckets in
    [0, mod_by); output [..., num_hash, 1] like HashOutputSize.
    XXH64(seed=ihash) becomes a splitmix64 over (row-digest, seed) —
    same contract (deterministic, seed-separated buckets)."""
    x = np.asarray(ins["X"]).astype(np.int64)
    rows = x.reshape(-1, x.shape[-1]).astype(np.uint64)
    num_hash = int(attrs["num_hash"])
    mod_by = np.uint64(int(attrs["mod_by"]))
    with np.errstate(over="ignore"):
        digest = np.zeros(rows.shape[0], np.uint64)
        for col in range(rows.shape[1]):
            digest = _splitmix64(digest ^ _splitmix64(rows[:, col]))
        out = np.empty((rows.shape[0], num_hash, 1), np.int64)
        for ihash in range(num_hash):
            out[:, ihash, 0] = (_splitmix64(digest ^ np.uint64(ihash))
                                % mod_by).astype(np.int64)
    return {"Out": out.reshape(x.shape[:-1] + (num_hash, 1))}


@register_op("unique", inputs=("X",), outputs=("Out", "Index"),
             attrs={"dtype": "int32"}, differentiable=False,
             host_only=True)
def unique_op(ins, attrs):
    """unique_op.cc: 1-D unique values in first-occurrence order + the
    index of each input element in Out.  Variable-length output keeps
    this a host op like the reference's CPU-only kernel."""
    x = np.asarray(ins["X"]).reshape(-1)
    _, first_idx, inverse = np.unique(x, return_index=True,
                                      return_inverse=True)
    order = np.argsort(first_idx)            # first-occurrence order
    out = x[np.sort(first_idx)]
    remap = np.empty_like(order)
    remap[order] = np.arange(len(order))
    index = remap[inverse].astype(np.dtype(attrs["dtype"]))
    return {"Out": out, "Index": index}

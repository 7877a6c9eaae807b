"""Static whole-program shape/dtype inference + sharding checker.

Shape half: walk block 0 in op order re-running the registry's
``infer_shapes`` (the same jax.eval_shape machinery append_op uses)
over the DECLARED VarDesc shapes, and flag every declared-vs-inferred
mismatch with a typed diagnostic naming op-index / slot / var.  A
transpiler that rewrites an op chain but leaves a stale VarDesc shape
behind is caught here at transpile time instead of at trace time (or
on chip).  Unknown dims (-1) compare loose; inference failures mark
the op's outputs unknown rather than guessing.

Sharding half (GSPMD, Xu et al., 2021): validate every
``VarDesc.sharding`` annotation against a ``MeshPlan`` statically —
axis names exist in the plan, no axis is used twice in one spec, the
spec is no longer than the var rank, and every sharded dim divides
evenly by the product of its axis sizes (ZeRO x tp composition: a
("tp","dp") dim must divide by tp*dp).  Also closes the two escapes
the GSPMD rounds found dynamically:

  * the silent shard_map divisibility fallback — a flash_attention op
    tagged with gspmd axes whose batch/head extents don't divide the
    plan falls back to the unsharded kernel at trace time with no
    signal; here it is a typed diagnostic at annotate time;
  * the untagged-grad-op escape — a tagged flash_attention whose
    flash_attention_grad sibling lost its tags runs its kernels
    inside the SPMD partitioner, outside shard_map ("Mosaic kernels
    cannot be automatically partitioned", caught once at the export
    gate, at zero chip cost only by luck).

docs/ANALYSIS.md has the rule table.
"""

from __future__ import annotations

import numpy as np

from paddle_tpu.analysis.verifier import Diagnostic, VerifierError

_ERROR = "error"
_WARNING = "warning"


class ShapeCheckError(VerifierError):
    """Static shape/dtype inference found declared-vs-inferred
    mismatches."""

    code = "shape_check"


class ShardingCheckError(VerifierError):
    """A VarDesc.sharding annotation is illegal for the MeshPlan."""

    code = "sharding_check"


def _spec_of(var):
    import jax

    if var is None or var.shape is None or var.dtype is None:
        return None
    return jax.ShapeDtypeStruct(tuple(var.shape), np.dtype(var.dtype))


def infer_program_shapes(program):
    """Re-infer every block-0 op's output shapes/dtypes from the
    declared inputs.  Returns (env, diags): env maps var name ->
    ShapeDtypeStruct for every var whose shape inference succeeded
    (declared shapes seed the walk; inferred shapes flow forward),
    diags carries ``shape-mismatch`` / ``dtype-mismatch`` /
    ``infer-failed`` diagnostics."""
    import jax

    from paddle_tpu.core import registry

    block = program.global_block()
    diags = []
    env = {}
    for name, v in block.vars.items():
        spec = _spec_of(v)
        if spec is not None:
            env[name] = spec

    for i, op in enumerate(block.ops):
        if not registry.has_op_def(op.type):
            continue  # the structural verifier owns unknown-op
        try:
            op_def = registry.get_op_def(op.type)
        except KeyError:
            continue
        if op_def.host_only:
            continue
        ins_specs = {}
        ok = True
        for slot, names in op.inputs.items():
            specs = []
            for n in names:
                spec = env.get(n)
                if spec is None:
                    ok = False
                    break
                specs.append(spec)
            if not ok:
                break
            if slot in op_def.duplicable:
                ins_specs[slot] = specs
            elif specs:
                ins_specs[slot] = specs[0]
        if not ok:
            continue
        try:
            out = registry.infer_shapes(
                op_def, ins_specs, op.attrs, strict=True,
                var_names={s: list(ns) for s, ns in op.inputs.items()})
        except registry.InferShapeError as e:
            diags.append(Diagnostic(
                "infer-failed", str(e), severity=_WARNING,
                block_idx=0, op_idx=i, op_type=op.type))
            continue
        if out is None:
            continue
        for slot, names in op.outputs.items():
            if slot not in out:
                continue
            specs = out[slot]
            if not isinstance(specs, list):
                specs = [specs]
            for n, spec in zip(names, specs):
                declared = env.get(n)
                v = block.vars.get(n)
                if v is not None and v.shape is not None and \
                        declared is not None:
                    if len(declared.shape) != len(spec.shape) or any(
                            dd not in (-1, di) and di != -1
                            for dd, di in zip(declared.shape,
                                              spec.shape)):
                        diags.append(Diagnostic(
                            "shape-mismatch",
                            f"slot {slot!r}: declared shape "
                            f"{tuple(declared.shape)} but inference "
                            f"gives {tuple(spec.shape)}",
                            block_idx=0, op_idx=i, op_type=op.type,
                            var=n))
                    elif str(np.dtype(declared.dtype)) != \
                            str(np.dtype(spec.dtype)):
                        # f32 <-> bf16 divergence is the AMP contract:
                        # rewrite_program casts op INPUTS and lets XLA
                        # type-propagate, leaving intermediates'
                        # declared dtypes f32 by design (bf16_transpile
                        # relies on exactly this) — warning, not error.
                        # Any OTHER dtype divergence (int8 vs f32, int
                        # vs float) is a stale rewrite.
                        pair = {str(np.dtype(declared.dtype)),
                                str(np.dtype(spec.dtype))}
                        # ... and 64->32-bit truncation pairs: the
                        # declared IR is platform-independent (int64
                        # labels), while eval_shape runs under this
                        # process's x64-disabled jax config
                        amp_loose = pair in ({"float32", "bfloat16"},
                                             {"int64", "int32"},
                                             {"float64", "float32"})
                        diags.append(Diagnostic(
                            "dtype-mismatch",
                            f"slot {slot!r}: declared dtype "
                            f"{np.dtype(declared.dtype)} but "
                            f"inference gives {np.dtype(spec.dtype)}"
                            + (" (amp-legal pair)" if amp_loose
                               else ""),
                            severity=_WARNING if amp_loose else _ERROR,
                            block_idx=0, op_idx=i, op_type=op.type,
                            var=n))
                # inferred shapes flow forward (filling -1 dims where
                # inference pinned them keeps downstream ops checked)
                merged = spec
                if declared is not None and \
                        len(declared.shape) == len(spec.shape):
                    merged = jax.ShapeDtypeStruct(
                        tuple(di if di != -1 else dd
                              for dd, di in zip(declared.shape,
                                                spec.shape)),
                        spec.dtype)
                env[n] = merged
    return env, diags


def check_shapes(program, raise_=True, label=""):
    """Static shape/dtype check of block 0.  Returns diagnostics;
    raises ShapeCheckError on any error-severity one."""
    _, diags = infer_program_shapes(program)
    if raise_ and any(d.severity == _ERROR for d in diags):
        raise ShapeCheckError(diags, label=label)
    return diags


# ---------------------------------------------------------------------------
# sharding checker
# ---------------------------------------------------------------------------

def _axes_of(entry):
    if entry is None:
        return ()
    if isinstance(entry, str):
        return (entry,)
    return tuple(entry)


def check_sharding(program, plan, raise_=True, label=""):
    """Validate every VarDesc.sharding annotation (and the gspmd
    attention tags) against ``plan`` (parallel/gspmd.MeshPlan).
    Returns diagnostics; raises ShardingCheckError on errors."""
    diags = []
    for block in program.blocks:
        for name, v in block.vars.items():
            spec = v.sharding
            if spec is None:
                continue
            if v.shape is None:
                diags.append(Diagnostic(
                    "sharding-unknown-shape",
                    "sharded var has no declared shape",
                    severity=_WARNING, block_idx=block.idx, var=name))
                continue
            if len(spec) > len(v.shape):
                diags.append(Diagnostic(
                    "sharding-rank",
                    f"spec {spec!r} is longer than the var rank "
                    f"{len(v.shape)}",
                    block_idx=block.idx, var=name))
                continue
            used = []
            for dim, entry in enumerate(spec):
                axes = _axes_of(entry)
                factor = 1
                for a in axes:
                    if a not in plan.axes:
                        diags.append(Diagnostic(
                            "sharding-unknown-axis",
                            f"dim {dim}: axis {a!r} is not in the "
                            f"plan {plan!r}",
                            block_idx=block.idx, var=name))
                        continue
                    if a in used:
                        diags.append(Diagnostic(
                            "sharding-axis-reuse",
                            f"dim {dim}: axis {a!r} already shards "
                            "another dim of this var (GSPMD forbids "
                            "axis reuse within one spec)",
                            block_idx=block.idx, var=name))
                    used.append(a)
                    factor *= plan.axis_size(a)
                extent = v.shape[dim]
                if extent is not None and extent >= 0 and factor > 1 \
                        and extent % factor != 0:
                    diags.append(Diagnostic(
                        "sharding-indivisible",
                        f"dim {dim}: extent {extent} is not divisible "
                        f"by {'x'.join(_axes_of(entry))} = {factor}",
                        block_idx=block.idx, var=name))

    # attention tag rules: divisibility must hold statically (the
    # trace-time fallback is silent) and fwd/grad tags must pair
    gb = program.global_block()
    tagged = []
    for i, op in enumerate(gb.ops):
        if op.type not in ("flash_attention", "flash_attention_grad"):
            continue
        ba = op.attrs.get("gspmd_batch_axis") or None
        ha = op.attrs.get("gspmd_head_axis") or None
        if op.type == "flash_attention":
            tagged.append((i, op, ba or ha))
        if ba is None and ha is None:
            continue
        qname = (op.inputs.get("Q") or [None])[0]
        qvar = gb.vars.get(qname) if qname else None
        heads = op.attrs.get("heads") or None
        if qvar is None or qvar.shape is None \
                or len(qvar.shape) != (3 if heads else 4):
            continue
        # token-major [B, T, H*D] with the head count an attr, or
        # [B, H, T, D]
        B, H = qvar.shape[0], heads or qvar.shape[1]
        for axis, extent, what in ((ba, B, "batch"), (ha, H, "head")):
            if axis is None:
                continue
            if axis not in plan.axes:
                diags.append(Diagnostic(
                    "sharding-unknown-axis",
                    f"gspmd_{what}_axis {axis!r} is not in the plan "
                    f"{plan!r}",
                    block_idx=0, op_idx=i, op_type=op.type))
            elif extent >= 0 and extent % plan.axis_size(axis) != 0:
                diags.append(Diagnostic(
                    "sharding-indivisible",
                    f"gspmd_{what}_axis {axis!r}: {what} extent "
                    f"{extent} is not divisible by "
                    f"{plan.axis_size(axis)} — shard_map would fall "
                    "back to the unsharded kernel SILENTLY at trace "
                    "time",
                    block_idx=0, op_idx=i, op_type=op.type))
        tp = plan.axis_size(ha) if ha in plan.axes else 1
        if heads and H % tp == 0 and qvar.shape[2] >= 0 \
                and qvar.shape[2] // H == 64 and (H // tp) % 2:
            diags.append(Diagnostic(
                "attention-head-layout",
                f"{H // tp} heads of 64 lanes a shard do not fill "
                "128-lane blocks: every shard transposes q, k, v and "
                "out to [B, H, T, D] and back (the same numbers at "
                "the cost of the copies; ops/pallas_kernels.py "
                "_flash_layout)",
                severity=_WARNING, block_idx=0, op_idx=i,
                op_type=op.type))
    if any(t[2] for t in tagged):
        for i, op in enumerate(gb.ops):
            if op.type != "flash_attention_grad":
                continue
            if not (op.attrs.get("gspmd_batch_axis") or
                    op.attrs.get("gspmd_head_axis")):
                diags.append(Diagnostic(
                    "sharding-untagged-grad",
                    "flash_attention ops are gspmd-tagged but this "
                    "grad op is not: it runs the backward kernels "
                    "(and, without saved Out/LSE, the forward again) "
                    "under the GRAD op's attrs, so they land inside "
                    "the SPMD partitioner untagged ('Mosaic kernels "
                    "cannot be automatically partitioned')",
                    block_idx=0, op_idx=i, op_type=op.type))

    if raise_ and any(d.severity == _ERROR for d in diags):
        raise ShardingCheckError(diags, label=label)
    return diags

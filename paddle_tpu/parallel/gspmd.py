"""GSPMD pod-scale front-end: one mesh plan, per-var PartitionSpec
annotations on the Program IR, the whole train step as ONE pjit program.

ROADMAP "New directions" #3 (ISSUE 8): today mesh parallelism lives in
hand-rolled modules (parallel/zero.py rule closures, ring_attention /
ulysses shard_map wrappers, pipeline.py schedules) stitched around the
executor, so the compiler never sees the whole step.  This module is
the spec-carrying half of the replacement:

  * ``MeshPlan`` — named dp/tp/pp axes over ``jax.sharding.Mesh``
    (SNIPPETS [1] is the pjit/partitioning exemplar; [2]/[3] the
    NamedSharding idiom).  dp carries the batch, tp carries tensor
    splits, pp places stage-stacked pipeline params; any extra axes
    (sp/ep) ride along by name.
  * annotation passes — ``annotate_zero3`` (ZeRO-3 as a sharding SPEC:
    params + optimizer state dim-sharded over dp, all-gathered at use
    sites by the XLA SPMD partitioner — the communication pattern
    DeepSpeed implements by hand) and ``annotate_tp_transformer``
    (Megatron-style column/row splits as tp PartitionSpecs on the
    existing fc layers, keyed on the transformer models' deterministic
    param-prefix name grammar).  Annotations live on
    ``VarDesc.sharding`` (serialized with the program, hashed into the
    compiled-program fingerprint).
  * ``tag_attention_ops`` — flash_attention IR ops get
    ``gspmd_batch_axis``/``gspmd_head_axis`` attrs so the Pallas
    kernel runs under shard_map on the same mesh (attention is
    independent per (batch, head) row, so the dp x tp split is exact);
    divisibility is re-checked at trace time with a plain fallback.

``transpiler.sharding_transpiler.shard_program`` consumes all of this
and emits the one jitted train step.  Everything is gated by the typed
``gspmd`` flag (default off, flag-off bit-parity asserted in
tests/test_gspmd.py).  docs/GSPMD.md has the annotation grammar.
"""

from __future__ import annotations

from typing import Optional

__all__ = ["MeshPlan", "annotate_var", "annotate_zero3",
           "annotate_tp_transformer", "annotate_tp_inference",
           "tag_attention_ops", "partition_spec_of", "carve_slices"]


class MeshPlan:
    """Named parallel axes over a device mesh.

    ``MeshPlan(dp=4, tp=2)`` = a (4, 2) mesh with axes ("dp", "tp").
    Size-1 axes are kept (a spec naming them is a no-op shard), so the
    same annotated program runs on any plan shape.  ``pp`` places
    stage-stacked pipeline parameters (parallel/pipeline.py
    stack_stage_params layout: stage axis leading).
    """

    def __init__(self, dp=1, tp=1, pp=1, extra=None, data_axis="dp"):
        axes = {"dp": int(dp), "tp": int(tp), "pp": int(pp)}
        for name, size in (extra or {}).items():
            if name in axes:
                raise ValueError(f"duplicate mesh axis '{name}'")
            axes[name] = int(size)
        for name, size in axes.items():
            if size < 1:
                raise ValueError(f"mesh axis '{name}': size {size} < 1")
        if data_axis not in axes:
            raise ValueError(f"data_axis '{data_axis}' not an axis "
                             f"of {tuple(axes)}")
        self.axes = axes
        self.data_axis = data_axis

    # -- introspection ----------------------------------------------------
    @property
    def axis_names(self):
        return tuple(self.axes)

    @property
    def shape(self):
        return tuple(self.axes.values())

    def size(self):
        n = 1
        for s in self.axes.values():
            n *= s
        return n

    def axis_size(self, name) -> int:
        """Size of an axis; 1 for axes the plan doesn't know (a spec
        naming them still validates — it shards by a factor of 1)."""
        return int(self.axes.get(name, 1))

    def __repr__(self):
        return "MeshPlan(%s)" % ", ".join(
            f"{k}={v}" for k, v in self.axes.items())

    def __eq__(self, other):
        return isinstance(other, MeshPlan) and \
            other.axes == self.axes and other.data_axis == self.data_axis

    # -- construction -----------------------------------------------------
    @staticmethod
    def from_mesh(mesh, data_axis=None):
        plan = MeshPlan.__new__(MeshPlan)
        plan.axes = {n: int(s) for n, s in
                     zip(mesh.axis_names, mesh.devices.shape)}
        plan.data_axis = data_axis or (
            "dp" if "dp" in plan.axes else mesh.axis_names[0])
        return plan

    def to_dict(self):
        return {"axes": dict(self.axes), "data_axis": self.data_axis}

    @staticmethod
    def from_dict(d):
        plan = MeshPlan.__new__(MeshPlan)
        plan.axes = {k: int(v) for k, v in d["axes"].items()}
        plan.data_axis = d.get("data_axis", "dp")
        return plan

    def build_mesh(self, devices=None):
        """jax.sharding.Mesh with this plan's axes over ``devices``
        (default: all).  The device count must equal the plan size."""
        import jax

        from paddle_tpu.parallel import env as penv

        if devices is None:
            devices = jax.devices()
        if len(devices) != self.size():
            raise ValueError(
                f"{self!r} needs {self.size()} devices, have "
                f"{len(devices)}; size the plan to the fleet "
                "(e.g. dp = n_devices // tp)")
        return penv.make_mesh(shape=self.shape,
                              axis_names=self.axis_names,
                              devices=devices)

    def spec(self, *entries):
        """PartitionSpec from per-dim entries, validated against the
        plan's axis names."""
        from jax.sharding import PartitionSpec as P

        for e in entries:
            for a in (e if isinstance(e, (list, tuple)) else (e,)):
                if a is not None and a not in self.axes:
                    raise ValueError(
                        f"spec axis '{a}' not in {self!r}")
        return P(*entries)


# ---------------------------------------------------------------------------
# annotation passes
# ---------------------------------------------------------------------------

def annotate_var(var, spec):
    """Write a PartitionSpec-like annotation onto a VarDesc (tuple per
    dim: None | axis name | tuple of axis names)."""
    return var.set_sharding(spec)


def _shard_factor(plan, entry):
    n = 1
    for a in (entry if isinstance(entry, (list, tuple)) else (entry,)):
        if a is not None:
            n *= plan.axis_size(a)
    return n


def partition_spec_of(var, plan, shape=None) -> Optional[object]:
    """The var's annotation as a jax PartitionSpec, validated against
    the plan: unknown axes raise; a dim the spec doesn't divide evenly
    (or a spec with more dims than the shape — e.g. a sharding rule
    queried for a beta-pow [1] accumulator through the param-prefix
    inheritance) returns None (replicated) — same fallback contract as
    CompiledProgram's rule validation, decided here so the transpiler
    can report it.  ``shape`` overrides the var's declared shape (rule
    queries pass the actual array shape)."""
    if getattr(var, "sharding", None) is None:
        return None
    from jax.sharding import PartitionSpec as P

    spec = var.sharding
    shape = var.shape if shape is None else tuple(shape)
    if shape is not None and len(spec) > len(shape):
        return None
    for entry in spec:
        for a in (entry if isinstance(entry, (list, tuple))
                  else (entry,)):
            if a is not None and a not in plan.axes:
                raise ValueError(
                    f"var '{var.name}': sharding axis '{a}' not in "
                    f"{plan!r}")
    if shape is not None:
        for dim, entry in zip(shape, spec):
            n = _shard_factor(plan, entry)
            if n > 1 and (dim is None or int(dim) < 0 or
                          int(dim) % n != 0):
                return None
    return P(*spec)


def annotate_zero3(program, plan, min_size=2 ** 12, axis="dp",
                   params=True, optimizer_state=True):
    """ZeRO-3 as a sharding spec: annotate parameters (stage 3) and
    optimizer-state vars (stages 1/2 fall out of the same rule — see
    parallel/zero.py's stage notes) with ``axis`` on their first
    free, evenly-divisible dim.  Small tensors (< min_size elements:
    biases, beta-pow scalars) stay replicated — sharding them costs
    more collective latency than it saves.  Composes with existing tp
    annotations: a dim already carrying an axis is skipped, so a
    row-parallel weight P("tp", None) becomes P("tp", "dp") —
    more sharding, same math.  Returns the annotated names.

    Optimizer state is detected EXACTLY via
    parallel.zero.collect_optimizer_state (the in-place-update op
    signature), so call this after minimize(); accumulators created
    later inherit their param's annotation at _add_accumulator time.
    """
    from paddle_tpu.parallel.zero import collect_optimizer_state

    nshard = plan.axis_size(axis)
    names = set()
    if optimizer_state:
        names |= collect_optimizer_state(program)
    if params:
        names |= {v.name for v in program.all_parameters()}
    gb = program.global_block()
    param_names = sorted((v.name for v in program.all_parameters()),
                         key=len, reverse=True)
    annotated = []
    for name in sorted(names):
        var = gb.vars.get(name)
        if var is None or var.shape is None:
            continue
        size = 1
        for d in var.shape:
            size *= max(int(d), 1)
        if not var.shape or size < min_size:
            continue
        if var.sharding is None:
            # an optimizer accumulator seeds from its param's (tp)
            # layout when shapes match, so moments shard exactly like
            # the weight they update (same rule _add_accumulator
            # applies for accumulators created after annotation)
            for pn in param_names:
                if name != pn and name.startswith(pn + "_"):
                    pv = gb.vars.get(pn)
                    if pv is not None and pv.sharding is not None \
                            and pv.shape == var.shape:
                        var.set_sharding(pv.sharding)
                    break
        spec = list(var.sharding) if var.sharding else \
            [None] * len(var.shape)
        while len(spec) < len(var.shape):
            spec.append(None)
        used = {a for e in spec
                for a in (e if isinstance(e, (list, tuple)) else (e,))}
        if axis in used:
            # already dp-sharded (seeded from an annotated param): a
            # mesh axis can map to at most one dim
            annotated.append(name)
            continue
        for i, (dim, entry) in enumerate(zip(var.shape, spec)):
            if entry is None and int(dim) % nshard == 0:
                spec[i] = axis
                var.set_sharding(tuple(spec))
                annotated.append(name)
                break
    return annotated


# the transformer models' deterministic param-name grammar
# (models/transformer.py _w/_b under a param_prefix): column-parallel
# weights split the OUTPUT dim (each tp shard computes its slice of
# heads / ffn hidden), row-parallel weights split the INPUT dim and
# the partitioner all-reduces the partial products — the Megatron-LM
# attention/MLP split expressed purely as PartitionSpecs.
_TP_COL_SUFFIXES = ("_q.w", "_k.w", "_v.w", "_fc1.w")
_TP_ROW_SUFFIXES = ("_out.w", "_fc2.w")
_TP_COL_BIAS_SUFFIXES = ("_fc1.b",)


def annotate_tp_transformer(program, plan, axis="tp"):
    """Tensor-parallel PartitionSpecs on the existing transformer
    layers, keyed on the deterministic name grammar the models emit
    under a ``param_prefix`` (q/k/v/fc1 column-parallel, out/fc2
    row-parallel, fc1 bias sharded with its column).  A model built
    without a prefix (auto fc_N.w_0 names) gets no tp annotations —
    build with ``param_prefix=...`` to opt in.  Returns
    {"column": [...], "row": [...]} of annotated names."""
    nshard = plan.axis_size(axis)
    out = {"column": [], "row": []}
    if nshard <= 1:
        return out
    for var in program.global_block().vars.values():
        if not (var.persistable and var.trainable) or var.shape is None:
            continue
        name, shape = var.name, var.shape
        if len(shape) == 2:
            if name.endswith(_TP_COL_SUFFIXES) and \
                    int(shape[1]) % nshard == 0:
                var.set_sharding((None, axis))
                out["column"].append(name)
            elif name.endswith(_TP_ROW_SUFFIXES) and \
                    int(shape[0]) % nshard == 0:
                var.set_sharding((axis, None))
                out["row"].append(name)
        elif len(shape) == 1:
            if name.endswith(_TP_COL_BIAS_SUFFIXES) and \
                    int(shape[0]) % nshard == 0:
                var.set_sharding((axis,))
                out["column"].append(name)
    return out


def carve_slices(devices, slice_size):
    """Partition a flat device list into consecutive ``slice_size``
    groups — the mesh slices a sharded ReplicaPool hands one replica
    each (ISSUE 14).  Consecutive carving matters on real topologies:
    jax.devices() orders by (host, chip) so a slice stays within one
    host/ICI domain whenever the size divides it.  Leftover devices
    (len % slice_size) are unused — a partial slice can't hold the
    plan.  Raises when not even one slice fits."""
    devices = list(devices)
    slice_size = int(slice_size)
    if slice_size < 1:
        raise ValueError(f"slice_size {slice_size} < 1")
    n = len(devices) // slice_size
    if n < 1:
        raise ValueError(
            f"{len(devices)} devices cannot hold one slice of "
            f"{slice_size} (size the MeshPlan to the fleet)")
    return [devices[i * slice_size:(i + 1) * slice_size]
            for i in range(n)]


# IR ops whose output carries its input's feature sharding unchanged
# (elementwise / shape-preserving): the column-parallel chain analysis
# may look THROUGH them.  Anything else consuming a feature-sharded
# activation (softmax over the sharded dim, pooling, reshapes) is a
# gather point and de-annotates its producer.
_TP_INFER_PASSTHROUGH = ("relu", "tanh", "sigmoid", "elementwise_add",
                         "fused_elemwise_activation", "scale",
                         "dropout")
# ops that consume activations against a 2-D persistable weight
_TP_INFER_MATMUL = ("mul", "matmul", "fc")


def _infer_fc_nodes(block):
    """(op, weight_var, bias_var_or_None, out_name) per fc-shaped op
    in the block — both the raw mul(+elementwise_add bias) form and
    the ir_optim-fused ``fc`` op."""
    nodes = []
    for i, op in enumerate(block.ops):
        if op.type in ("mul", "matmul"):
            wname = op.inputs.get("Y", [None])[0]
        elif op.type == "fc":
            wname = op.inputs.get("W", [None])[0]
        else:
            continue
        if wname is None:
            continue
        w = block.vars.get(wname)
        if w is None or not w.persistable or w.shape is None or \
                len(w.shape) != 2:
            continue
        out = op.outputs["Out"][0]
        bias = None
        if op.type == "fc":
            bnames = op.inputs.get("Bias", [])
            bias = block.vars.get(bnames[0]) if bnames else None
        else:
            # the raw form: a following elementwise_add with a 1-D
            # persistable Y of the weight's output width is the bias
            for later in block.ops[i + 1:]:
                if later.type == "elementwise_add" and \
                        later.inputs.get("X", [None])[0] == out:
                    cand = block.vars.get(
                        later.inputs.get("Y", [None])[0])
                    if cand is not None and cand.persistable and \
                            cand.shape is not None and \
                            len(cand.shape) == 1 and \
                            int(cand.shape[0]) == int(w.shape[1]):
                        bias = cand
                    break
        nodes.append((op, w, bias, out))
    return nodes


def annotate_tp_inference(program, plan, axis="tp"):
    """Column-parallel tp PartitionSpecs on an INFERENCE program's fc
    layers (ISSUE 14 — the sharded serving replica): every fc-shaped
    weight (raw ``mul`` or ir_optim-fused ``fc``) whose output dim
    divides the tp axis gets ``(None, axis)`` and its bias ``(axis,)``.

    Column-ONLY on purpose: an output-dim split keeps every matmul's
    contraction full-width (XLA all-gathers the activation between
    sharded layers instead of summing partial products), so the
    sharded replica's outputs are BIT-IDENTICAL (array_equal) to the
    unsharded predictor — the serving parity contract.  The Megatron
    column/row interleave (fewer gathers, partial-sum all-reduce,
    allclose-tight) stays opt-in via ``annotate_tp_transformer``.

    The bit-exactness guarantee needs the whole downstream chain to
    hold: a sharded activation reaching an UNSHARDED matmul would make
    XLA sum partial products over the sharded contraction.  So after
    the greedy pass, any annotated weight whose output chain (through
    elementwise pass-through ops) reaches an unannotated matmul — or
    any non-pass-through consumer — is DE-annotated, to a fixpoint.
    Returns the annotated weight/bias names."""
    nshard = plan.axis_size(axis)
    if nshard <= 1:
        return []
    block = program.global_block()
    nodes = _infer_fc_nodes(block)
    sharded = {}           # weight name -> (w, bias, out)
    for op, w, bias, out in nodes:
        if int(w.shape[1]) % nshard == 0 and \
                (bias is None or int(bias.shape[0]) % nshard == 0):
            sharded[w.name] = (w, bias, out)
    matmul_weight_of = {}  # activation name -> consuming weight name
    for op, w, bias, out in nodes:
        xkey = "Input" if op.type == "fc" else "X"
        xin = op.inputs.get(xkey, [None])[0]
        if xin is not None:
            matmul_weight_of.setdefault(xin, []).append(w.name)
    consumers = {}         # var name -> [op]
    for op in block.ops:
        for names in op.inputs.values():
            for n in names:
                consumers.setdefault(n, []).append(op)

    def chain_ok(out_name, seen):
        """True iff every consumer of a feature-sharded activation is
        a sharded matmul or a pass-through whose own chain holds."""
        if out_name in seen:
            return True
        seen.add(out_name)
        for op in consumers.get(out_name, ()):
            if op.type in _TP_INFER_MATMUL:
                wkey = "W" if op.type == "fc" else "Y"
                wn = op.inputs.get(wkey, [None])[0]
                if wn not in sharded:
                    return False
            elif op.type in _TP_INFER_PASSTHROUGH:
                for onames in op.outputs.values():
                    for on in onames:
                        if not chain_ok(on, seen):
                            return False
            else:
                return False       # unknown consumer = gather point
        return True

    changed = True
    while changed:
        changed = False
        for wn in list(sharded):
            _, _, out = sharded[wn]
            if not chain_ok(out, set()):
                del sharded[wn]
                changed = True
    annotated = []
    for wn, (w, bias, _) in sorted(sharded.items()):
        w.set_sharding((None, axis))
        annotated.append(wn)
        if bias is not None:
            bias.set_sharding((axis,))
            annotated.append(bias.name)
    # static legality check at annotate time (ISSUE 15): the pass
    # above only writes divisible specs, but composed annotations
    # (a pre-annotated program re-annotated for a different plan)
    # surface here instead of at predictor trace time
    from paddle_tpu.analysis.passes import verify_enabled

    if verify_enabled():
        from paddle_tpu.analysis.shape_check import check_sharding

        check_sharding(program, plan, label="annotate_tp_inference")
    return annotated


def tag_attention_ops(program, plan, batch_axis=None, head_axis=None):
    """Stamp ``gspmd_batch_axis``/``gspmd_head_axis`` attrs on every
    flash_attention op so its Pallas kernel runs under shard_map on
    the gspmd mesh (ops/pallas_kernels.py _flash_attention_op reads
    them; Mosaic kernels can't ride XLA's automatic partitioner, and
    attention is independent per (batch, head) row so the manual
    dp x tp split is exact).  Divisibility is re-checked against the
    traced shapes at compile time with a plain single-device fallback.
    Returns the number of ops tagged."""
    batch_axis = plan.data_axis if batch_axis is None else batch_axis
    head_axis = ("tp" if "tp" in plan.axes else None) \
        if head_axis is None else head_axis
    n = 0
    for block in program.blocks:
        for op in block.ops:
            # the _grad op runs the backward kernels on the forward's
            # saved Out/LSE (ops/pallas_kernels.py
            # _flash_attention_grad_op; jax.vjp over the forward when
            # they are not bound) under its OWN attrs, so they ride the
            # same shard_map iff the grad op is tagged too
            # (append_backward copied the attrs before this pass ran)
            if op.type not in ("flash_attention",
                               "flash_attention_grad"):
                continue
            if batch_axis and plan.axis_size(batch_axis) > 1:
                op.set_attr("gspmd_batch_axis", batch_axis)
            if head_axis and plan.axis_size(head_axis) > 1:
                op.set_attr("gspmd_head_axis", head_axis)
            n += 1
    return n

"""Attribute HLO layout traffic (transpose/copy) to framework ops.

Compiles a step, walks the HLO text, sizes every
transpose/copy/bitcast-convert by its result shape, and aggregates by
the Fluid op that owns it (the op_name metadata JAX attaches, read by
observability/step_owners.owner_of; the path itself where it names no
owner) — so each GB of layout traffic points back at a model layer or
an inserted pass.  It reads HLO and
times nothing.

Usage: python tools/hlo_traffic.py [--model resnet50|transformer]
           [--batch N] [--top 25] [--min-mb 1]
"""

from __future__ import annotations

import argparse
import collections
import re
import sys

import numpy as np

sys.path.insert(0, ".")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}

# e.g. "bf16[128,56,56,256]{3,2,1,0}" — capture dtype and dims
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPNAME_RE = re.compile(r'op_name="([^"]+)"')


def owner_label(op_name):
    """The group an instruction's traffic is booked under: the Fluid op
    that owns it, `[<pass>] <role>.<type> [<scope>]`
    (observability/step_owners.owner_of), so that the calls of one op
    add up whatever jax primitive each came from; the op_name itself
    where the path names no owner."""
    from paddle_tpu.observability.step_owners import owner_of

    who = owner_of(op_name)
    if who.role is None:
        return op_name
    label = "%s.%s" % (who.role, who.type)
    if who.step_pass != who.role:       # a segment's replay or gradient
        label = who.step_pass + " " + label
    return label + " [%s]" % who.scope if who.scope else label


def shape_bytes(shape_str):
    # delegates to the tuple-capable parser so the two reports can
    # never disagree on how a shape is sized
    return _shape_part_bytes(shape_str)


def scan_hlo(hlo_text, kinds=("transpose", "copy", "bitcast-convert")):
    """Yield (kind, bytes, op_name, fused, line) for every matching op.

    Ops inside %fused_computation bodies are loop-fused by the TPU
    backend (usually free); top-level ones are real HBM round trips.
    """
    in_fusion = False
    for line in hlo_text.splitlines():
        s = line.strip()
        if re.match(r"%?fused_computation[\w.\-]* ", s) and s.endswith("{"):
            in_fusion = True
            continue
        if in_fusion and s.startswith("}"):
            in_fusion = False
            continue
        # result lines look like:  %name = bf16[...]{...} transpose(...)
        # TPU layouts carry tile/memory-space annotations inside the
        # braces — "{3,2,1,0:T(8,128)(2,1)S(3)}" — so the layout part
        # must match any non-brace run, not just digits and commas
        # (the digits-only pattern matched ZERO ops on the first
        # on-chip run, 2026-08-01)
        m = re.match(
            r"(?:ROOT )?%?[\w.\-]+ = ([\w\[\],]+)(?:\{[^}]*\})? "
            r"(\w[\w\-]*)\(", s)
        if not m:
            continue
        shape_str, op = m.groups()
        if op not in kinds:
            continue
        nm = _OPNAME_RE.search(s)
        sm = _SHAPE_RE.match(shape_str)
        shape = (f"{sm.group(1)}[{sm.group(2)}]" if sm else shape_str)
        name = nm.group(1) if nm else shape
        yield op, shape_bytes(shape_str), name, in_fusion, s


_ENTRY_LINE_RE = re.compile(
    r"(?:ROOT )?%?([\w.\-]+) = (\([^)]*\)|[\w\[\],]+) "
    r"(\w[\w\-]*)\((.*)$")


def _shape_part_bytes(shape_part):
    """Total bytes of a result shape string — handles tuple shapes
    "(bf16[...], f32[...])" by summing every array in it."""
    total = 0
    for m in re.finditer(r"(\w+)\[([\d,]*)\]", shape_part):
        n = 1
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES.get(m.group(1), 4)
    return total


def _strip_braces(s):
    """Remove every {...} group (layout/tile annotations, metadata,
    window configs).  Tile annotations contain parens —
    "{0:T(256)}" — which would otherwise break tuple-shape parsing
    (a ')' inside the layout terminates a naive "\\([^)]*\\)").
    op_name must be extracted BEFORE stripping."""
    prev = None
    while prev != s:
        prev = s
        s = re.sub(r"\{[^{}]*\}", "", s)
    return s


def _operand_span(rest):
    """`rest` is everything after the opcode's opening '(' (braces
    already stripped): return the slice up to the MATCHING close
    paren.  Everything after it is metadata/attributes — scanning the
    whole tail for %refs let an op_name or sharding string that
    mentions an instruction name misattribute that instruction's
    bytes as a read (ADVICE r5).  Nested parens (tuple operands,
    computation refs) are depth-tracked; an unterminated line returns
    the whole rest (harmless: unmatched refs resolve to 0)."""
    depth = 0
    for i, ch in enumerate(rest):
        if ch == "(":
            depth += 1
        elif ch == ")":
            if depth == 0:
                return rest[:i]
            depth -= 1
    return rest


def roofline_rows(hlo_text):
    """Attribute HBM traffic to every TOP-LEVEL op of the entry
    computation: bytes = result bytes + sum of operand result bytes
    (operand names resolved against earlier result lines).  Fusion
    interiors are skipped — a fusion's traffic is its boundary.
    Yields (opcode, bytes, op_name)."""
    depth_skip = False
    # operand sizes are NAMESPACED per computation: HLO instruction
    # names are only unique within their computation, and a fusion
    # body reusing an entry-computation name (common for %param-style
    # locals) would otherwise overwrite the entry's recorded size and
    # misattribute bytes in the report (ADVICE r5)
    sizes = {}
    rows = []
    for line in hlo_text.splitlines():
        s = line.strip()
        if re.match(r"%?[\w.\-]+ ", s) and s.endswith("{") \
                and " = " not in s:
            # a computation definition header (fusion body, reduce
            # body, ENTRY, ...) — entry is handled like the rest:
            # every computation's results land in `sizes`, but only
            # rows whose line carries op_name metadata AND whose
            # opcode isn't parameter/constant matter for the report
            depth_skip = "ENTRY" not in s and not s.startswith("ENTRY")
            sizes = {}          # fresh namespace per computation
            continue
        if s.startswith("}"):
            depth_skip = False
            continue
        nm = _OPNAME_RE.search(s)  # before brace-stripping eats it
        m = _ENTRY_LINE_RE.match(_strip_braces(s))
        if not m:
            continue
        name, shape_part, opcode, rest = m.groups()
        nbytes = _shape_part_bytes(shape_part)
        sizes[name] = nbytes
        if depth_skip or opcode in ("parameter", "constant", "tuple",
                                    "get-tuple-element", "bitcast"):
            continue
        # operand names: %refs inside the call parens ONLY (the span
        # ends at the matching close paren; computation refs and
        # other non-result names resolve to 0)
        if opcode in ("slice", "dynamic-slice", "gather"):
            # these read only what they output (plus an index vector);
            # counting full operand bytes inflated 1-element BN probe
            # slices to the whole activation (2 GB of phantom "slice"
            # traffic in the 2026-08-01 roofline)
            reads = nbytes
        else:
            operand_part = _operand_span(rest)
            reads = sum(sizes.get(r, 0) for r in
                        re.findall(r"%([\w.\-]+)", operand_part))
        rows.append((opcode, nbytes + reads,
                     nm.group(1) if nm else name))
    return rows


def build_resnet(batch, nhwc=True, bf16=True, conv_bn_stats=False):
    """conv_bn_stats=True builds the lowering gate's
    resnet50_train_convbnstats graph (fuse_conv_bn_train + AMP + NHWC) so the
    roofline can show the BN-moment re-read of the conv output is gone
    — the ISSUE 4 acceptance check.  The default build stays the plain
    local construction below (kept so historical reports diff)."""
    if conv_bn_stats:
        import jax

        from tools.gate_programs import _build_resnet50_train
        from paddle_tpu.flags import set_flags

        out = _build_resnet50_train(batch, conv_bn_stats=True)[:3]
        if jax.devices()[0].platform != "tpu":
            # off-chip the "on" auto-impl is the unfused composite,
            # which would make this report identical to the plain one;
            # interpret mode keeps the kernel structure (stats as conv
            # sibling outputs, one normalize pass) in the compiled
            # graph so the moments-re-read check below is real.  The
            # roofline NUMBERS of an interpreted kernel are not — only
            # the on-chip run prices the fused graph.
            print("(CPU host: conv_bn_stats=interpret — structure "
                  "check only, not a roofline)", file=sys.stderr)
            set_flags({"conv_bn_stats": "interpret"})
        return out
    return _build_resnet_plain(batch, nhwc=nhwc, bf16=bf16)


def _build_resnet_plain(batch, nhwc=True, bf16=True):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu import framework, optimizer
    from paddle_tpu.models.resnet import resnet50
    from paddle_tpu.transpiler import nhwc_transpile
    from tools.gate_programs import _build_compiled_fn, _fresh_programs

    _fresh_programs()
    model = resnet50(is_test=False)
    if nhwc:
        nhwc_transpile(framework.default_main_program())
    if bf16:
        from paddle_tpu.contrib.mixed_precision import decorate
        opt = decorate(optimizer.Momentum(learning_rate=0.1, momentum=0.9),
                       init_loss_scaling=1.0, use_dynamic_loss_scaling=False)
    else:
        opt = optimizer.Momentum(learning_rate=0.1, momentum=0.9)
    opt.minimize(model["loss"])
    exe = fluid.Executor(fluid.TPUPlace())
    exe.run(framework.default_startup_program())
    compiled = fluid.CompiledProgram(framework.default_main_program())
    rng = np.random.RandomState(0)
    feed = {
        "image": jax.device_put(jnp.asarray(
            rng.rand(batch, 3, 224, 224).astype(np.float32))),
        "label": jax.device_put(
            rng.randint(0, 1000, (batch, 1)).astype(np.int64)),
    }
    fn, state = _build_compiled_fn(compiled, feed, [model["loss"].name])
    return fn, state, feed


def _s8_result_bytes(shape_part):
    """Bytes of the s8 arrays inside a result-shape string (tuple
    shapes included) — the inter-layer evidence counter for the
    --int8-interlayer check."""
    total = 0
    for m in re.finditer(r"s8\[([\d,]*)\]", shape_part):
        n = 1
        for d in m.group(1).split(","):
            if d:
                n *= int(d)
        total += n
    return total


def count_s8_activations(hlo_text, min_bytes):
    """Count instructions (any computation, fusion interiors included)
    whose result carries >= min_bytes of s8 data — compiled proof that
    activation-SIZED tensors flow int8, not a framework-IR claim.
    Fusion interiors count on purpose: a fusion-interior s8 convert
    whose consumer is the conv means the materialized conv operand is
    s8 (XLA:CPU additionally re-expands s8 conv operands to s32 — an
    emulation artifact the TPU lowering doesn't share)."""
    n, total = 0, 0
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _ENTRY_LINE_RE.match(_strip_braces(s))
        if not m:
            continue
        _name, shape_part, opcode, _rest = m.groups()
        if opcode in ("parameter", "constant", "get-tuple-element",
                      "tuple", "bitcast"):
            continue
        b = _s8_result_bytes(shape_part)
        if b >= min_bytes:
            n += 1
            total += b
    return n, total


def _bytes_accessed(comp):
    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return float(ca.get("bytes accessed", float("nan")))


def op_boundary_rows(program, state, feed):
    """Bytes crossing OP boundaries under op-at-a-time execution: for
    every global-block op, reads(inputs) + writes(outputs), shapes
    propagated with jax.eval_shape over the registered computes (no
    FLOPs executed).  This is the execution model in which the
    interlayer fold's traffic cut is structural — each op boundary is
    a real materialization point (the reference framework's per-op
    executor, our interpreter path).  Whole-graph XLA erases most op
    boundaries via fusion, which is why the compiled bytes-accessed
    of the fused and unfused graphs match (see docs/INT8.md).
    Returns (total_bytes, [(op_type, bytes)])."""
    import jax

    from paddle_tpu.core.registry import get_op_def

    specs = {}
    for src in (state, feed):
        for name, arr in src.items():
            a = np.asarray(arr) if not hasattr(arr, "dtype") else arr
            specs[name] = jax.ShapeDtypeStruct(a.shape, a.dtype)

    def nbytes(spec):
        n = 1
        for d in spec.shape:
            n *= int(d)
        return n * np.dtype(spec.dtype).itemsize

    total, rows = 0, []
    for op in program.global_block().ops:
        d = get_op_def(op.type)
        ins, skip = {}, False
        for slot, names in op.inputs.items():
            vals = [specs.get(n) for n in names]
            if slot in d.duplicable:
                if any(v is None for v in vals):
                    if slot in d.optional:
                        continue
                    skip = True
                    break
                ins[slot] = vals
            else:
                v = vals[0] if vals else None
                if v is None:
                    if slot in d.optional or not names:
                        continue
                    skip = True
                    break
                ins[slot] = v
        if skip:
            continue
        try:
            outs = jax.eval_shape(lambda i: d.compute(i, op.attrs), ins)
        except Exception:  # noqa: BLE001 — host-only/special op: skip
            continue
        b = 0
        for v in jax.tree_util.tree_leaves(ins):
            b += nbytes(v)
        for slot, names in op.outputs.items():
            if slot not in outs:
                continue
            vals = outs[slot]
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            for n, v in zip(names, vals):
                specs[n] = v
                b += nbytes(v)
        total += b
        rows.append((op.type, b))
    return total, rows


def int8_interlayer_report(batch, min_reduction_pct):
    """ISSUE-5 acceptance check, three instruments over the EXACT
    gate programs (gate_programs._build_resnet50_infer_int8):

    1. compiled s8 evidence — the interlayer module must carry at
       least one activation-sized s8 tensor per folded edge (assert);
    2. op-boundary bytes — the per-op-materialization traffic model
       where the fold is structural; assert >= min_reduction_pct;
    3. whole-graph XLA bytes-accessed — reported as-is.  Finding
       (2026-08-04, docs/INT8.md): XLA already fuses the unfused
       dequant->BN->ReLU->quant chain down to s8 conv operands, so
       this number matches between the graphs; the IR fold turns that
       fusion from a compiler outcome into a graph INVARIANT and cuts
       the op-at-a-time path, it does not change the jit-compiled
       module.  (On CPU the number also counts the s8->s32 conv
       emulation upcasts, which TPU's MXU lowering doesn't have.)

    Returns process exit code."""
    from paddle_tpu.core.scope import Scope, scope_guard
    from tools import gate_programs

    rows = {}
    for name, inter in (("calibrated", False), ("interlayer", True)):
        with scope_guard(Scope()):
            fn, state, feed, _fetch, calib, prog = \
                gate_programs._build_resnet50_infer_int8(
                    batch, int8_activations=inter)
            comp = fn.lower(state, feed).compile()
            btotal, brows = op_boundary_rows(prog, state, feed)
            rows[name] = {"bytes": _bytes_accessed(comp),
                          "hlo": comp.as_text(), "calib": calib,
                          "boundary": btotal, "boundary_rows": brows}
    n_req = rows["interlayer"]["calib"].get("n_requant_epilogues", 0)
    # the smallest inter-layer activation in rn50 is the final-stage
    # [N, 7, 7, 512] block tensor — anything that size or larger and
    # s8 is an activation, not a weight (the biggest int8 weight,
    # fc1000 at 2048x1000 ~ 2 MB, sits below it for mb >= 128)
    thr = batch * 7 * 7 * 512
    n_s8, s8_bytes = count_s8_activations(rows["interlayer"]["hlo"],
                                          thr)
    n_s8_base, _ = count_s8_activations(rows["calibrated"]["hlo"], thr)
    base_b = rows["calibrated"]["bytes"]
    inter_b = rows["interlayer"]["bytes"]
    xla_delta = 100.0 * (1.0 - inter_b / base_b) if base_b else 0.0
    bb, bi = rows["calibrated"]["boundary"], \
        rows["interlayer"]["boundary"]
    bdelta = 100.0 * (1.0 - bi / bb) if bb else 0.0
    print("== int8-interlayer check (mb=%d) ==" % batch)
    print("  requantize epilogues in graph : %d "
          "(fold coverage %.1f%%, int8-in consumers %d)" %
          (n_req,
           100 * rows["interlayer"]["calib"].get(
               "interlayer_fold_coverage", 0.0),
           rows["interlayer"]["calib"].get("n_int8_inputs", 0)))
    print("  compiled s8 tensors >= %.1f MB : %d (%.3f GB) "
          "[calibrated module: %d]"
          % (thr / 1e6, n_s8, s8_bytes / 1e9, n_s8_base))
    print("  op-boundary bytes  : calibrated %.3e, interlayer %.3e "
          "-> %.1f%% reduction" % (bb, bi, bdelta))
    print("  XLA bytes accessed : calibrated %.3e, interlayer %.3e "
          "-> %.1f%% delta (expected ~0: XLA had already fused the "
          "chain to s8 boundaries — see docs/INT8.md)"
          % (base_b, inter_b, xla_delta))
    ok = True
    if n_req <= 0 or n_s8 < n_req:
        print("  FAIL: expected >= %d activation-sized s8 tensors in "
              "the compiled interlayer module, found %d"
              % (n_req, n_s8))
        ok = False
    if bdelta < min_reduction_pct:
        print("  FAIL: op-boundary bytes reduction %.1f%% < required "
              "%.1f%%" % (bdelta, min_reduction_pct))
        ok = False
    print("  int8-interlayer check %s" % ("OK" if ok else "FAILED"))
    return 0 if ok else 1


def build_deepfm(batch):
    """The DeepFM train step, byte-attributable: CTR is a
    gather/scatter workload, so what bounds it is in this report
    (embedding lookups, segment-sum grads, Adam state), not in MFU."""
    from tools import gate_programs

    fn, state, feed, _loss = gate_programs._build_deepfm_train(batch)
    return fn, state, feed


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50",
                    choices=["resnet50", "deepfm"])
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--min-mb", type=float, default=1.0)
    ap.add_argument("--conv-bn-stats", action="store_true",
                    help="build the fused conv+BN-stats train graph "
                         "(flag conv_bn_stats, fuse_conv_bn_train) — "
                         "the report should show the standalone "
                         "BN-moment reduction re-read of the conv "
                         "output is gone (ISSUE 4 acceptance)")
    ap.add_argument("--int8-interlayer", action="store_true",
                    help="ISSUE-5 acceptance check: compile the "
                         "calibrated int8 rn50 infer graph AND the "
                         "int8-interlayer graph, assert the compiled "
                         "inter-layer activation tensors are s8, and "
                         "report the bytes-accessed delta")
    ap.add_argument("--min-reduction-pct", type=float, default=20.0,
                    help="fail the --int8-interlayer check below this "
                         "bytes-accessed reduction (acceptance bar "
                         "20%%)")
    args = ap.parse_args()

    if args.int8_interlayer:
        sys.exit(int8_interlayer_report(args.batch,
                                        args.min_reduction_pct))

    if args.model == "resnet50":
        fn, state, feed = build_resnet(
            args.batch, conv_bn_stats=args.conv_bn_stats)
    else:
        fn, state, feed = build_deepfm(args.batch if args.batch != 128
                                       else 2048)

    comp = fn.lower(state, feed).compile()
    hlo = comp.as_text()

    rows = list(scan_hlo(hlo))
    if not rows:
        # never return blind again: if the line format drifted, show
        # raw samples of the ops we failed to parse
        print("!! scan matched ZERO ops — raw transpose/copy samples:")
        shown = 0
        for line in hlo.splitlines():
            if " transpose(" in line or " copy(" in line:
                print("   ", line.strip()[:200])
                shown += 1
                if shown >= 5:
                    break
    total = collections.Counter()
    by_name = collections.Counter()
    for op, nbytes, name, fused, _ in rows:
        key = (op, "fused" if fused else "TOP")
        total[key] += nbytes
        if not fused:
            by_name[(op, owner_label(name))] += nbytes

    print("== layout-traffic totals (result bytes; traffic ~2x: r+w) ==")
    for (op, where), b in total.most_common():
        n = sum(1 for r in rows
                if r[0] == op and (r[3] == (where == "fused")))
        print(f"  {op:16s} [{where:5s}] {n:4d} ops  {b/1e9:7.3f} GB")

    print(f"\n== top {args.top} TOP-LEVEL (op, owner) by bytes ==")
    for (op, name), b in by_name.most_common(args.top):
        if b < args.min_mb * 1e6:
            break
        n = sum(1 for r in rows
                if r[0] == op and owner_label(r[2]) == name and not r[3])
        print(f"  {b/1e9:7.3f} GB  {n:3d}x {op:10s} {name}")

    # full roofline attribution: every top-level op, result+operand
    # bytes — names where the step's HBM traffic actually lives
    # (the 2026-08-01 run showed transpose/copy are NOT it: 0.5 GB of
    # 46.5 GB total)
    rr = roofline_rows(hlo)
    by_kind = collections.Counter()
    n_kind = collections.Counter()
    for opcode, b, _ in rr:
        by_kind[opcode] += b
        n_kind[opcode] += 1
    print("\n== top-level bytes (result+operands) by opcode ==")
    for opcode, b in by_kind.most_common(12):
        print(f"  {opcode:22s} {n_kind[opcode]:4d} ops  "
              f"{b/1e9:7.3f} GB")
    by_op = collections.Counter()
    for opcode, b, name in rr:
        by_op[(opcode, owner_label(name))] += b
    print(f"\n== top {args.top} top-level ops by bytes ==")
    for (opcode, name), b in by_op.most_common(args.top):
        print(f"  {b/1e9:7.3f} GB  {opcode:12s} {name[:90]}")

    # the ISSUE 4 acceptance probe: the train graph's standalone
    # BN-moment reduction re-reads the full conv output once per BN —
    # in the fused graph those moments ride out of the conv kernel as
    # sibling outputs, so the big top-level reduces must be gone.
    # Printed for every run so the plain-vs-fused A/B is one diff.
    act_bytes = 4 * args.batch * 56 * 56 * 64   # smallest rn50 conv out
    big_red = [(b, name) for opcode, b, name in rr
               if opcode == "reduce" and b >= act_bytes]
    print(f"\n== BN-moments check: top-level reduce ops reading "
          f">= one conv activation ({act_bytes / 1e6:.0f} MB) ==")
    print(f"  {len(big_red)} ops, {sum(b for b, _ in big_red) / 1e9:.3f}"
          f" GB")
    print("  (the fused conv_bn_stats graph drops every FORWARD "
          "BN-moment re-read of the conv output — stats ride out of "
          "the conv kernel; the backward's dbias/dscale sums remain "
          "in both graphs)")

    ca = comp.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    print(f"\nXLA bytes accessed total: "
          f"{ca.get('bytes accessed', float('nan')):.3e}")


if __name__ == "__main__":
    main()

"""The one reduction from a profiler trace (.xplane.pb) to the device
numbers of the benchmark: busy and idle time, per-category and
per-operation device time, collective time and its exposed part, and
the longest idle gaps with what the host was doing in them.

Written against a trace of the v5e read by hand
(benchmarks/tests/record_trace.py prints one), which shows:

* one plane per chip, `/device:TPU:<n>`.  Its line `XLA Modules` has
  one event per execution of a jitted program (`jit_step(<hash>)`),
  `XLA Ops` one event per HLO instruction the TensorCore ran, named by
  the instruction's whole HLO text (`%name = shape opcode(...), ...`),
  and `Async XLA Ops` one span from each `-start` to its `-done`.
  Events carry device times only: no category, no FLOPs.
* a Pallas kernel is `custom-call(...), custom_call_target=
  "tpu_custom_call"`.  A convolution or a matmul (a dot is a
  convolution to the TPU compiler) sits inside a `fusion(...),
  calls=%<computation>`, whose text does not say so: the caller passes
  the compiled step's HLO text, and a fusion whose computation holds a
  `convolution(` is classed `convolution`.
* `/host:CPU` has one line per host thread; TraceAnnotations are on
  the line `python`.  The device's clock is NOT the host's: the first
  traces showed every program starting on the device about 1 ms before
  the host launched it (`tpu::System::Execute`) and ending 2 ms before
  the host saw it done (`tpu::System::Execute=>Done`).
  `host_offset_ns` puts the shift midway between those two bounds.

The steady window is device-clocked and whole: from the start of the
first traced execution of the step program (the module with the most
device time) to the start of the last, N-1 periods of busy-then-idle.
Everything `reduce` reports covers that window.
"""

from __future__ import annotations

import gzip
import re

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", re.M)


# ---------------------------------------------------------------------------
# interval arithmetic (integer or float nanoseconds, half-open)
# ---------------------------------------------------------------------------

def merge(intervals):
    """Disjoint, sorted union of (start, end) intervals."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(intervals):
    return sum(e - s for s, e in merge(intervals))


def subtract(a, b):
    """The part of the union of `a` that no interval of `b` covers."""
    out, b = [], merge(b)
    for s, e in merge(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo, hi):
    """The parts of [lo, hi) that no interval covers."""
    return subtract([(lo, hi)], intervals)


def overlap(a, b):
    """Length of the intersection of the unions of `a` and `b`."""
    return total(a) - total(subtract(a, b))


# ---------------------------------------------------------------------------
# naming
# ---------------------------------------------------------------------------

def conv_computations(hlo_text):
    """Names of the computations of a compiled module's text that hold
    a convolution (on the TPU: a convolution or a matmul), themselves
    or in a computation they call.  Besides `fused_computation.N` a
    sharded step has `async_collective_fusion.N`: a matmul with the
    all-gather or reduce-scatter it hides fused around it."""
    if not hlo_text:
        return frozenset()
    heads = list(_COMPUTATION.finditer(hlo_text))
    holds, calls = set(), {}
    for i, m in enumerate(heads):
        end = heads[i + 1].start() if i + 1 < len(heads) else len(hlo_text)
        body = hlo_text[m.end():end]
        if " convolution(" in body:
            holds.add(m.group(1))
        calls[m.group(1)] = set(_CALLS.findall(body))
    grew = True
    while grew:
        grew = False
        for name, callees in calls.items():
            if name not in holds and callees & holds:
                holds.add(name)
                grew = True
    return frozenset(holds)


def opcode(hlo_name):
    """The opcode of an `XLA Ops` event name (an instruction's text)."""
    rhs = hlo_name.split(" = ", 1)[-1]
    # skip the result shape, which may be a tuple in parentheses
    depth = 0
    for i, ch in enumerate(rhs):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            m = _OPCODE.match(rhs[i:])
            if m:
                return m.group(1)
    m = _OPCODE.search(" " + rhs)
    return m.group(1) if m else rhs.split("(")[0].strip()


def instruction(hlo_name):
    return hlo_name.split(" = ", 1)[0].lstrip("%").strip()


def classify(hlo_name, conv_comps=frozenset()):
    """mosaic | convolution | collective | copy | fusion | other."""
    op = opcode(hlo_name)
    base = op[:-6] if op.endswith("-start") else \
        op[:-5] if op.endswith("-done") else op
    if base in COLLECTIVES:
        return "collective"
    if op == "custom-call":
        return "mosaic" if "tpu_custom_call" in hlo_name else "other"
    if op == "convolution":
        return "convolution"
    if op == "fusion":
        m = _CALLS.search(hlo_name)
        return "convolution" if m and m.group(1) in conv_comps \
            else "fusion"
    if base in ("copy", "transpose", "bitcast", "reshape", "slice",
                "dynamic-slice", "dynamic-update-slice", "concatenate",
                "pad", "broadcast"):
        return "copy"
    return "other"


def group_name(hlo_name, category):
    """A stable label for the breakdown: category and the instruction's
    name without its numeric suffix, so the 24 flash calls of a step
    add up under one name."""
    return "%s:%s" % (category, re.sub(r"[.\d]+$", "",
                                       instruction(hlo_name)))


# ---------------------------------------------------------------------------
# reading
# ---------------------------------------------------------------------------

def read_xplane(path):
    """{"devices": {plane: {"modules": [...], "ops": [...], "async":
    [...]}}, "host": [...]}, every entry (name, start_ns, end_ns).
    `host` holds the events of every host thread.  A path ending in
    .gz is a gzipped .xplane.pb (the recorded traces of the tests)."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {"XLA Modules": [], "XLA Ops": [], "Async XLA Ops": []}
            for line in plane.lines:
                if line.name in lines:
                    lines[line.name] = [
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events]
            devices[plane.name] = {"modules": lines["XLA Modules"],
                                   "ops": lines["XLA Ops"],
                                   "async": lines["Async XLA Ops"]}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events)
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

def step_module(modules):
    """The program that is the step: the module name with the most
    device time.  Returns (name, [(start, end), ...]) in time order."""
    by = {}
    for name, s, e in modules:
        by.setdefault(name, []).append((s, e))
    if not by:
        return None, []
    name = max(by, key=lambda n: sum(e - s for s, e in by[n]))
    return name, sorted(by[name])


LAUNCH_EVENTS = ("tpu::System::Execute", "PJRT_LoadedExecutable_Execute")
DONE_EVENTS = ("tpu::System::Execute=>Done",)


def host_offset_ns(step_runs, launches, completions=()):
    """(shift, slack): what to add to a device time to get the host's,
    and how far off it may be.  No execution starts before the host
    launched it (launch k is matched to execution k, counted from the
    end), which bounds the shift from below; none ends after the host
    saw it complete, which bounds it from above.  With both bounds the
    estimate is their midpoint and the slack half their distance; with
    one, that bound and no slack known (None).  (None, None) without
    either."""
    lo = [la[0] - run[0] for la, run in zip(reversed(sorted(launches)),
                                            reversed(step_runs))]
    hi = [done[0] - run[1] for done, run in zip(
        reversed(sorted(completions)), reversed(step_runs))]
    if lo and hi and min(hi) >= max(lo):
        return (max(lo) + min(hi)) / 2, (min(hi) - max(lo)) / 2
    if lo:
        return max(lo), None
    if hi:
        return min(hi), None
    return None, None


def _first_named(host, names, count, last=False):
    """One span per execution of the step from the first of `names`
    that the trace holds `count` of, or a whole multiple of `count`
    (a program over k chips is launched, and seen done, k times): then
    the first of each k in time order, or the `last`."""
    for name in names:
        found = sorted((s, e) for n, s, e in host if n == name)
        k = len(found) // count if count else 0
        if k and len(found) == k * count:
            return [found[i * k + (k - 1 if last else 0)]
                    for i in range(count)]
    return []


def reduce_device(dev, host_phases, conv_comps, host=()):
    """One device's numbers over its steady window, or None when the
    trace holds fewer than three executions of the step."""
    mod_name, runs = step_module(dev["modules"])
    if len(runs) < 3:
        return None
    lo, hi = runs[0][0], runs[-1][0]
    steps = len(runs) - 1
    ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev["ops"]
           if min(e, hi) > max(s, lo)]
    spans = [(n, max(s, lo), min(e, hi)) for n, s, e in dev["async"]
             if min(e, hi) > max(s, lo)]
    cat_of = {}

    def cat(name):
        if name not in cat_of:
            cat_of[name] = classify(name, conv_comps)
        return cat_of[name]

    busy = merge([(s, e) for _, s, e in ops] + [(s, e) for _, s, e in spans])
    by_cat, by_op = {}, {}
    for n, s, e in ops:
        c = cat(n)
        by_cat[c] = by_cat.get(c, 0.0) + (e - s)
        g = group_name(n, c)
        by_op[g] = by_op.get(g, 0.0) + (e - s)
    coll = [(s, e) for n, s, e in ops if cat(n) == "collective"] + \
        [(s, e) for n, s, e in spans if cat(n) == "collective"]
    other = [(s, e) for n, s, e in ops if cat(n) != "collective"]
    exposed = subtract(coll, other)

    # idle gaps, on the host's clock where launches and completions
    # give the shift: every gap is shared out among the host phases it
    # overlaps (the rest is "between steps"), and labelled with the
    # phase that covers most of it
    base = (mod_name or "").split("(")[0]
    base = base[4:] if base.startswith("jit_") else base
    # runtime events are not named by program: they count only where
    # the trace holds one per execution of the step
    launches = _first_named(host, LAUNCH_EVENTS
                            + ("PjitFunction(%s)" % base,), len(runs))
    shift, slack = host_offset_ns(
        runs, launches, _first_named(host, DONE_EVENTS, len(runs),
                                     last=True))
    idle, by_phase = [], {}
    for s, e in gaps(busy, lo, hi):
        label = "unlabelled"
        if shift is not None and host_phases:
            span = [(s + shift, e + shift)]
            shares = {name: overlap(span, spans_)
                      for name, spans_ in host_phases.items()}
            shares["between steps"] = max(
                0.0, (e - s) - sum(shares.values()))
            label = max(shares, key=shares.get)
            for name, n in shares.items():
                if n:
                    by_phase[name] = by_phase.get(name, 0.0) + n
        else:
            by_phase[label] = by_phase.get(label, 0.0) + (e - s)
        idle.append((label, e - s))
    return {
        "module": mod_name, "steps": steps, "window_ns": hi - lo,
        "busy_ns": total(busy),
        "category_ns": by_cat, "op_ns": by_op,
        "collective_ns": total(coll), "collective_exposed_ns":
            total(exposed),
        "idle_gaps": sorted(idle, key=lambda g: -g[1]),
        "idle_by_phase_ns": by_phase,
        "host_offset_ns": shift, "host_offset_slack_ns": slack,
    }


PHASE_PREFIX = "bm:"      # the harness's TraceAnnotations


def reduce(trace, hlo_text=None):
    """The whole reduction.  `trace`: what read_xplane returns.
    Returns None when no device ran the step three times; else
    {"devices": {plane: reduce_device(...)}, "first": <plane of the
    lowest-numbered device>, "busy_s", "window_s" (averaged over
    devices), "device_ops", "idle_by_phase", "idle_gaps" (of the first
    device, [name, seconds] with the largest first)}."""
    conv = conv_computations(hlo_text)
    phases = {}
    for n, s, e in trace["host"]:
        if n.startswith(PHASE_PREFIX):
            phases.setdefault(n[len(PHASE_PREFIX):], []).append((s, e))
    # the outer span of a step is no phase of its own
    phases.pop("step", None)
    out = {}
    for plane, dev in trace["devices"].items():
        r = reduce_device(dev, phases, conv, host=trace["host"])
        if r is not None:
            out[plane] = r
    if not out:
        return None
    first = min(out, key=lambda p: (len(p), p))
    n = len(out)
    r0 = out[first]
    return {
        "devices": out, "first": first,
        "busy_s": sum(r["busy_ns"] for r in out.values()) / n / 1e9,
        "window_s": sum(r["window_ns"] for r in out.values()) / n / 1e9,
        "device_ops": [[k, v / 1e9] for k, v in sorted(
            r0["op_ns"].items(), key=lambda kv: -kv[1])[:10]],
        "idle_by_phase": [[k, v / 1e9] for k, v in sorted(
            r0["idle_by_phase_ns"].items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v / 1e9] for k, v in r0["idle_gaps"][:5]],
    }


def per_step_ms(reduced, key, category=None):
    """A per-step time in ms of the first device: `key` is a field of
    reduce_device's result, or "category_ns" with `category`."""
    r = reduced["devices"][reduced["first"]]
    v = r["category_ns"].get(category, 0.0) if category else r[key]
    return v / r["steps"] / 1e6

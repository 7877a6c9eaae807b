"""Who owns an instruction of the compiled step: the parser of what
``core/program.py:op_scope`` writes, and the table in device time.

Every op's compute runs under ``jax.named_scope("pt_<role>.<type>")``
(the op's ``op_role`` and ``type``), with the name scope it was
appended under and the scopes its compute opens (``pt_mla``,
``pt_moe_experts``, a kernel's ``pt_flash_fwd``) nested inside.  jax
writes the path into every instruction's ``op_name`` metadata, e.g.

  jit(step)/pt_forward.moe_route/pt_moe_route/top_k
  jit(step)/pt_backward.mul_grad/transpose(jvp(pt_backward.mul_grad))/dot_general
  jit(step)/pt_backward.recompute_segment_grad/.../jvp()/checkpoint/
      rematted_computation/pt_forward.rms_norm/pt_rms_norm/rsqrt
  jit(step)/pt_optimize.adam/div

Pure functions of strings and tuples: no jax, no profiler session, no
state.  ``profiler.device_op_table`` and ``tools/step_owners.py`` join
them to a trace; a later benchmark metric is a reader of ``owners``.

Known limits (tests/test_step_owners.py holds both as jax 0.9 has
them).  jax lowers an inner ``jax.jit`` once a module, one function with
a call from each owner; the compiler's inliner gives each copy its own
caller's path, so a helper that a forward op and its segment's replay
both call (``_over_live_rows``) is booked under each; a call the
compiler did NOT inline would carry its first caller's.  And a
primitive whose lowering jax caches a module (``cumsum``'s
``reduce_window_sum``, a sort's comparator, a reduction's body) carries
its bare name and no path: no owner can be read from it.  Nor from what
the compiler makes itself (layout and memory-space copies,
``copy-start`` / ``copy-done``, ``AllocateBuffer``): they carry no
metadata at all.
"""

from __future__ import annotations

import collections
import re

__all__ = ["PASSES", "Owner", "owner_of", "owners", "device_time",
           "format_table"]

PASSES = ("forward", "replay", "backward", "optimize", "other")

Owner = collections.namedtuple("Owner", "step_pass role type scope")
NO_OWNER = Owner(None, None, None, None)

# `pt_<role>.<type>`; a name scope (`pt_mla`) has no dot
_OWNER = re.compile(r"pt_([a-z_]+)\.(\w+)")
_SCOPE = re.compile(r"pt_\w+")
_FORWARD_ROLES = ("forward", "loss")    # parallel/pipeline.py's fwd_roles


def owner_of(op_name):
    """Owner(step_pass, role, type, scope) of one `op_name` path.

    `role`, `type`: of the INNERMOST `pt_<role>.<type>` on the path
    (inside a recompute segment: the forward op that is replayed or
    differentiated, not the segment's grad op).  `scope`: the `pt_*`
    elements after it joined by '/', None where there is none.
    `step_pass`: `replay` (a `rematted_computation` element), else
    `backward` (role `backward`, or a `transpose(` element: the
    cotangent pass of a jax.vjp), else `forward` (roles `forward` and
    `loss`), `optimize`, or `other` (`lr_sched`, `stat`, `rpc`).  All
    None where the path names no owner."""
    found = None
    for found in _OWNER.finditer(op_name or ""):
        pass
    if found is None:
        return NO_OWNER
    role, op_type = found.groups()
    scope = "/".join(_SCOPE.findall(op_name[found.end():])) or None
    if "rematted_computation" in op_name:
        step_pass = "replay"
    elif role == "backward" or "transpose(" in op_name:
        step_pass = "backward"
    elif role in _FORWARD_ROLES:
        step_pass = "forward"
    elif role == "optimize":
        step_pass = "optimize"
    else:
        step_pass = "other"
    return Owner(step_pass, role, op_type, scope)


# -- a compiled module's text ------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", re.M)
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_FUSION_CALLS = re.compile(r" fusion\(.*calls=%?([\w.\-]+)")
_RUNS = re.compile(r"(?:body|condition|to_apply)=%?([\w.\-]+)"
                   r"|branch_computations=\{([^}]*)\}")
_TRAILING_NUMBER = re.compile(r"[.\d]+$")
# an event of one of these spans the events of the computation it
# runs, on the same line of the trace
CONTAINERS = ("while", "conditional", "call")


def _computations(hlo_text):
    """[(name, [(is_root, instruction name, rest of the line)])]."""
    heads = list(_COMPUTATION.finditer(hlo_text))
    for i, head in enumerate(heads):
        end = heads[i + 1].start() if i + 1 < len(heads) else len(hlo_text)
        yield head.group(1), [
            (bool(m.group(1)), m.group(2), m.group(3))
            for m in _INSTRUCTION.finditer(hlo_text, head.end(), end)]


def _op_name(rest):
    m = _OP_NAME.search(rest)
    return m.group(1) if m else None


def owners(hlo_text):
    """{instruction name: Owner} over the instructions of every
    computation of a compiled module's text that is not a fused one
    (the entry, loop bodies, branches): what the profiler's `XLA Ops`
    line has events of.  A fusion is owned by its own metadata, which
    the compiler takes from the fusion's root, and where it has none by
    the root of the computation it calls: a fusion across two ops has
    one owner, as in every profiler.  What names no owner inside a loop
    body or a branch (jax lowers some loops once a module, with paths
    that start at the loop; the compiler's copies carry none) is owned
    by the `while`, `conditional` or `call` that runs it.  Any other
    instruction whose path names no owner maps to an Owner of Nones;
    keys have no leading '%'."""
    comps = dict(_computations(hlo_text or ""))
    fused, calls, run_by = set(), {}, {}
    for body in comps.values():
        for _, name, rest in body:
            m = _FUSION_CALLS.search(rest)
            if m:
                fused.add(m.group(1))
                calls[name] = m.group(1)
            elif _opcode(rest) in CONTAINERS:
                for one, several in _RUNS.findall(rest):
                    for comp in [one] if one else re.findall(
                            r"[\w.\-]+", several):
                        run_by[comp] = name
    out = {}
    for comp, body in comps.items():
        if comp in fused:
            continue
        for _, name, rest in body:
            path = _op_name(rest)
            if path is None and name in calls:
                path = next((_op_name(r) for root, _, r
                             in comps.get(calls[name], ()) if root), None)
            out[name] = owner_of(path)
    inherited = True
    while inherited:        # a loop in a loop: once a level
        inherited = False
        for comp, holder in run_by.items():
            who = out.get(holder, NO_OWNER)
            if who.role is None:
                continue
            for _, name, _ in comps.get(comp, ()):
                if out[name].role is None:
                    out[name] = who
                    inherited = True
    return out


def _instruction(event_name):
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


def _opcode(text):
    """The opcode of an instruction's text, with or without its
    `%name = `: the word before the first '(' that follows the result
    shape (a tuple shape has parentheses of its own)."""
    rhs, depth = text.split(" = ", 1)[-1], 0
    for i, ch in enumerate(rhs):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == " " and depth == 0:
            m = re.match(r" ([a-z][a-z0-9\-]*)\(", rhs[i:])
            if m:
                return m.group(1)
    return ""


def _row_key(event_name, owned):
    """(step_pass, "role.type", scope, compiler's name) of an event,
    None for a container."""
    if _opcode(event_name) in CONTAINERS:
        return None
    inst = _instruction(event_name)
    who = owned.get(inst, NO_OWNER)
    kind = _TRAILING_NUMBER.sub("", inst)
    if who.role is None:
        return ("other", "-", "-", kind)
    return (who.step_pass, "%s.%s" % (who.role, who.type),
            who.scope or "-", kind)


def device_time(events, owned):
    """Rows (step_pass, "role.type", scope, compiler's name, calls, ns),
    largest first, of one device's `XLA Ops` events `(name, start_ns,
    end_ns)`: the caller cuts them to the window it wants.  `owned`:
    what `owners` gives for the module that ran.  An event is named by
    its instruction's whole text (`%name = shape opcode(...)`) or by
    the instruction's name alone.  A `while`, `conditional` or `call`
    spans its own body's events and adds nothing, so no time is counted
    twice; an event without an owner is a row `("other", "-", "-",
    name)`: the rows sum to the time of the line's leaf events.  The
    compiler's name is the instruction's without its number, so the
    calls of one kind add up."""
    acc, key_of = {}, {}
    for name, start, end in events:
        if name not in key_of:      # a step's events repeat every step
            key_of[name] = _row_key(name, owned)
        key = key_of[name]
        if key is not None:
            calls, ns = acc.get(key, (0, 0))
            acc[key] = (calls + 1, ns + (end - start))
    return sorted((k + v for k, v in acc.items()),
                  key=lambda r: (-r[5], r[:4]))


_SORT = {"total": lambda e: -e[2], "calls": lambda e: -e[1],
         "ave": lambda e: -e[2] / e[1]}


def format_table(rows, steps=1, sorted_key="total"):
    """The reference profiler's report over `device_time`'s rows, as
    text: `Event  Calls  Total(ms)  Ave(ms)  Share`, a pass (in
    PASSES' order), then `role.type [scope]` within it with the
    compiler's names folded in, sorted by `sorted_key` (`total` |
    `calls` | `ave`); what has no owner is listed by the compiler's
    name under `other`.  Times are per step where `steps` says how
    many the rows cover."""
    whole = sum(r[5] for r in rows) or 1
    by_pass = {}
    for step_pass, op, scope, kind, calls, ns in rows:
        event = kind if op == "-" else \
            op if scope == "-" else "%s [%s]" % (op, scope)
        c, n = by_pass.setdefault(step_pass, {}).get(event, (0, 0))
        by_pass[step_pass][event] = (c + calls, n + ns)
    lines = ["%-64s %8s %11s %9s %7s"
             % ("Event", "Calls", "Total(ms)", "Ave(ms)", "Share")]

    def line(event, calls, ns):
        calls = calls / steps
        return "%-64s %8.1f %11.3f %9.4f %6.1f%%" % (
            event[:64], calls, ns / steps / 1e6,
            ns / steps / 1e6 / max(calls, 1e-9), 100.0 * ns / whole)

    for step_pass in PASSES:
        events = [(e,) + v for e, v in by_pass.get(step_pass, {}).items()]
        if not events:
            continue
        events.sort(key=_SORT.get(sorted_key, _SORT["total"]))
        lines.append(line("== " + step_pass, sum(e[1] for e in events),
                          sum(e[2] for e in events)))
        lines.extend(line("  " + e[0], e[1], e[2]) for e in events)
    return "\n".join(lines)

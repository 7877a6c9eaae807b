#!/usr/bin/env python
"""Repo-discipline linter (ISSUE 15): AST-enforce the rules the repo
only WROTE down until now (docs + review habit), so drift becomes a CI
failure instead of an archaeology project.

Rules (docs/ANALYSIS.md has the table; each finding carries its rule
id, file:line, and a one-line message):

  flag-default-off     every flags.define_flag default is off
                       (False / 0 / 0.0 / "off") — new surfaces ship
                       dark; strategy-selector flags whose default
                       picks an implementation (not a behavior change)
                       live in the allowlist with a reason.
  serving-error-code   every (transitive) ServingError subclass
                       defines a stable class-level ``code`` string in
                       its own body — fleet callers shed on codes, a
                       subclass inheriting its parent's code silently
                       aliases two failure modes.
  metric-name-grammar  every literal metric name at a
                       counter/gauge/histogram call site matches the
                       registry grammar ^[a-z][a-z0-9_]*$ AND the repo
                       namespace prefix ``paddle_tpu_``.
  fault-type-registered every literal/constant msg type consulted at a
                       faultinject ``decide()`` site (or declared as a
                       ``MSG_*`` constant) is registered via
                       ``faultinject.register_msg_type`` or an RPC
                       ``register_handler`` literal — a typo'd fault
                       point never fires and reads as "chaos passed".
  env-knob-documented  every ``PADDLE_TPU_*`` literal referenced in
                       code appears in a docs/*.md env-knob table.
  no-bare-except       no ``except:`` — it eats KeyboardInterrupt and
                       SystemExit; ``except Exception`` at minimum.
  epilogue-stage-names every literal ``epilogue`` attr string — a
                       ``{"epilogue": "<...>"}`` dict entry or a
                       ``set_attr("epilogue", "<...>")`` site — parses
                       and validates against the stage grammar in
                       ops/epilogue.py (ISSUE 17): a typo'd or
                       mis-ordered stage list would otherwise only
                       explode when the verifier meets the op at
                       runtime.  spec_attr()-built values are checked
                       at build time by construction and are not
                       literals, so they don't reach this rule.

Intentional exceptions live in tools/repo_lint_allowlist.json as
{"rule", "id", "reason"} entries; an allowlist entry that no longer
matches anything is itself a finding (stale-allowlist), so the list
can only shrink.

Usage: python tools/repo_lint.py [--json]   (exit 0 iff clean)
"""

from __future__ import annotations

import argparse
import ast
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# lint scope: the library, the tools, the driver's entry point.  tests/ are
# excluded on purpose: broken-IR fixtures and fake fault types are
# the point of tests.
SCAN_DIRS = ("paddle_tpu", "tools")
SCAN_FILES = ("__graft_entry__.py",)

METRIC_NAME_RE = re.compile(r"[a-z][a-z0-9_]*\Z")
METRIC_PREFIX = "paddle_tpu_"
ENV_KNOB_RE = re.compile(r"PADDLE_TPU_[A-Z][A-Z0-9_]*")


def _iter_py_files():
    for d in SCAN_DIRS:
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(ROOT, d)):
            dirnames[:] = [x for x in dirnames
                           if x not in ("__pycache__",)]
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)
    for fn in SCAN_FILES:
        p = os.path.join(ROOT, fn)
        if os.path.exists(p):
            yield p


def _rel(path):
    return os.path.relpath(path, ROOT)


class Finding:
    def __init__(self, rule, ident, path, line, message):
        self.rule = rule
        self.id = ident        # stable allowlist key
        self.path = _rel(path) if os.path.isabs(path) else path
        self.line = line
        self.message = message

    def __str__(self):
        return (f"{self.path}:{self.line}: [{self.rule}] {self.id}: "
                f"{self.message}")

    def to_dict(self):
        return {"rule": self.rule, "id": self.id, "path": self.path,
                "line": self.line, "message": self.message}


def _str_const(node):
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _call_name(call):
    """Dotted-ish name of a Call's func: 'a.b.c' -> 'c' kept too."""
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


class _FileScan:
    """One parsed file + the per-rule raw facts."""

    def __init__(self, path):
        self.path = path
        with open(path) as f:
            self.src = f.read()
        self.tree = ast.parse(self.src, filename=path)


def lint():
    findings = []
    files = list(_iter_py_files())
    scans = []
    for p in files:
        try:
            scans.append(_FileScan(p))
        except SyntaxError as e:
            findings.append(Finding(
                "parse-error", os.path.basename(p), p,
                getattr(e, "lineno", 0) or 0, str(e)))

    # ---------------------------------------------------------- rule 1
    # flag-default-off: flags.py define_flag second arg
    for s in scans:
        if not s.path.endswith(os.path.join("paddle_tpu", "flags.py")):
            continue
        for node in ast.walk(s.tree):
            if not (isinstance(node, ast.Call) and
                    _call_name(node) == "define_flag"):
                continue
            if len(node.args) < 2:
                continue
            name = _str_const(node.args[0])
            default = node.args[1]
            off = isinstance(default, ast.Constant) and (
                default.value is False or default.value == 0 or
                default.value == 0.0 or default.value == "off")
            if not off:
                dv = getattr(default, "value", "<expr>")
                findings.append(Finding(
                    "flag-default-off", f"flag:{name}", s.path,
                    node.lineno,
                    f"flag {name!r} defaults to {dv!r} (not off) — "
                    "new surfaces ship dark"))

    # ---------------------------------------------------------- rule 2
    # serving-error-code: transitive ServingError subclasses define a
    # class-body `code = "<str>"`
    classes = {}   # name -> (bases, has_code, path, line)
    for s in scans:
        for node in ast.walk(s.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            bases = []
            for b in node.bases:
                if isinstance(b, ast.Name):
                    bases.append(b.id)
                elif isinstance(b, ast.Attribute):
                    bases.append(b.attr)
            has_code = any(
                isinstance(st, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == "code"
                    for t in st.targets) and
                _str_const(st.value) is not None
                for st in node.body)
            classes.setdefault(node.name,
                               (bases, has_code, s.path, node.lineno))

    serving_errors = {"ServingError"}
    changed = True
    while changed:
        changed = False
        for name, (bases, _, _, _) in classes.items():
            if name not in serving_errors and \
                    any(b in serving_errors for b in bases):
                serving_errors.add(name)
                changed = True
    for name in sorted(serving_errors - {"ServingError"}):
        bases, has_code, path, line = classes[name]
        if not has_code:
            findings.append(Finding(
                "serving-error-code", f"class:{name}", path, line,
                f"ServingError subclass {name} defines no stable "
                "class-level `code` string — it silently aliases its "
                "parent's shed code"))

    # ---------------------------------------------------------- rule 3
    # metric-name-grammar at counter/gauge/histogram call sites
    for s in scans:
        if s.path.endswith(os.path.join("observability", "metrics.py")):
            continue  # the registry itself (helpers + generic kinds)
        for node in ast.walk(s.tree):
            if not (isinstance(node, ast.Call) and _call_name(node) in
                    ("counter", "gauge", "histogram")):
                continue
            name = _str_const(node.args[0]) if node.args else None
            if name is None:
                continue
            if not METRIC_NAME_RE.match(name) or \
                    not name.startswith(METRIC_PREFIX):
                findings.append(Finding(
                    "metric-name-grammar", f"metric:{name}", s.path,
                    node.lineno,
                    f"metric name {name!r} violates the registry "
                    f"grammar ^[a-z][a-z0-9_]*$ + '{METRIC_PREFIX}' "
                    "namespace prefix"))

    # ---------------------------------------------------------- rule 4
    # fault-type-registered: registered set = register_msg_type +
    # register_handler literals; checked set = decide() args
    # (literal or same-module constant) + MSG_* constant literals
    registered = set()
    for s in scans:
        for node in ast.walk(s.tree):
            if isinstance(node, ast.Call) and _call_name(node) in (
                    "register_msg_type", "register_handler"):
                v = _str_const(node.args[0]) if node.args else None
                if v is not None:
                    registered.add(v)
    for s in scans:
        consts = {}
        for node in ast.walk(s.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name):
                tname = node.targets[0].id
                v = _str_const(node.value)
                if v is None and isinstance(node.value, ast.Call) and \
                        _call_name(node.value) == "register_msg_type" \
                        and node.value.args:
                    v = _str_const(node.value.args[0])
                if v is not None:
                    consts[tname] = (v, node.lineno)
        for node in ast.walk(s.tree):
            if not (isinstance(node, ast.Call) and
                    _call_name(node) == "decide" and node.args):
                continue
            arg = node.args[0]
            v = _str_const(arg)
            if v is None and isinstance(arg, ast.Name):
                v = consts.get(arg.id, (None, 0))[0]
            if v is None:
                continue  # dynamic (wire dispatch) — runtime's business
            if v != "*" and v not in registered:
                findings.append(Finding(
                    "fault-type-registered", f"msgtype:{v}", s.path,
                    node.lineno,
                    f"faultinject msg type {v!r} consulted here is "
                    "never registered (register_msg_type / an RPC "
                    "register_handler) — a plan naming it can't fire"))

    # ---------------------------------------------------------- rule 5
    # env-knob-documented: PADDLE_TPU_* literals vs docs/*.md
    documented = set()
    docs_dir = os.path.join(ROOT, "docs")
    for fn in sorted(os.listdir(docs_dir)):
        if fn.endswith(".md"):
            with open(os.path.join(docs_dir, fn)) as f:
                documented.update(ENV_KNOB_RE.findall(f.read()))
    for extra in ("README.md", "ROADMAP.md"):
        p = os.path.join(ROOT, extra)
        if os.path.exists(p):
            with open(p) as f:
                documented.update(ENV_KNOB_RE.findall(f.read()))
    seen_knobs = {}
    for s in scans:
        for m in ENV_KNOB_RE.finditer(s.src):
            knob = m.group(0)
            line = s.src.count("\n", 0, m.start()) + 1
            seen_knobs.setdefault(knob, (s.path, line))
    for knob in sorted(seen_knobs):
        if knob in documented:
            continue
        path, line = seen_knobs[knob]
        findings.append(Finding(
            "env-knob-documented", f"env:{knob}", path, line,
            f"env knob {knob} is referenced in code but appears in "
            "no docs/*.md env-knob table"))

    # ---------------------------------------------------------- rule 6
    # epilogue-stage-names: literal epilogue attr strings must parse
    # against the ops/epilogue.py stage grammar.  Sites are collected
    # first; the (jax-heavy) grammar import only happens if any exist.
    ep_sites = []   # (value, path, line)
    for s in scans:
        for node in ast.walk(s.tree):
            if isinstance(node, ast.Dict):
                for k, v in zip(node.keys, node.values):
                    if _str_const(k) == "epilogue" and \
                            _str_const(v) is not None:
                        ep_sites.append(
                            (_str_const(v), s.path, v.lineno))
            elif isinstance(node, ast.Call) and \
                    _call_name(node) == "set_attr" and \
                    len(node.args) >= 2 and \
                    _str_const(node.args[0]) == "epilogue" and \
                    _str_const(node.args[1]) is not None:
                ep_sites.append((_str_const(node.args[1]), s.path,
                                 node.lineno))
    if ep_sites:
        sys.path.insert(0, ROOT)
        from paddle_tpu.ops.epilogue import EpilogueSpec
        for value, path, line in ep_sites:
            try:
                EpilogueSpec.from_attr(value).validate()
            except ValueError as e:
                findings.append(Finding(
                    "epilogue-stage-names", f"epilogue:{value}", path,
                    line,
                    f"epilogue attr literal {value!r} is not a valid "
                    f"stage list: {e}"))

    # ---------------------------------------------------------- rule 7
    # no-bare-except
    for s in scans:
        for node in ast.walk(s.tree):
            if isinstance(node, ast.ExceptHandler) and \
                    node.type is None:
                findings.append(Finding(
                    "no-bare-except",
                    f"bare-except:{_rel(s.path)}:{node.lineno}",
                    s.path, node.lineno,
                    "bare `except:` catches KeyboardInterrupt/"
                    "SystemExit — use `except Exception` at minimum"))

    return findings


def apply_allowlist(findings):
    path = os.path.join(ROOT, "tools", "repo_lint_allowlist.json")
    entries = []
    if os.path.exists(path):
        with open(path) as f:
            entries = json.load(f)["allow"]
    allowed = {(e["rule"], e["id"]): e for e in entries}
    used = set()
    kept = []
    for f in findings:
        if (f.rule, f.id) in allowed:
            used.add((f.rule, f.id))
        else:
            kept.append(f)
    for key, e in sorted(allowed.items()):
        if key not in used:
            kept.append(Finding(
                "stale-allowlist", f"{key[0]}/{key[1]}",
                "tools/repo_lint_allowlist.json", 0,
                f"allowlist entry {key} matches no finding any more "
                "— delete it (the list only shrinks)"))
    return kept, len(used)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", action="store_true",
                    help="one-JSON-line verdict on stdout")
    args = ap.parse_args(argv)
    findings, allowed = apply_allowlist(lint())
    if args.json:
        print(json.dumps({
            "metric": "repo_lint", "value": len(findings),
            "unit": "findings", "ok": not findings,
            "allowed": allowed,
            "findings": [f.to_dict() for f in findings],
        }))
    else:
        for f in findings:
            print(f)
        print(f"repo_lint: {len(findings)} finding(s), "
              f"{allowed} allowlisted")
    return 1 if findings else 0


if __name__ == "__main__":
    sys.exit(main())

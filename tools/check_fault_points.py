#!/usr/bin/env python3
"""Static fault-point check (tier-1 via tests/test_fault_points.py).

The cluster fault plane matches rules by PREFIX against fired point
names (utils/fault_injection.py), which means a typo'd ``point=`` in a
spec injects NOTHING — silently. A chaos schedule that never fires is
worse than no schedule: it reports green while testing nothing. This
check closes that hole statically, mirroring the recorder-registry
check's shape:

1. FIRE SITES — AST-walk ``tpu3fs/`` collecting every point name that
   can actually fire: literal first arguments of ``inject(...)`` /
   ``inject_result(...)`` calls and of ``<plane>.fire(...)`` calls;
   f-string arguments contribute their leading constant as a DYNAMIC
   PREFIX (``f"rpc.send.{method}"`` → ``rpc.send.``).

2. SPEC POINTS — every ``point=<name>`` occurrence in the repo's
   Python, JSON (the ``tests/chaos_seeds/`` corpus), TOML, and Markdown
   files (drive scripts, tests, docs examples, deploy configs), plus
   the chaos generator's ``FAULT_POINTS`` menu. Fire sites in
   tests/drive scripts count too (a test may fire its own synthetic
   point), and a line carrying ``# fault-ok`` is exempt (parse-only
   grammar tests).

3. RESOLUTION — a spec point ``S`` resolves iff some fired name can
   start with it: a static point ``P`` with ``P.startswith(S)``, or a
   dynamic prefix ``D`` with ``S.startswith(D)`` or
   ``D.startswith(S)``. Anything else is an error naming the file.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: directories scanned for fault specs (point= occurrences)
SPEC_DIRS = ("tpu3fs", "tests", "tools", "docs", "deploy",
             os.path.join(".claude", "skills", "verify"))
SPEC_EXTS = (".py", ".json", ".toml", ".md")

#: spec-string context only: the token must follow a quote, whitespace,
#: ``;`` or start-of-line and begin with a letter — Python kwargs like
#: ``dict(point=r.point)`` don't match
#: the negative lookahead drops Python kwarg usage whose value is a
#: subscript/call (``point=fields["point"]``)
_POINT_RE = re.compile(
    r"""(?:^|["'\s;`])point=([a-z][a-z0-9_.]*)(?![\w\[(])""")

INJECT_FNS = {"inject", "inject_result"}

#: fire sites may also live in tests/drive scripts (a test that
#: defines AND fires its own synthetic point is self-contained)
FIRE_DIRS = ("tpu3fs", "tests",
             os.path.join(".claude", "skills", "verify"))


def _walk(root: str, exts: Tuple[str, ...]) -> List[str]:
    out = []
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames
                       if d not in ("__pycache__", ".git", "node_modules")]
        for name in filenames:
            if name.endswith(exts):
                out.append(os.path.join(dirpath, name))
    return sorted(out)


def fire_points() -> Tuple[Set[str], Set[str], List[str]]:
    """-> (static points, dynamic prefixes, errors) over FIRE_DIRS."""
    static: Set[str] = set()
    dynamic: Set[str] = set()
    errors: List[str] = []
    paths: List[str] = []
    for d in FIRE_DIRS:
        root = os.path.join(REPO, d)
        if os.path.isdir(root):
            paths.extend(_walk(root, (".py",)))
    for path in paths:
        rel = os.path.relpath(path, REPO)
        with open(path, encoding="utf-8") as f:
            try:
                tree = ast.parse(f.read(), filename=rel)
            except SyntaxError as e:
                errors.append(f"{rel}: unparseable: {e}")
                continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or not node.args:
                continue
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else "")
            if name not in INJECT_FNS and name != "fire":
                continue
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                static.add(arg.value)
            elif isinstance(arg, ast.JoinedStr):
                head = arg.values[0] if arg.values else None
                if isinstance(head, ast.Constant) \
                        and isinstance(head.value, str) and head.value:
                    dynamic.add(head.value)
                else:
                    errors.append(
                        f"{rel}:{node.lineno}: {name}() f-string point "
                        "without a literal leading prefix — statically "
                        "unmatchable")
            # non-literal args (variables) are executor plumbing, not
            # declarations — e.g. FaultPlane.fire(point) itself
    return static, dynamic, errors


def spec_points() -> List[Tuple[str, str]]:
    """-> [(where, point)] for every point= occurrence in repo specs,
    plus the chaos generator's FAULT_POINTS menu."""
    out: List[Tuple[str, str]] = []
    for d in SPEC_DIRS:
        root = os.path.join(REPO, d)
        if not os.path.isdir(root):
            continue
        for path in _walk(root, SPEC_EXTS):
            rel = os.path.relpath(path, REPO)
            if os.path.abspath(path) == os.path.abspath(__file__):
                continue
            with open(path, encoding="utf-8", errors="replace") as f:
                for lineno, line in enumerate(f, 1):
                    if "# fault-ok" in line:
                        continue  # parse-only grammar test
                    for m in _POINT_RE.finditer(line):
                        out.append((f"{rel}:{lineno}", m.group(1)))
    sys.path.insert(0, REPO)
    try:
        from tpu3fs.chaos.schedule import FAULT_POINTS

        for p in FAULT_POINTS:
            out.append(("tpu3fs/chaos/schedule.py:FAULT_POINTS", p))
    finally:
        sys.path.pop(0)
    return out


def resolves(point: str, static: Set[str], dynamic: Set[str]) -> bool:
    if any(p.startswith(point) for p in static):
        return True
    return any(point.startswith(d) or d.startswith(point) for d in dynamic)


def run_checks() -> Tuple[List[str], List[str]]:
    static, dynamic, errors = fire_points()
    if not static:
        errors.append("no static injection points found under tpu3fs/ "
                      "(the AST walk is broken)")
    specs = spec_points()
    unresolved = []
    for where, point in specs:
        if not resolves(point, static, dynamic):
            unresolved.append(
                f"{where}: fault point {point!r} matches no "
                f"inject()/inject_result()/plane().fire() call site — "
                f"this rule can never fire")
    errors.extend(sorted(set(unresolved)))
    notes = [
        f"{len(static)} static point(s): {sorted(static)}",
        f"{len(dynamic)} dynamic prefix(es): {sorted(dynamic)}",
        f"{len(specs)} spec point reference(s) checked",
    ]
    return errors, notes


def main() -> int:
    errors, notes = run_checks()
    for n in notes:
        print(f"note: {n}")
    if errors:
        for e in errors:
            print(f"ERROR: {e}")
        print(f"{len(errors)} error(s)")
        return 1
    print("fault points clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())

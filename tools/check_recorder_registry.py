"""Static recorder-registry check (CI tier-1; check_rpc_registry pattern).

Walks every ``tpu3fs/`` source file's AST and collects each
``CounterRecorder/ValueRecorder/DistributionRecorder/LatencyRecorder``
construction, then enforces the observability contract
(docs/observability.md):

1. NAMING — every recorder name is a ``subsystem.metric`` dotted
   lowercase path (``[a-z0-9_]`` segments, >= 2 of them);
2. UNIQUENESS — a name is declared at exactly ONE source location
   (instances may be many — per node, per target — but the declaration
   site, and therefore the semantic owner, is single; two subsystems
   silently sharing ``x.y`` would corrupt every aggregation over it);
3. DOC TABLE — every name appears in docs/observability.md's metric
   table (and the table carries no stale names), so the doc IS the
   registry;
4. TAG VOCABULARY — literal tag dicts only use keys from the fixed
   vocabulary (service, class, tenant, chain, node, kind, point,
   target): the collector's group-bys and admin_cli top's joins key on
   these.
5. SLO RULE REFERENCES — every metric name referenced by an ``[slo]``
   rule in any shipped/default config (the
   ``slo.DEFAULT_CLUSTER_SPEC`` constant plus every ``[slo] spec``
   found in repo TOML files) must resolve to a declared recorder name
   (LatencyRecorder families expand to ``.succeeded``/``.failed``/
   ``.latency_us``; the ``memory.*`` proc gauges come from
   ``monitor/memory._FIELDS``). A typo'd rule must fail HERE,
   statically — not ship and silently never fire.

Dynamic names (f-strings, variables) are only allowed in the whitelisted
infrastructure files that build recorders ON BEHALF of callers
(monitor/recorder.py's LatencyRecorder family, monitor/memory.py's
source gauges — their metric STRINGS are still checked where the callers
declare them).

Run: ``python tools/check_recorder_registry.py`` (exit 0 = clean);
tests/test_recorder_registry.py wires it into tier-1.
"""

from __future__ import annotations

import ast
import os
import re
import sys
from typing import Dict, List, Tuple

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "tpu3fs")
DOC = os.path.join(REPO, "docs", "observability.md")

RECORDER_CLASSES = {"CounterRecorder", "ValueRecorder",
                    "DistributionRecorder", "LatencyRecorder"}

#: the fixed tag-key vocabulary (docs/observability.md)
TAG_VOCAB = {"service", "class", "tenant", "chain", "node", "kind", "point",
             "target"}

#: files allowed to construct recorders with NON-LITERAL names (they
#: build on behalf of callers; the caller-side literals are checked)
DYNAMIC_NAME_OK = {
    os.path.join("tpu3fs", "monitor", "recorder.py"),
    os.path.join("tpu3fs", "monitor", "memory.py"),
}

NAME_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_]+)+$")


def _call_name(node: ast.Call) -> str:
    fn = node.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return ""


def collect_declarations() -> Tuple[List[Tuple[str, str, int, str]],
                                    List[str]]:
    """-> ([(name, relpath, lineno, kind)], errors) over tpu3fs/."""
    decls: List[Tuple[str, str, int, str]] = []
    errors: List[str] = []
    for dirpath, _dirnames, filenames in os.walk(PKG):
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, REPO)
            with open(path, encoding="utf-8") as f:
                src = f.read()
            try:
                tree = ast.parse(src)
            except SyntaxError as e:  # tier-1 would fail anyway; be loud
                errors.append(f"{rel}: unparsable: {e}")
                continue
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                kind = _call_name(node)
                if kind == "add_source":
                    # MemoryMonitor sources declare gauge names too
                    # (mem.* / engine used-size): same registry rules
                    if node.args and isinstance(node.args[0], ast.Constant) \
                            and isinstance(node.args[0].value, str):
                        decls.append((node.args[0].value, rel,
                                      node.lineno, "source"))
                    continue
                if kind not in RECORDER_CLASSES:
                    continue
                where = f"{rel}:{node.lineno}"
                if not node.args:
                    errors.append(f"{where}: {kind} without a name arg")
                    continue
                name_node = node.args[0]
                if isinstance(name_node, ast.Constant) and isinstance(
                        name_node.value, str):
                    decls.append((name_node.value, rel, node.lineno, kind))
                elif rel not in DYNAMIC_NAME_OK:
                    errors.append(
                        f"{where}: {kind} name is not a string literal "
                        "(dynamic names only in "
                        f"{sorted(DYNAMIC_NAME_OK)})")
                # tag vocabulary: literal dict in args[1] or tags=
                tag_node = None
                if len(node.args) > 1:
                    tag_node = node.args[1]
                for kw in node.keywords:
                    if kw.arg == "tags":
                        tag_node = kw.value
                if isinstance(tag_node, ast.Dict):
                    for k in tag_node.keys:
                        if isinstance(k, ast.Constant) and isinstance(
                                k.value, str):
                            if k.value not in TAG_VOCAB:
                                errors.append(
                                    f"{where}: tag key {k.value!r} not in "
                                    f"the fixed vocabulary "
                                    f"{sorted(TAG_VOCAB)}")
    return decls, errors


def doc_table_names() -> List[str]:
    """Names from the rows of docs/observability.md's "## Metric table"
    section only (the doc's other tables — stage glossary, knobs — are
    not metric declarations)."""
    if not os.path.exists(DOC):
        return []
    names = []
    in_section = False
    with open(DOC, encoding="utf-8") as f:
        for line in f:
            if line.startswith("## "):
                in_section = line.strip().lower() == "## metric table"
                continue
            if not in_section:
                continue
            # an optional `{tag,tag}` suffix documents a tagged family
            # (e.g. `faults.fired{kind,point}`): tags are annotation,
            # the metric NAME is what round-trips with the declarations
            m = re.match(r"^\|\s*`([a-z0-9_.]+)(?:\{[a-z0-9_,]+\})?`\s*\|",
                         line)
            if m:
                names.append(m.group(1))
    return names


def slo_spec_sources() -> List[Tuple[str, str]]:
    """-> [(label, spec)] of every shipped/default [slo] rule spec: the
    engine's DEFAULT_CLUSTER_SPEC plus any [slo] section in repo TOML
    files (deploy configs, examples)."""
    out: List[Tuple[str, str]] = []
    from tpu3fs.monitor.slo import DEFAULT_CLUSTER_SPEC

    out.append(("tpu3fs.monitor.slo.DEFAULT_CLUSTER_SPEC",
                DEFAULT_CLUSTER_SPEC))
    import tomllib

    for dirpath, dirnames, filenames in os.walk(REPO):
        dirnames[:] = [d for d in dirnames
                       if d not in (".git", "__pycache__",
                                    ".claude", "node_modules")]
        for fn in sorted(filenames):
            if not fn.endswith(".toml"):
                continue
            path = os.path.join(dirpath, fn)
            try:
                with open(path, "rb") as f:
                    data = tomllib.load(f)
            except Exception:
                continue
            spec = (data.get("slo") or {}).get("spec", "")
            if spec:
                out.append((os.path.relpath(path, REPO), spec))
    return out


def check_slo_specs(decls: List[Tuple[str, str, int, str]]) -> List[str]:
    """Check 5: every [slo]-rule metric resolves to a declared
    recorder name."""
    from tpu3fs.monitor.memory import _FIELDS
    from tpu3fs.monitor.slo import parse_slo_spec

    known = set(_FIELDS.values())
    for name, _rel, _lineno, kind in decls:
        known.add(name)
        if kind == "LatencyRecorder":
            for suffix in (".succeeded", ".failed", ".latency_us"):
                known.add(name + suffix)
    errors: List[str] = []
    for label, spec in slo_spec_sources():
        try:
            rules = parse_slo_spec(spec)
        except ValueError as e:
            errors.append(f"{label}: unparsable [slo] spec: {e}")
            continue
        for rule in rules.values():
            if rule.metric not in known:
                errors.append(
                    f"{label}: slo rule {rule.name!r} references "
                    f"metric {rule.metric!r}, which no recorder "
                    "declares (typo'd rules must fail statically, "
                    "not silently never fire)")
    return errors


def run_checks() -> Tuple[List[str], List[str]]:
    decls, errors = collect_declarations()
    notes: List[str] = []

    # 1. naming
    for name, rel, lineno, kind in decls:
        if not NAME_RE.match(name):
            errors.append(
                f"{rel}:{lineno}: recorder name {name!r} is not a "
                "subsystem.metric dotted lowercase path")

    # 2. uniqueness of the declaration site
    sites: Dict[str, List[str]] = {}
    for name, rel, lineno, _kind in decls:
        sites.setdefault(name, []).append(f"{rel}:{lineno}")
    for name, where in sorted(sites.items()):
        if len(where) > 1:
            errors.append(
                f"recorder name {name!r} declared at {len(where)} sites: "
                f"{', '.join(where)} (one name, one owner)")

    # 3. doc table round trip
    doc = doc_table_names()
    if not doc:
        errors.append(f"{os.path.relpath(DOC, REPO)}: metric table "
                      "missing or empty")
    doc_set = set(doc)
    for name in sorted(sites):
        if name not in doc_set:
            errors.append(
                f"recorder {name!r} missing from the metric table in "
                "docs/observability.md")
    for name in sorted(doc_set - set(sites)):
        errors.append(
            f"docs/observability.md lists {name!r} but no recorder "
            "declares it (stale row)")
    dupes = {n for n in doc if doc.count(n) > 1}
    for name in sorted(dupes):
        errors.append(f"docs/observability.md lists {name!r} twice")

    # 5. shipped/default [slo] rules reference only declared metrics
    errors.extend(check_slo_specs(decls))

    notes.append(f"{len(decls)} recorder declarations, "
                 f"{len(sites)} distinct names, {len(doc)} doc rows, "
                 f"{len(slo_spec_sources())} slo spec source(s)")
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
    print("recorder registry clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Lint: telemetry metrics, registry, and docs must agree.

Three-way contract (wired into the suite as tests/test_metric_docs.py),
mirroring tools/check_fault_sites.py:

1. every string-literal metric name passed to ``inc_counter(...)`` /
   ``set_gauge(...)`` / ``observe(...)`` inside the ``horovod_tpu``
   package must be declared in ``telemetry.registry.KNOWN_METRICS`` —
   an undeclared name raises at runtime when the registry is on, and
   this catches it at lint time;
2. every registered metric must appear in the docs/metrics.md table
   (word-boundary match, same rule as tools/check_env_docs.py) — the
   registry IS the user-facing scrape surface;
3. the registry may declare metrics with no literal in-package call
   site (names built at runtime would be invisible to the AST scan),
   but never the reverse;
4. every share of two counters that the registry declares for
   ``GET /stats`` (``stats_shares()``) names two registered counters and
   appears in docs/metrics.md under its key.

Usage: ``python tools/check_metric_docs.py`` (exit 1 on violations).
"""

from __future__ import annotations

import ast
import re
import sys
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parent.parent
PKG_DIR = REPO_ROOT / "horovod_tpu"
DOC_FILE = REPO_ROOT / "docs" / "metrics.md"

_HOOKS = ("inc_counter", "set_gauge", "observe")


def _called_hook(call: ast.Call) -> bool:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id in _HOOKS
    if isinstance(fn, ast.Attribute):
        return fn.attr in _HOOKS
    return False


def used_literals(pkg_dir: Path = PKG_DIR) -> dict:
    """``{metric: [relpath, ...]}`` for every literal first argument to
    an ``inc_counter()`` / ``set_gauge()`` / ``observe()`` call in the
    package (the registry's own implementation excluded)."""
    import os

    out: dict = {}
    skip = pkg_dir / "telemetry" / "registry.py"
    for py in sorted(pkg_dir.rglob("*.py")):
        if py == skip:
            continue
        tree = ast.parse(py.read_text(encoding="utf-8"))
        rel = os.path.relpath(str(py), str(REPO_ROOT))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call) and _called_hook(node)
                    and node.args):
                continue
            first = node.args[0]
            if isinstance(first, ast.Constant) and \
                    isinstance(first.value, str):
                out.setdefault(first.value, []).append(rel)
    return out


def _registry_module():
    sys.path.insert(0, str(REPO_ROOT))
    try:
        from horovod_tpu.telemetry import registry as reg
    finally:
        sys.path.pop(0)
    return reg


def registry() -> dict:
    return _registry_module().known_metrics()


def undeclared_metrics(pkg_dir: Path = PKG_DIR) -> dict:
    known = registry()
    return {m: files for m, files in used_literals(pkg_dir).items()
            if m not in known}


def undocumented_metrics(doc_file: Path = DOC_FILE) -> list:
    if not doc_file.is_file():
        return sorted(registry())
    text = doc_file.read_text(encoding="utf-8")
    # Word-boundary match so hvd_cycles_total is not satisfied by
    # hvd_cycles_total_ever or hvd_cycles (metric names are identifier
    # words).
    return [m for m in sorted(registry())
            if not re.search(rf"\b{re.escape(m)}\b", text)]


def bad_shares(doc_file: Path = DOC_FILE) -> list:
    """Declared ``GET /stats`` shares whose key docs/metrics.md does not
    name, or whose two counters are not both registered counters."""
    reg = _registry_module()
    known = reg.known_metrics()
    text = doc_file.read_text(encoding="utf-8") if doc_file.is_file() else ""
    return [key for key, pair in sorted(reg.stats_shares().items())
            if not re.search(rf"\b{re.escape(key)}\b", text)
            or any(known.get(m, {}).get("kind") != "counter" for m in pair)]


def alert_rules() -> tuple:
    sys.path.insert(0, str(REPO_ROOT))
    try:
        from horovod_tpu.telemetry import aggregate as agg
    finally:
        sys.path.pop(0)
    return tuple(agg.ALERT_RULES)


def undocumented_alert_rules(doc_file: Path = DOC_FILE) -> list:
    """Anomaly-engine rule names (telemetry.aggregate.ALERT_RULES)
    missing from the docs/metrics.md rule table — the same contract as
    the metric table, for the alert surface."""
    if not doc_file.is_file():
        return sorted(alert_rules())
    text = doc_file.read_text(encoding="utf-8")
    return [r for r in sorted(alert_rules())
            if not re.search(rf"\b{re.escape(r)}\b", text)]


def main() -> int:
    bad = False
    undecl = undeclared_metrics()
    if undecl:
        bad = True
        print("metric names used in code but missing from "
              "telemetry.registry.KNOWN_METRICS:", file=sys.stderr)
        for m, files in sorted(undecl.items()):
            print(f"  {m!r}  ({', '.join(sorted(set(files)))})",
                  file=sys.stderr)
    undoc = undocumented_metrics()
    if undoc:
        bad = True
        print("registered metrics missing from the docs/metrics.md "
              "table:", file=sys.stderr)
        for m in undoc:
            print(f"  {m!r}", file=sys.stderr)
    shares = bad_shares()
    if shares:
        bad = True
        print("GET /stats shares missing from docs/metrics.md, or not of "
              "two registered counters:", file=sys.stderr)
        for key in shares:
            print(f"  {key!r}", file=sys.stderr)
    undoc_rules = undocumented_alert_rules()
    if undoc_rules:
        bad = True
        print("anomaly-engine alert rules missing from the "
              "docs/metrics.md rule table:", file=sys.stderr)
        for r in undoc_rules:
            print(f"  {r!r}", file=sys.stderr)
    if bad:
        print("declare each metric in KNOWN_METRICS "
              "(horovod_tpu/telemetry/registry.py) and document it in "
              "the table in docs/metrics.md.", file=sys.stderr)
        return 1
    print(f"ok: {len(registry())} metrics registered and documented; "
          f"{len(used_literals())} literal call sites in the package; "
          f"{len(alert_rules())} alert rules documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())

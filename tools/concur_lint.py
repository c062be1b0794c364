#!/usr/bin/env python
"""Concurrency-contract gate: static lock/guard + effect analysis +
module-boundary manifest enforcement over starrocks_tpu/.

Runs ahead of pytest in tools/run_tier1.sh (next to src_lint/plan_lint):

- analysis/concur_check.py — lock inventory, the cross-object
  lock-acquisition graph (lock-order cycles = potential deadlocks,
  lexical self-nesting of non-reentrant locks = certain deadlocks), and
  the `# guarded_by:` field discipline, strict: any error finding fails
  the gate. Warn findings (the unannotated-mutable-attr coverage ratchet)
  print and count but do not fail — tests/test_concur_check.py bounds
  the count; use --strict-warn to ratchet hard.

- analysis/effects_check.py — interprocedural effect summaries over the
  same parse + name index: exception-safe acquire, checkpoint density of
  blocking loops, no blocking under lock, daemon-thread lifecycle. Warn
  findings are suppression annotations missing a reason (the
  `--strict-warn` ratchet keeps unexplained exceptions at zero).

- analysis/boundary_check.py — the repo-root module_boundary_manifest.json
  (the reference's be/module_boundary_manifest.json analog): every
  package-internal import must match its unit's declared allow/forbid
  prefixes; undeclared coupling fails.

The checkers are loaded by FILE PATH (not package import): the gate must
run on a box with no jax install, and starrocks_tpu/__init__.py pulls
jax. They share one parsed AST per module (analysis/astwalk.py) — the
same trees src_lint walks.

Exit 1 on any error finding; prints `concur_lint: ...` summary with the
counts the driver and bench read. `--json` emits the findings as one
machine-readable object instead (pass name, severity, contract rule,
file:line, message, per-pass stats) for dashboards and the driver.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(name: str, rel: str):
    existing = sys.modules.get(name)
    if existing is not None:
        return existing
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def collect():
    """Run all three passes over ONE shared parse; returns
    (findings_by_pass, stats_by_pass, module_count)."""
    astwalk = _load("sr_astwalk", "starrocks_tpu/analysis/astwalk.py")
    concur_check = _load("sr_concur_check",
                         "starrocks_tpu/analysis/concur_check.py")
    effects_check = _load("sr_effects_check",
                          "starrocks_tpu/analysis/effects_check.py")
    boundary_check = _load("sr_boundary_check",
                           "starrocks_tpu/analysis/boundary_check.py")

    sources = astwalk.package_sources(REPO)
    crep = concur_check.check_sources(sources)
    erep = effects_check.check_sources(sources)
    bfindings = boundary_check.check_imports(
        boundary_check.load_manifest(REPO), sources)
    findings = {"concur": crep.findings, "effects": erep.findings,
                "boundary": bfindings}
    stats = {"concur": crep.stats, "effects": erep.stats}
    return findings, stats, len(sources)


def run(strict_warn: bool = False, as_json: bool = False) -> int:
    by_pass, stats, n_modules = collect()
    flat = [(p, f) for p in ("concur", "effects", "boundary")
            for f in by_pass[p]]
    errors = [f for _, f in flat if f.severity == "error"]
    warns = [f for _, f in flat if f.severity == "warn"]
    failed = bool(errors or (strict_warn and warns))

    if as_json:
        out = {
            "ok": not failed,
            "errors": len(errors),
            "warns": len(warns),
            "modules": n_modules,
            "suppressions": stats["effects"]["suppressions"],
            "suppressions_unexplained":
                stats["effects"]["suppressions_unexplained"],
            "findings": [
                {"pass": p, "severity": f.severity, "rule": f.rule,
                 "where": f.where, "message": f.message}
                for p, f in flat
            ],
            "stats": stats,
        }
        print(json.dumps(out, indent=1, sort_keys=True))
        return 1 if failed else 0

    for _, f in flat:
        print(f)
    cst, est = stats["concur"], stats["effects"]
    print(f"concur_lint: {len(errors)} error(s), {len(warns)} warn(s); "
          f"locks={cst['locks']} guarded_attrs={cst['guarded_attrs']} "
          f"order_edges={cst['edges']} "
          f"effect_fns={est['functions']} acquires={est['acquire_sites']} "
          f"suppressions={est['suppressions']} modules={n_modules}")
    return 1 if failed else 0


def main():
    ap = argparse.ArgumentParser(
        description="static lock-order + guarded-by + effect-contract + "
                    "module-boundary gate")
    ap.add_argument("--strict-warn", action="store_true",
                    help="fail on warn-level findings too (the coverage "
                         "ratchet, once annotations reach 100%%)")
    ap.add_argument("--json", action="store_true", dest="as_json",
                    help="machine-readable findings object on stdout")
    args = ap.parse_args()
    return run(strict_warn=args.strict_warn, as_json=args.as_json)


if __name__ == "__main__":
    sys.exit(main())

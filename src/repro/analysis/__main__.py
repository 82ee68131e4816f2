"""plan-lint CLI: ``python -m repro.analysis``.

Runs the three passes —

1. jaxpr contract lint over every registered cost surface,
2. recompile/memo-key audit (dynamic probe sweep over the available
   backends + static memo-key coverage of the backend sources),
3. AST host-sync lint over every ``@hot_path`` function in src/repro —

applies inline pragmas, prints the human report, optionally writes the
structured JSON, and exits non-zero when any *unallowed* finding reaches
the ``--fail-on`` threshold.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path
from typing import Dict, List

from repro.analysis.report import (Finding, apply_pragmas, render_report,
                                   severity_at_least, write_json)

_REPO_ROOT = Path(__file__).resolve().parents[3]


def collect(backends=None, skip_audit: bool = False):
    """Run all passes; returns (findings, compile-count table, hash)."""
    from repro.analysis import hotpath_lint, jaxpr_lint, recompile_audit

    findings: List[Finding] = []
    findings.extend(jaxpr_lint.lint_registered())

    table: Dict[str, Dict[str, int]] = {}
    thash = None
    if not skip_audit:
        table, audit_findings = recompile_audit.audit_backends(backends)
        findings.extend(audit_findings)
        thash = recompile_audit.table_hash(table)
    findings.extend(recompile_audit.audit_sources())

    findings.extend(hotpath_lint.lint_tree())

    # apply pragmas globally (idempotent for the hotpath pass, which
    # already applied its own): jaxpr/static findings are anchored to
    # real source lines too and may carry allow() pragmas
    sources: Dict[str, str] = {}
    for f in findings:
        if f.path not in sources:
            p = _REPO_ROOT / f.path
            if p.is_file():
                sources[f.path] = p.read_text()
    apply_pragmas(findings, sources)
    return findings, table, thash


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="plan-lint: certify the backend parity, dtype and "
                    "recompile contracts statically")
    ap.add_argument("--fail-on", choices=("info", "warn", "error", "never"),
                    default="warn",
                    help="lowest severity that fails the run (default warn)")
    ap.add_argument("--json", type=Path, default=None, metavar="PATH",
                    help="write the structured findings/summary JSON here")
    ap.add_argument("--backends", default=None,
                    help="comma-separated backend subset for the dynamic "
                         "recompile audit (default: all available)")
    ap.add_argument("--skip-audit", action="store_true",
                    help="skip the dynamic recompile probe sweep")
    args = ap.parse_args(argv)

    backends = args.backends.split(",") if args.backends else None
    findings, table, thash = collect(backends, skip_audit=args.skip_audit)

    print(render_report(findings, table or None, thash))
    if args.json is not None:
        write_json(args.json, findings, table or None, thash)

    if args.fail_on == "never":
        return 0
    bad = [f for f in findings
           if not f.allowed and severity_at_least(f.severity, args.fail_on)]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

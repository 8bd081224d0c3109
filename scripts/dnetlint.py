#!/usr/bin/env python
"""dnetlint: repo-native static analysis for async-safety, JIT purity,
and contract drift (dnet_tpu/analysis/).

Usage::

    python scripts/dnetlint.py                  # full run, exit 1 on findings
    python scripts/dnetlint.py --ast-only       # skip runtime metric passes
    python scripts/dnetlint.py --select DL006   # one check
    python scripts/dnetlint.py --diff HEAD      # only files changed vs HEAD
                                                # (pre-commit mode: AST-only,
                                                # exit 1 on new findings)
    python scripts/dnetlint.py --json           # also write ANALYSIS_r<NN>.json
    python scripts/dnetlint.py --json out.json  # ...to an explicit path
    python scripts/dnetlint.py --write-baseline # grandfather current findings
    python scripts/dnetlint.py --list-checks    # catalog

Inline suppression (reason mandatory)::

    something_flagged()  # dnetlint: disable=DL005 calibration probe: the sync IS the measurement

Baseline: ``.dnetlint-baseline`` at the repo root — grandfathered
fingerprints, one per line, each with a justification.  Stale entries
fail the run, so the file cannot rot.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))


def main(argv=None) -> int:
    from dnet_tpu.analysis import (
        ALL_CHECKS,
        DEFAULT_BASELINE,
        next_report_path,
        run_analysis,
        write_baseline,
        write_report_json,
    )
    from dnet_tpu.analysis.core import changed_files

    ap = argparse.ArgumentParser(
        prog="dnetlint", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--ast-only", action="store_true",
                    help="skip runtime passes (DL010+); pure-AST run")
    ap.add_argument("--select", default="",
                    help="comma-separated DL codes to run (default: all); "
                         "unknown codes are an error (exit 2)")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="lint only .py files changed vs REV (working tree "
                         "+ untracked, via git); implies --ast-only — the "
                         "fast pre-commit mode.  Cross-file checks still "
                         "see the whole tree, so diff findings agree with "
                         "a full run's for the same files")
    ap.add_argument("--baseline", default=None,
                    help=f"baseline path (default: {DEFAULT_BASELINE})")
    ap.add_argument("--write-baseline", action="store_true",
                    help="write current findings as the new baseline")
    ap.add_argument("--json", nargs="?", const="auto", default=None,
                    metavar="PATH",
                    help="write a JSON report (default path: next "
                         "ANALYSIS_r<NN>.json)")
    ap.add_argument("--list-checks", action="store_true")
    ap.add_argument("-q", "--quiet", action="store_true")
    args = ap.parse_args(argv)

    if args.list_checks:
        for c in ALL_CHECKS:
            kind = "runtime" if c.requires_runtime else "ast"
            print(f"{c.code}  {c.name:28s} [{kind:7s}] {c.description}")
        # the dsan catalog: detectors that only fire in a RUNNING process
        # (DNET_SAN=1); their findings merge into --json's runtime section
        from dnet_tpu.analysis.runtime import RUNTIME_CHECKS

        for code, name, description in RUNTIME_CHECKS:
            print(f"{code}  {name:28s} [dsan   ] {description}")
        return 0

    checks = ALL_CHECKS
    if args.select:
        wanted = {c.strip().upper() for c in args.select.split(",") if c.strip()}
        known = {c.code for c in ALL_CHECKS}
        unknown = sorted(wanted - known)
        if unknown:
            print(
                f"dnetlint: unknown check code(s) {', '.join(unknown)}; "
                f"known codes: {', '.join(sorted(known))}",
                file=sys.stderr,
            )
            return 2
        checks = [c for c in ALL_CHECKS if c.code in wanted]
    if args.ast_only or args.diff is not None:
        checks = [c for c in checks if not c.requires_runtime]
    if not checks:
        print(f"dnetlint: no checks left to run (--select {args.select!r}"
              f"{' with --ast-only' if args.ast_only else ''}) — refusing "
              f"a green no-op", file=sys.stderr)
        return 2

    if args.diff is not None and args.write_baseline:
        # a diff run sees only the changed files' findings (and no
        # runtime passes); writing that partial set would silently
        # truncate every other file's grandfathered entries
        print("dnetlint: --write-baseline needs a full run; drop --diff",
              file=sys.stderr)
        return 2

    only_files = None
    if args.diff is not None:
        only_files = changed_files(REPO, args.diff)
        if only_files is None:
            print(
                f"dnetlint: git diff vs {args.diff!r} failed; falling back "
                f"to a full run", file=sys.stderr,
            )
        elif not only_files:
            print(f"dnetlint: no .py changes vs {args.diff} — nothing to lint")
            return 0

    baseline_path = (
        Path(args.baseline) if args.baseline else REPO / DEFAULT_BASELINE
    )
    report = run_analysis(
        REPO,
        checks=checks,
        include_runtime=not (args.ast_only or args.diff is not None),
        baseline_path=baseline_path,
        ignore_baseline=args.write_baseline,
        only_files=only_files,
    )

    if args.write_baseline:
        write_baseline(baseline_path, report.findings)
        print(f"dnetlint: wrote {len(report.findings)} entries to "
              f"{baseline_path} — add a justification per line")
        return 0

    if not args.quiet:
        for f in report.findings:
            print(f.render())
    if args.json is not None:
        out = (
            next_report_path(REPO) if args.json == "auto" else Path(args.json)
        )
        # merge the runtime-sanitizer section: DS catalog + any findings a
        # DNET_SAN=1 run persisted (DNET_SAN_REPORT / .dsan-findings.json)
        from dnet_tpu.analysis.runtime import runtime_section

        write_report_json(report, out, extra={"runtime": runtime_section(REPO)})
        if not args.quiet:
            print(f"dnetlint: report written to {out}")
    scope = (
        f" ({len(only_files)} changed file(s) vs {args.diff})"
        if only_files is not None else ""
    )
    summary = (
        f"dnetlint: {len(report.findings)} finding(s) "
        f"({len(report.baselined)} baselined, {report.suppressed} "
        f"suppressed) over {report.files_scanned} files{scope}, "
        f"{len(report.checks_run)} checks"
    )
    print(summary)
    return 0 if report.clean else 1


if __name__ == "__main__":
    sys.exit(main())

"""dslint — static analysis for the Pallas/jit stack.

Runs the kernel contract checker (every registered ``pallas_call``
site, validated against TPU tiling/coverage/VMEM contracts without
compiling) and the jit-safety AST lint over the package, filters the
committed baseline, and exits nonzero on any NEW finding::

    python tools/dslint.py                      # lint the repo
    python tools/dslint.py --format json        # machine-readable
    python tools/dslint.py --write-baseline     # accept current debt
    python tools/dslint.py --skip-pallas path/  # AST rules only

Wired into tier-1 via ``tests/unit/test_analysis.py`` with the
committed ``.dslint_baseline.json``, so a new finding fails the suite
the same way a crash or hang now does.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_BASELINE = ".dslint_baseline.json"


def run(argv=None) -> int:
    from deepspeed_tpu.analysis.common import Baseline, repo_root

    ap = argparse.ArgumentParser(
        prog="dslint", description="Pallas kernel contract checker + "
                                   "jit-safety lint")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs for the AST pass "
                         "(default: deepspeed_tpu/)")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--baseline",
                    default=os.path.join(repo_root(), DEFAULT_BASELINE))
    ap.add_argument("--write-baseline", action="store_true",
                    help="record every current finding as accepted debt")
    ap.add_argument("--no-baseline", action="store_true",
                    help="report baselined findings too (and fail on them)")
    ap.add_argument("--skip-pallas", action="store_true",
                    help="skip the kernel contract checker")
    ap.add_argument("--skip-jit", action="store_true",
                    help="skip the jit-safety AST pass")
    ap.add_argument("--skip-metrics", action="store_true",
                    help="skip the metric-name registry cross-check")
    args = ap.parse_args(argv)

    findings = []
    paths = args.paths or [os.path.join(repo_root(), "deepspeed_tpu")]
    if not args.skip_jit:
        from deepspeed_tpu.analysis.jit_lint import run_jit_lint

        findings.extend(run_jit_lint(paths))
    if not args.skip_metrics:
        from deepspeed_tpu.analysis.metrics_lint import run_metrics_lint

        # default scope widens beyond the package: the tools also name
        # metrics, and a typo there misreads a real series
        mpaths = args.paths or [
            os.path.join(repo_root(), "deepspeed_tpu"),
            os.path.join(repo_root(), "tools"),
        ]
        findings.extend(run_metrics_lint(mpaths))
    if not args.skip_pallas:
        from deepspeed_tpu.analysis.pallas_lint import run_pallas_lint

        findings.extend(run_pallas_lint())

    findings.sort(key=lambda f: (f.path, f.line, f.rule))

    if args.write_baseline:
        Baseline.from_findings(findings).save(args.baseline)
        print(f"dslint: wrote {len(findings)} suppression(s) to "
              f"{args.baseline}")
        return 0

    baseline = Baseline() if args.no_baseline else Baseline.load(
        args.baseline)
    new, old = baseline.split(findings)

    if args.format == "json":
        print(json.dumps({
            "new": [f.to_dict() for f in new],
            "baselined": [f.to_dict() for f in old],
            "counts": {"new": len(new), "baselined": len(old)},
            "ok": not new,
        }, indent=2))
    else:
        for f in new:
            print(f.format())
        if old:
            print(f"dslint: {len(old)} baselined finding(s) suppressed "
                  f"({args.baseline})")
        print(f"dslint: {len(new)} new finding(s), "
              f"{len(old)} baselined")
    return 1 if new else 0


if __name__ == "__main__":
    sys.exit(run())

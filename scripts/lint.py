#!/usr/bin/env python
"""Contract linter driver.

Runs every rule in racon_tpu.analysis.rules.ALL_RULES over the repo
(racon_tpu/, scripts/), subtracts the baseline, and prints a
byte-stable report plus a ``lint_findings_total=...`` summary line.

    python scripts/lint.py              # report, exit 0 always
    python scripts/lint.py --ci         # exit 1 on non-baselined findings
    python scripts/lint.py --json       # machine-readable report
    python scripts/lint.py --baseline p # alternate baseline file

The baseline (.lint-baseline.json, a JSON list of
``rule:path:message`` fingerprints) grandfathers known findings; the
repo ships an empty one — new findings fail CI immediately.
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from racon_tpu.analysis import (ALL_RULES, Context, load_baseline,  # noqa: E402
                                render_json, render_text, run_rules,
                                split_findings, summary_line)


def main(argv=None) -> int:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ci", action="store_true",
                    help="exit 1 when non-baselined findings exist")
    ap.add_argument("--json", action="store_true",
                    help="emit the JSON report instead of text")
    ap.add_argument("--baseline",
                    default=os.path.join(repo, ".lint-baseline.json"),
                    help="baseline file (JSON list of fingerprints)")
    ap.add_argument("--root", default=repo,
                    help="repo root to lint (default: this repo)")
    args = ap.parse_args(argv)

    ctx = Context(args.root)
    findings = run_rules(ALL_RULES, ctx)
    active, suppressed = split_findings(
        findings, load_baseline(args.baseline))

    if args.json:
        sys.stdout.write(render_json(active, suppressed))
    else:
        sys.stdout.write(render_text(active, suppressed))
    print(summary_line(active, suppressed, len(ALL_RULES),
                       len(ctx.files)))

    if args.ci and active:
        print(f"[racon_tpu::lint] FAIL: {len(active)} non-baselined "
              f"finding(s); fix them or (exceptionally) add their "
              f"fingerprints to {os.path.basename(args.baseline)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

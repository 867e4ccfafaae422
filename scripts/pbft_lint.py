#!/usr/bin/env python
"""pbft_lint — run every static-analysis pass over core/ and pbft_tpu/.

One entry point for the conformance-and-lint layer (ISSUE 8,
pbft_tpu/analysis/): cross-runtime constant conformance, the
no-blocking-calls-in-async check, and the metrics/trace manifest lint
(the generalized successor of scripts/check_trace_schema.py, which now
delegates here).

    python scripts/pbft_lint.py               # all passes, repo tree
    python scripts/pbft_lint.py --passes constants,metrics
    python scripts/pbft_lint.py --root /tmp/shadow-tree   # tests use this

Exit codes: 0 clean, 1 findings, 2 usage error. Wired into tier-1 via
tests/test_lint.py — drift between the runtimes fails the build.
"""

from __future__ import annotations

import argparse
import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from pbft_tpu import analysis  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--root", type=pathlib.Path, default=analysis.REPO,
                    help="tree to lint (default: this repo)")
    ap.add_argument("--passes", default=None,
                    help=f"comma-separated subset of {sorted(analysis.PASSES)}")
    ap.add_argument("--list", action="store_true",
                    help="list available passes and exit")
    args = ap.parse_args()

    if args.list:
        for name in analysis.PASSES:
            print(name)
        return 0

    passes = None
    if args.passes:
        passes = [p.strip() for p in args.passes.split(",") if p.strip()]
    try:
        results = analysis.run_all(args.root.resolve(), passes)
    except ValueError as exc:
        print(exc, file=sys.stderr)
        return 2

    total = 0
    for name, errors in results.items():
        status = "ok" if not errors else f"{len(errors)} problem(s)"
        print(f"[pbft_lint] {name}: {status}")
        for e in errors:
            print(f"  {e}")
        total += len(errors)
    if total:
        print(f"[pbft_lint] FAILED: {total} problem(s) across "
              f"{sum(1 for e in results.values() if e)} pass(es)")
        return 1
    print("[pbft_lint] all passes clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())

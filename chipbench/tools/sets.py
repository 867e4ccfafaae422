#!/usr/bin/env python3
"""Run the real command several times and read the spread the way the
benchmark's bounds are set: sets of runs with the same seeds in each set.

    sets.py --workload W --seeds 11,12,13,14,15,16 --sets 2 --seconds 51 [--trace 0]

Every run is ``python3 chipbench/run.py ...`` as the driver starts it, a
new process each. Prints each result line, then for every metric each
set's median and spread (quartile distance over the median, and the same
without the run farthest from the median) and how far the second set's
median lies from the first's. Writes ``chiprun_out/sets/<tag>.json``; the
end of each run's standard error goes to ``chiprun_out/sets/<tag>.log``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import stats  # noqa: E402

OUT = ROOT / "chiprun_out" / "sets"


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--sets", type=int, default=2)
    p.add_argument("--seconds", type=int, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--tag", default="set")
    args = p.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    OUT.mkdir(parents=True, exist_ok=True)
    log_path = OUT / f"{args.workload}-{args.tag}.log"
    results = []
    with open(log_path, "w") as log:
        for k in range(args.sets):
            for seed in seeds:
                t = time.monotonic()
                proc = subprocess.run(
                    [sys.executable, "chipbench/run.py", "--workload", args.workload,
                     "--seed", str(seed), "--seconds", str(args.seconds),
                     "--trace", str(args.trace)],
                    capture_output=True, text=True, cwd=ROOT,
                )
                wall = time.monotonic() - t
                lines = proc.stdout.strip().splitlines()
                line = json.loads(lines[-1]) if lines and proc.returncode == 0 else None
                log.write(f"=== set {k} seed {seed} exit {proc.returncode} wall {wall:.1f}s\n")
                log.write(proc.stderr[-6000:] + "\n")
                log.flush()
                results.append({"set": k, "seed": seed, "exit": proc.returncode,
                                "wall_s": wall, "line": line})
                print(f"set {k} seed {seed} exit {proc.returncode} wall {wall:.1f}s: "
                      f"{json.dumps(line)}", flush=True)
                if line is None:
                    print(proc.stderr[-3000:], flush=True)
    (OUT / f"{args.workload}-{args.tag}.json").write_text(json.dumps(results))
    good = [r for r in results if r["line"] is not None]
    print(f"{len(good)} of {len(results)} runs gave a result; correct in "
          f"{sum(r['line']['correct'] for r in good)}", flush=True)
    names = sorted({n for r in good for n in r["line"]["metrics"]})
    for name in names:
        meds = []
        for k in range(args.sets):
            vals = [r["line"]["metrics"][name]["value"] for r in good
                    if r["set"] == k and name in r["line"]["metrics"]]
            if len(vals) < 3:
                continue
            meds.append(statistics.median(vals))
            print(f"{name} set {k}: median {meds[-1]:.4f} min {min(vals):.4f} max {max(vals):.4f} "
                  f"spread {100 * stats.iqr_spread(vals):.2f}% "
                  f"without-farthest {100 * stats.trimmed_iqr_spread(vals):.2f}% "
                  f"values {[round(v, 3) for v in vals]}", flush=True)
        if len(meds) == 2:
            print(f"{name}: second median {100 * (meds[1] / meds[0] - 1):+.2f}% of the first",
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

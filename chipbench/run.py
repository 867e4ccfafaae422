#!/usr/bin/env python3
"""Run one cell of the benchmark once and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and
``breakdown`` with ``--trace 1``). With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics. No
result is printed, and the exit code is not 0, when there is no TPU, when
a child dies, when any verification fell back to the host, or when a
child is left alive. See ``harness.py`` for what a run does.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args()
    import harness

    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START
        )
    except harness.BenchFailure as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr, flush=True)
        return 1
    line.pop("_run")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

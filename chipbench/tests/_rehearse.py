"""The whole of one run on the CPU at a tiny size, for the tests: the
harness's look for a chip is skipped and ``tools/verifyd_control.py
--stub-engine`` answers in the engine's place. Prints the result line like ``run.py``.

    python3 _rehearse.py WORKLOAD SECONDS TRACE [--benchmark F --root D] [wrapper flags..]
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("workload")
    parser.add_argument("seconds", type=float)
    parser.add_argument("trace", type=int)
    parser.add_argument("--seed", type=int, default=2147483659)
    parser.add_argument("--benchmark", type=Path, default=None)
    parser.add_argument("--root", type=Path, default=None)
    args, wrapper_flags = parser.parse_known_args()
    import harness

    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace), t_start=T_START,
            require_tpu=False,
            verifyd_wrapper=[str(harness.HERE / "tools" / "verifyd_control.py"),
                             "--stub-engine", *wrapper_flags],
            benchmark=args.benchmark, root=args.root,
        )
    except harness.BenchFailure as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr, flush=True)
        return 1
    line.pop("_run")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of a benchmark cell on the CPU at a tiny size, for
``test_f1_mac_rehearsal.py`` (after ``chipbench/tests/_rehearse.py``): the
harness's look for a chip is skipped and this file, started a second time as
``verifyd``, serves an engine double: the benchmark's stub engine (the
host's native verifier, no kernel compiled) that also times itself into the
launch's span as the sharded engine does, so that the readers of the engine's
spans have something to read.

    python3 _f1_mac_rehearse.py run WORKLOAD SECONDS TRACE [WORK]   prints the result line
    python3 _f1_mac_rehearse.py verifyd --control-fifo F ...        what the harness starts

``WORK`` names a work directory of the run's own (``.chipbench_work_<WORK>``),
so that two test files may rehearse at once.
"""

import time

T_START = time.monotonic()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent.parent / "chipbench"
sys.path[:0] = [str(BENCH), str(BENCH / "tools")]


def serve(argv: list) -> None:
    import verifyd_control
    import verifyd_wrap

    from pbft_tpu.net.verify_service import ShardedVerifyEngine
    from pbft_tpu.utils.trace import current_span

    class SpannedStub(verifyd_control.stub(ShardedVerifyEngine)):
        def verify(self, items):
            t_dev = time.monotonic()
            verdicts = super().verify(items)
            span = current_span()
            if span is not None:
                span.update(t_dev=round(t_dev, 6), pad_s=0.0, put_s=0.0, dispatch_s=0.0,
                            wait_s=round(time.monotonic() - t_dev, 6), unpack_s=0.0)
            return verdicts

    verifyd_wrap.main(argv, engine=verifyd_wrap.traced(SpannedStub))


def run(workload: str, seconds: str, trace: str, work: str = "") -> int:
    import harness

    if work:
        harness.WORK = harness.ROOT / f".chipbench_work_{work}"
    try:
        line = harness.run_cell(
            workload, 3200000033, float(seconds), bool(int(trace)), t_start=T_START,
            require_tpu=False, verifyd_wrapper=[__file__, "verifyd"],
        )
    except harness.BenchFailure as e:
        print(f"[chipbench] no result: {e}", file=sys.stderr, flush=True)
        return 1
    status = line.pop("_run")["final"]["status"]
    line["replicas"] = [{k: d[k] for k in ("mode", "mac_rejected", "tentative_rollbacks",
                                            "executed_upto", "committed_upto", "net_threads")}
                        for d in status]
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1] == "verifyd":
        serve(sys.argv[2:])
    else:
        sys.exit(run(*sys.argv[2:6]))

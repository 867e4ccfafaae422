"""The benchmark cell ``f1-mac-tentative.closed`` rehearsed on the CPU
through ``chipbench`` itself (ISSUE 32): the cell's own configuration,
traffic and metric files, a window of a few seconds, an engine double behind
the real ``verifyd`` entry (``_f1_mac_rehearse.py``). The chip's numbers come
from the chip; what is held here is that the harness takes the cell by its
data files alone, that the run comes out ``correct``, and that every
per-layer reader the cell is listed under finds its span or counter.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parent
CELL = "f1-mac-tentative.closed"


def _rehearse(seconds: int, trace: int):
    proc = subprocess.run(
        [sys.executable, str(TESTS / "_f1_mac_rehearse.py"), "run", CELL, str(seconds), str(trace)],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_cell_is_taken_by_its_data_files_and_comes_out_correct():
    line, err = _rehearse(3, 0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"commit_rate", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # Every comparison of `correct` stands beside its limit, all inside it.
    compared = [ln for ln in err.splitlines() if "compare " in ln]
    assert len(compared) == 13 and not any("NOT OK" in ln for ln in compared)
    assert "compare engine_items_minus_items_sent: 0" in err
    # Four replicas in MAC mode, nothing undone, every execution committed.
    assert len(line["replicas"]) == 4
    for d in line["replicas"]:
        assert d["mode"] == "mac" and d["mac_rejected"] == 0 and d["tentative_rollbacks"] == 0
        assert d["executed_upto"] == d["committed_upto"] > 0
    cell = next(c for c in _bench()["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("f1-mac-tentative", "closed-1024-p100", 1)


def test_a_traced_rehearsal_reports_every_per_layer_metric_of_the_cell():
    line, _ = _rehearse(4, 1)
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    # On the CPU there is no device plane: the readers of the device trace
    # find nothing and are left out; every other reader reports a number.
    from_trace = {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert from_trace & listed == {"kernel_ms_per_launch.closed", "device_idle_pct.closed"}
    # (nor does the benchmark's stub engine write the sharded engine's `fused`)
    assert set(line["metrics"]) == listed - from_trace - {"fused_launch_share.closed"}
    assert len(listed) == 29
    # No verify trip on a reply's path, so no launch to make ahead: the cell
    # is not listed under ISSUE 37's readers nor under the apply's clock, and
    # its line carries none of them.
    assert not [k for k in line["metrics"]
                if k.startswith(("launched_ahead", "verdict_held", "verdict_apply"))]
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(isinstance(v, (int, float)) for v in value.values())
    # The primary's loop by kind of work (ISSUE 38): it waits part of the
    # time, every stage but the verify inbox costs a request something, and
    # every reply is signed.
    assert 0 < value["loop_wait_share.closed"] < 1
    for stage in ("read", "protocol", "wal", "send", "other"):
        assert value[f"loop_{stage}_us_per_req.closed"] > 0, stage
    assert value["loop_verify_us_per_req.closed"] < 1.0
    assert value["signs_per_req.closed"] >= 1.0
    # A sequence number's replies leave in one send() (ISSUE 41): a system
    # call carries several frames, where it carried one.
    assert value["frames_per_send.closed"] > 2.0
    # The mode, as numbers: no item sent for verification, every execution at
    # PREPARED, none undone, MAC frames on the wire, the probe alone in a launch.
    assert value["sig_checks_per_req.closed"] == 0 and value["tentative_rollbacks.closed"] == 0
    assert value["tentative_share.closed"] > 0.9 and value["mac_frames_per_req.closed"] > 0
    assert value["items_per_launch.closed"] == 16 and value["seal_refused_per_req.closed"] >= 0
    assert value["request_wait_ms_mean.closed"] > 0 and value["commit_lag_ms_mean.closed"] > 0
    assert value["inline_verifies_per_req.closed"] > 0 and value["engine_idle_pct.closed"] > 50

"""The benchmark cell ``f5-sig-wal-x4.closed`` rehearsed on the CPU through
``chipbench`` itself (ISSUE 36): the cell's own configuration, traffic and
metric files, a window of a few seconds, and behind the real ``verifyd``
entry the REAL ``ShardedVerifyEngine`` over four virtual devices with a
stand-in for the arithmetic (``_f5_x4_rehearse.py``). The chips' numbers come
from the chips; what is held here is that the harness takes the cell by its
data files alone, that one verify service sharded four ways serves sixteen
replicas to a ``correct`` result, that every launch line says over how many
chips it ran and how many rows a chip that was, and that every per-layer
reader the cell is listed under finds its span or counter.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parent
CELL = "f5-sig-wal-x4.closed"
LADDER = (16, 64, 256, 1024, 4096)

sys.path.insert(0, str(ROOT / "chipbench"))


def _rehearse(seconds: int, trace: int):
    # A cluster of sixteen takes 33 ports it found free a moment before; one
    # lost to another test's process is tried again, nothing else is.
    for attempt in (1, 2):
        proc = subprocess.run(
            [sys.executable, str(TESTS / "_f5_x4_rehearse.py"), "run", CELL, str(seconds), str(trace)],
            capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
            timeout=240,
        )
        if proc.returncode == 0 or "bind failed" not in proc.stderr + _replica_logs():
            break
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _replica_logs() -> str:
    failed = ROOT / "chiprun_out" / "chipbench" / "failed"
    return "".join(p.read_text(errors="replace")[-500:] for p in failed.glob("pbftd-*/replica-*.log"))


def _bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_one_verifyd_over_four_devices_serves_sixteen_replicas_to_a_correct_result():
    import xplane

    line, err = _rehearse(3, 0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"commit_rate", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    # Every comparison of `correct` stands beside its limit, all inside it.
    compared = [ln for ln in err.splitlines() if "compare " in ln]
    assert len(compared) == 13 and not any("NOT OK" in ln for ln in compared)
    assert len(line["compared"]) == 13
    assert "compare engine_items_minus_items_sent: 0" in err
    # Sixteen replicas, one executed count, one chain digest.
    assert len(line["replicas"]) == 16
    assert len({(d["executed"], d["chain_digest"]) for d in line["replicas"]}) == 1
    assert line["replicas"][0]["executed"] > 0
    # The mesh came up over all four devices by the default alone, every shape
    # of the ladder a quarter of its rows a device ...
    served = line["verifyd"]
    assert served["devices"] == 4 and served["warmed_shapes"] == list(LADDER)
    assert [(p["size"], p["devices"], p["rows_per_device"]) for p in served["per_shape"]] == [
        (size, [0, 1, 2, 3], size // 4) for size in LADDER
    ]
    # ... and every launch line says so: four chips, and the rows a chip of
    # the thinnest executable the window ran (its `chunks` shapes sum to `rung`).
    assert len(line["launches"]) > 20
    for e in line["launches"]:
        shapes = xplane.shapes_run(e, LADDER)
        assert shapes and e["devices"] == 4 and e["rows_per_chip"] == min(shapes) // 4, e
    by_rows = served["launches_by_rows_per_chip"]
    assert set(by_rows) <= {str(size // 4) for size in LADDER}
    assert sum(by_rows.values()) == sum(served["launches_by_rung"].values()) >= len(line["launches"])
    cell = next(c for c in _bench()["workloads"] if c["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("f5-sig-wal-x4", "closed-256", 4)


def test_a_traced_rehearsal_reports_every_per_layer_metric_of_the_cell():
    line, _ = _rehearse(4, 1)
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    # On the CPU there is no device plane: the three readers of the device
    # trace find nothing and are left out; every other reader reports a number.
    from_trace = {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert from_trace & listed == {
        "kernel_ms_per_launch.closed", "verify_kernel_roofline.closed", "device_idle_pct.closed",
    }
    assert set(line["metrics"]) == listed - from_trace and len(listed) == 46
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(isinstance(v, (int, float)) for v in value.values())
    # The reading that says the cell ran over four chips, and the one that
    # explains its cost table; both from the launch lines of the window.
    assert value["mesh_chips.closed"] == 4.0
    assert value["fused_launch_share.closed"] == 0.0  # a stand-in kernel has no VMEM chains
    rows = [e["rows_per_chip"] for e in line["launches"]]
    assert value["rows_per_chip_mean.closed"] == pytest.approx(sum(rows) / len(rows), rel=1e-12)
    assert 4 <= value["rows_per_chip_mean.closed"] <= 1024
    # The twin on one chip is listed under the same two readers, and the
    # cell under everything its twin reports.
    twin = {m["name"] for m in bench["per_layer"] if "f5-sig-wal.closed" in m["workloads"]}
    # (but for the four readers PR 42 brought for the two one-chip f=5 / f=10 cells)
    assert twin - listed == {"pending_at_cut_mean.closed", "full_window_share.closed",
                             "gateway_cpu_share.closed", "verifyd_cpu_share.closed"}
    assert listed <= twin
    assert value["pad_fill.closed"] == pytest.approx(
        value["items_per_launch.closed"] / value["rung_slots_mean.closed"], rel=1e-12)
    assert value["engine_idle_pct.closed"] >= 0 and value["fsyncs_per_req.closed"] > 0
    # Sixteen replicas under 256 outstanding requests launch ahead of the
    # verdicts they keep, and keeping them costs a batch milliseconds at most.
    assert 0 < value["launched_ahead_share.closed"] <= 1
    assert 0 <= value["verdict_held_ms_mean.closed"] < 1000
    # The primary's loop by kind of work, the span that closes the verify
    # cycle and the signatures a request costs (ISSUE 38): numbers, not None.
    assert 0 < value["loop_wait_share.closed"] < 1
    for stage in ("read", "protocol", "wal", "send", "verify", "other"):
        assert value[f"loop_{stage}_us_per_req.closed"] > 0, stage
    assert 0 < value["verdict_apply_ms_mean.closed"] < 1000
    assert value["signs_per_req.closed"] >= 1.0

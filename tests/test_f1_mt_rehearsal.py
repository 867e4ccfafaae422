"""The cell of ISSUE 40 rehearsed on the CPU through ``chipbench`` itself, after
``test_f1_mac_rehearsal.py``: ``f1-sig-wal-mt.closed``, the multi-core replica
(``pbftd --net-threads 2``) under the closed loop of 1,024: the cell's own
configuration, traffic and metric files, a window of a few seconds, an engine
double behind the real ``verifyd`` entry (``_f1_mac_rehearse.py``). The
chip's numbers come from the chip; what is held here is that the harness
takes the cell by its data files alone, that the run comes out ``correct``
with nothing failed, and that every per-layer reader the cell is listed under
finds its span or counter. Up to PR 39 it ran to a result line with
``correct: false`` and no request due in its window: the sharded front end
stopped after one or two sequence numbers (``core/net_shard.cc``
``WakeFd::drain``). (ISSUE 40's second cell, ``f5-sig-wal.rate``, was measured
on the chip and left out: ``PERF.md`` section 7.)
"""

import json
import os
import subprocess
import sys
from pathlib import Path

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parent
MT, MT_TWIN = "f1-sig-wal-mt.closed", "f1-sig-wal.closed"
SHARD_TIER = {
    "net_threads_seen.closed", "shard_busy_share.closed", "pipe_busy_share.closed",
    "shard_read_us_per_req.closed", "shard_send_us_per_req.closed",
    "pipe_decode_us_per_req.closed", "pipe_encode_us_per_req.closed",
    "handoff_ms_mean.closed", "cross_wakes_per_req.closed", "shard_dropped.closed",
}


def _rehearse(cell: str, seconds: int, trace: int):
    proc = subprocess.run(
        [sys.executable, str(TESTS / "_f1_mac_rehearse.py"), "run", cell, str(seconds), str(trace), "mt"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_the_cell_is_taken_by_its_data_files_and_comes_out_correct():
    cell, metric, threads, replicas = MT, "commit_rate", 2, 4
    line, err = _rehearse(cell, 4, 0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 100
    assert set(line["metrics"]) == {metric, "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = [ln for ln in err.splitlines() if "compare " in ln]
    assert len(compared) == 13 and not any("NOT OK" in ln for ln in compared)
    assert "compare votes_missing_from_a_wal: 0" in err
    assert "compare views_above_zero: 0" in err
    # Every replica ran the front end the configuration names, in signature
    # mode, and all of them ended on one sequence number.
    assert len(line["replicas"]) == replicas
    assert {d["net_threads"] for d in line["replicas"]} == {threads}
    assert {d["mode"] for d in line["replicas"]} == {"sig"}
    assert len({d["executed_upto"] for d in line["replicas"]}) == 1
    assert line["replicas"][0]["executed_upto"] > 10


def test_the_multicore_configuration_is_its_twin_but_for_the_threads():
    bench = _bench()
    cell = next(c for c in bench["workloads"] if c["name"] == MT)
    twin = next(c for c in bench["workloads"] if c["name"] == MT_TWIN)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("f1-sig-wal-mt", twin["traffic"], 1)
    mine = json.loads((ROOT / "chipbench" / "configs" / "f1-sig-wal-mt.json").read_text())
    base = json.loads((ROOT / "chipbench" / "configs" / "f1-sig-wal.json").read_text())
    assert list(mine) == list(base)
    assert {k for k in base if mine[k] != base[k]} == {
        "name", "source", "deployment", "cluster", "assumed", "reduced"}
    assert dict(mine["cluster"], net_threads=1) == base["cluster"] and mine["cluster"]["net_threads"] == 2
    assert mine["guarantees"] == base["guarantees"] and mine["limits"] == base["limits"]
    assert list(mine["reduced"]) == ["replica_hosts"]
    entry = next(c for c in bench["configs"] if c["name"] == "f1-sig-wal-mt")
    assert entry["reduced"] == ["replica_hosts"] and len(entry["source"]) <= 200


def test_a_traced_rehearsal_of_the_multicore_cell_reports_every_per_layer_metric():
    line, _ = _rehearse(MT, 4, 1)
    assert line["correct"] is True and line["failed"] == 0
    bench = _bench()
    listed = {m["name"] for m in bench["per_layer"] if MT in m["workloads"]}
    twin = {m["name"] for m in bench["per_layer"] if MT_TWIN in m["workloads"]}
    # Whatever the one-thread twin reports, and the ten readers of what the
    # deployment adds, which list this cell alone.
    assert listed == twin | SHARD_TIER and not twin & SHARD_TIER
    assert all(m["workloads"] == [MT] and m["moves"] == "commit_rate"
               for m in bench["per_layer"] if m["name"] in SHARD_TIER)
    from_trace = {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    # No device plane on the CPU: those readers find nothing and are left
    # out; every other reader reports a number (but the reader of `fused`,
    # which the benchmark's stub engine does not write).
    assert set(line["metrics"]) == listed - from_trace - {"fused_launch_share.closed"}
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(isinstance(v, (int, float)) for v in value.values())
    # The deployment, as numbers: two shards a replica on every replica,
    # nothing lost at a thread boundary, both kinds of thread at work and
    # asleep part of the time, every stage costing a request something.
    assert value["net_threads_seen.closed"] == 2 and value["shard_dropped.closed"] == 0
    assert 0 < value["shard_busy_share.closed"] < 1 and 0 < value["pipe_busy_share.closed"] < 1
    for name in ("shard_read_us_per_req", "shard_send_us_per_req", "pipe_decode_us_per_req",
                 "pipe_encode_us_per_req", "cross_wakes_per_req", "handoff_ms_mean"):
        assert value[f"{name}.closed"] > 0, name
    assert value["handoff_ms_mean.closed"] < 1000
    # The consensus thread keeps its seven stages; the socket work has left
    # it: what a request costs it in `read` and `send` is the queue
    # hand-offs, less than what the shards and pipelines now spend on it.
    assert 0 < value["loop_wait_share.closed"] < 1
    assert value["loop_protocol_us_per_req.closed"] > 0 and value["loop_wal_us_per_req.closed"] > 0
    front = sum(value[f"{n}.closed"] for n in (
        "shard_read_us_per_req", "shard_send_us_per_req", "pipe_decode_us_per_req", "pipe_encode_us_per_req"))
    assert value["loop_read_us_per_req.closed"] + value["loop_send_us_per_req.closed"] < front
    assert value["launched_ahead_share.closed"] > 0 and value["signs_per_req.closed"] >= 1.0

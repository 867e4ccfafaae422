"""The cell of ISSUE 42 rehearsed on the CPU through ``chipbench`` itself, after
``test_f1_mt_rehearsal.py``: ``f10-sig-wal.closed``, 31 ``pbftd`` (f=10) in
signature mode behind one gateway and ONE ``verifyd`` under the closed loop of
1,024: the cell's own configuration, traffic and metric files, a window of a
few seconds, an engine double behind the real ``verifyd`` entry
(``_f1_mac_rehearse.py``). The chip's numbers come from the chip; what is held
here is that the harness takes the cell by its data files alone, that the run
comes out ``correct`` with nothing failed, and that every per-layer reader the
cell is listed under finds its span or counter: the four this deployment
brought among them, in this cell and in its sibling ``f5-sig-wal.closed``.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from tests.test_verify_spans import _read

TESTS = Path(__file__).resolve().parent
ROOT = TESTS.parent
CELL, TWIN = "f10-sig-wal.closed", "f5-sig-wal.closed"
BROUGHT = {
    "pending_at_cut_mean.closed": ("items", "program_span", "verifyd dispatcher"),
    "full_window_share.closed": ("ratio", "program_span", "verifyd dispatcher"),
    "gateway_cpu_share.closed": ("cores", "host_clock", "gateway"),
    "verifyd_cpu_share.closed": ("cores", "host_clock", "verifyd dispatcher"),
}
# Fields that only the sharded engine writes into a launch's span (the double
# here is the benchmark's stub); ``test_f5_x4_rehearsal.py`` holds their
# readers with the real engine.
ENGINE_ONLY = {"mesh_chips.closed", "rows_per_chip_mean.closed", "fused_launch_share.closed"}


def _rehearse(cell: str, seconds: int, trace: int):
    proc = subprocess.run(
        [sys.executable, str(TESTS / "_f1_mac_rehearse.py"), "run", cell, str(seconds), str(trace), "f10"],
        capture_output=True, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=ROOT,
        timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def _bench() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("metric, child", [("gateway_cpu_share.closed", "gateway"),
                                           ("verifyd_cpu_share.closed", "verifyd")])
def test_a_childs_cpu_share_is_its_cpu_seconds_over_the_windows_length(metric, child):
    spec = json.loads((ROOT / "chipbench" / "metrics" / f"{metric}.json").read_text())
    assert spec == {"name": metric, "reducer": "child_cpu_share", "args": {"child": child}}
    cpu = {"gateway": 25.5, "verifyd": 61.2, "pbftd-0": 12.0, "loadgen-0": 30.0}
    want = {"gateway": 0.5, "verifyd": 1.2}[child]  # several threads: above one core
    assert _read(metric, {"cpu_window": cpu, "seconds": 51.0}) == pytest.approx(want, rel=1e-12)
    # A run that kept no such reading, a child that had ended by the closing
    # edge (cpu_seconds gives NaN then): nothing, and no error.
    assert _read(metric, {"seconds": 51.0}) is None
    assert _read(metric, {"cpu_window": {"pbftd-0": 12.0}, "seconds": 51.0}) is None
    assert _read(metric, {"cpu_window": {child: float("nan")}, "seconds": 51.0}) is None


def test_the_two_readers_of_the_cut_read_the_launch_lines_and_nothing_on_the_parent():
    for metric, field in (("pending_at_cut_mean.closed", "pending_at_cut"),
                          ("full_window_share.closed", "cut_full")):
        spec = json.loads((ROOT / "chipbench" / "metrics" / f"{metric}.json").read_text())
        assert spec == {"name": metric, "reducer": "launch_field_stat",
                        "args": {"fields": [field], "stat": "mean"}}
    lines = [{"size": 4090, "pending_at_cut": 9000, "cut_full": 1},
             {"size": 4001, "pending_at_cut": 3000, "cut_full": 1},
             {"size": 1200, "pending_at_cut": 0, "cut_full": 0},
             {"size": 16, "pending_at_cut": 0, "cut_full": 0}]
    assert _read("pending_at_cut_mean.closed", {"launches": lines}) == 3000.0
    assert _read("full_window_share.closed", {"launches": lines}) == 0.5
    # The parent commit's launch lines carry pending_at_cut and no cut_full:
    # the new field's reader finds nothing there and does not raise.
    parent = [{k: v for k, v in e.items() if k != "cut_full"} for e in lines]
    assert _read("pending_at_cut_mean.closed", {"launches": parent}) == 3000.0
    assert _read("full_window_share.closed", {"launches": parent}) is None
    assert _read("full_window_share.closed", {"launches": []}) is None


def test_the_cell_is_taken_by_its_data_files_and_comes_out_correct():
    line, err = _rehearse(CELL, 4, 0)
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 100
    assert set(line["metrics"]) == {"commit_rate", "setup_s"}
    assert all(v["value"] > 0 for v in line["metrics"].values())
    compared = [ln for ln in err.splitlines() if "compare " in ln]
    assert len(compared) == 13 and not any("NOT OK" in ln for ln in compared)
    assert "compare votes_missing_from_a_wal: 0" in err
    assert "compare views_above_zero: 0" in err
    assert "compare distinct_chain_digests: 1" in err
    assert "cluster of 31 up" in err
    # Thirty-one replicas in signature mode on the one-thread loop, all of
    # them ended on one sequence number.
    assert len(line["replicas"]) == 31
    assert {(d["mode"], d["net_threads"]) for d in line["replicas"]} == {("sig", 1)}
    assert len({d["executed_upto"] for d in line["replicas"]}) == 1
    assert line["replicas"][0]["executed_upto"] > 10


def test_the_configuration_is_f5_sig_wal_key_for_key_at_31_replicas():
    bench = _bench()
    cell = next(c for c in bench["workloads"] if c["name"] == CELL)
    f1 = next(c for c in bench["workloads"] if c["name"] == "f1-sig-wal.closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("f10-sig-wal", f1["traffic"], 1)
    assert cell["traffic"] == "closed-1024" and bench["workloads"][-1] == cell
    assert sum(c["chips"] == 4 for c in bench["workloads"]) == 1 and len(bench["workloads"]) == 7
    mine = json.loads((ROOT / "chipbench" / "configs" / "f10-sig-wal.json").read_text())
    base = json.loads((ROOT / "chipbench" / "configs" / "f5-sig-wal.json").read_text())
    assert list(mine) == list(base)
    assert {k for k in base if mine[k] != base[k]} == {
        "name", "source", "deployment", "cluster", "guarantees", "assumed", "reduced", "limits"}
    # n, f and the batcher's flush window (20 ms where the smaller clusters
    # run 2: at saturation the 2 ms window leaves the batch size loose and
    # the cell's rate with it, PERF.md section 5); every other cluster key is
    # f5-sig-wal's.
    assert dict(mine["cluster"], n=16, f=5, batch_flush_us=2000) == base["cluster"]
    assert (mine["cluster"]["n"], mine["cluster"]["f"]) == (31, 10) == (3 * 10 + 1, 10)
    assert mine["cluster"]["batch_flush_us"] == 20000 and "batch_flush_us" in mine["assumed"]
    assert mine["limits"]["wal_fsyncs_per_verify_batch_min"] == base["limits"]["wal_fsyncs_per_verify_batch_min"] == 0.5
    assert mine["verifyd"] == base["verifyd"] and mine["ladder"] == base["ladder"] == [16, 64, 256, 1024, 4096]
    assert mine["operation"] == base["operation"] and mine["links"] == base["links"]
    # The five guarantees, restated for 31 replicas and letter for letter otherwise.
    restated = [g.replace("f+1 = 6", "f+1 = 11").replace("the 16 replicas", "the 31 replicas")
                .replace("over all 16", "over all 31").replace("up to 31 a sequence", "up to 61 a sequence")
                for g in base["guarantees"]]
    assert mine["guarantees"] == restated and len(restated) == 5
    assert list(mine["reduced"]) == ["replica_hosts"] and set(base["assumed"]) < set(mine["assumed"])
    entry = next(c for c in bench["configs"] if c["name"] == "f10-sig-wal")
    assert entry["reduced"] == ["replica_hosts"] and len(entry["source"]) <= 200
    assert entry["file"] == "chipbench/configs/f10-sig-wal.json" and bench["configs"][-1] == entry


def test_the_cell_is_listed_wherever_its_sibling_is_and_brings_four_readers():
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(next(iter(BROUGHT)))  # appended by PR 42, as four; PR 43's two stand behind
    assert names[at : at + 4] == list(BROUGHT) and at == 89
    for m in bench["per_layer"][at : at + 4]:
        unit, source, layer = BROUGHT[m["name"]]
        assert m == {"name": m["name"], "unit": unit, "better": "lower", "source": source,
                     "layer": layer, "moves": "commit_rate", "workloads": [TWIN, CELL]}
        assert layer in {x["layer"] for x in bench["per_layer"][:at]}  # a layer the benchmark names
        spec = json.loads((ROOT / "chipbench" / "metrics" / f"{m['name']}.json").read_text())
        assert (ROOT / "chipbench" / "reducers" / f"{spec['reducer']}.py").is_file()
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if m["name"].endswith(".closed") or m["name"] == "commit_rate":
            assert (CELL in cells) == (TWIN in cells), m["name"]
            assert CELL not in cells or cells[-1] == CELL  # appended, nothing else moved
        else:
            assert CELL not in cells, m["name"]


@pytest.mark.parametrize("cell, replicas", [(CELL, 31), (TWIN, 16)])
def test_a_traced_rehearsal_reports_every_per_layer_metric_the_cell_is_listed_under(cell, replicas):
    line, err = _rehearse(cell, 4, 1)
    assert line["correct"] is True and line["failed"] == 0 and len(line["replicas"]) == replicas
    bench = _bench()
    listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    from_trace = {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    # No device plane on the CPU: those readers find nothing and are left
    # out; every other reader reports a number.
    assert set(line["metrics"]) == listed - from_trace - ENGINE_ONLY
    assert set(BROUGHT) <= set(line["metrics"])
    value = {k: v["value"] for k, v in line["metrics"].items()}
    assert all(isinstance(v, (int, float)) for v in value.values())
    # The two children's shares of a core, from the harness's own reading of
    # /proc at the window's edges (the double verifies on the host's pool, so
    # verifyd's is no chip-side number).
    assert sum("cpu seconds in the window" in ln and "'gateway':" in ln for ln in err.splitlines()) == 1
    assert 0 < value["gateway_cpu_share.closed"] < 2 and value["verifyd_cpu_share.closed"] > 0
    assert 0 <= value["full_window_share.closed"] <= 1 and value["pending_at_cut_mean.closed"] >= 0
    assert (value["full_window_share.closed"] > 0) == (value["pending_at_cut_mean.closed"] > 0)
    if cell == CELL:
        # 31 replicas' batches behind one engine: windows are cut at the
        # largest window with requests left queued, and run at 4,096 slots.
        assert value["full_window_share.closed"] > 0 and value["pending_at_cut_mean.closed"] > 0
        assert value["window_items_max.closed"] > 2048 and value["rung_slots_mean.closed"] > 1024
        assert value["requests_per_launch.closed"] > 1
        assert value["launched_ahead_share.closed"] > 0 and value["signs_per_req.closed"] > 0.9
        assert 0 < value["loop_wait_share.closed"] < 1 and value["frames_per_send.closed"] > 1

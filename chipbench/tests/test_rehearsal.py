"""CPU rehearsals of the whole command at a tiny size (no chip, a stub
engine behind the real verifyd entry), and the controls of ``correct``.

Run with ``JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests -q``. Each
rehearsal starts a real cluster, gateway and generator for a few seconds.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
ROOT = BENCH_DIR.parent
ENV = dict(os.environ, JAX_PLATFORMS="cpu")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def rehearse(*args, timeout=240):
    proc = subprocess.run(
        [sys.executable, str(TESTS / "_rehearse.py"), *map(str, args)],
        capture_output=True, text=True, env=ENV, cwd=ROOT, timeout=timeout,
    )
    lines = proc.stdout.strip().splitlines()
    return proc, (json.loads(lines[-1]) if lines else None)


def bench_entries():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [c["name"] for c in bench_entries()["workloads"]])
def test_result_line_has_exactly_the_contracts_keys(cell):
    proc, line = rehearse(cell, 3, 0)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line) == KEYS
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    bench = bench_entries()
    want = {m["name"] for m in bench["end_to_end"] if cell in m.get("workloads", [cell])}
    assert set(line["metrics"]) == want and "setup_s" in want and len(want) >= 2
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # every number compared stands beside its limit at the end of stderr,
    # and under the line's last key
    tail = proc.stderr.strip().splitlines()[-13:]
    assert all("compare " in ln and "(limit " in ln for ln in tail), tail
    assert list(line)[-1] == "compared" and len(line["compared"]) == 13
    assert all(set(c) == {"value", "limit"} for c in line["compared"].values())


def test_traced_run_reports_the_cells_per_layer_metrics():
    cell = "f1-sig-wal.rate"
    proc, line = rehearse(cell, 4, 1)
    assert proc.returncode == 0, proc.stderr[-2000:]
    bench = bench_entries()
    listed = {m["name"] for m in bench["per_layer"] if cell in m["workloads"]}
    # On the CPU there is no device plane: the trace's readers find nothing
    # to read and are left out; every other reader reports, those of the
    # engine's own fields from what the stub engine writes in its place.
    from_trace = {m["name"] for m in bench["per_layer"] if m["source"] == "device_trace"}
    assert set(line["metrics"]) == listed - from_trace and len(listed) == 24
    assert line["correct"] is True and "breakdown" not in line
    value = {k: v["value"] for k, v in line["metrics"].items()}
    # the fill is items over the slots the lines say ran, to the digit
    assert value["pad_fill.rate"] == pytest.approx(
        value["items_per_launch.rate"] / value["rung_slots_mean.rate"], rel=1e-12)
    assert 0 < value["pad_fill.rate"] <= 1 and value["staging_ms_mean.rate"] == 0
    # the slice's xspace is written where the harness looks, and nothing else
    assert "traced slice of" in proc.stderr and "trace without device numbers" in proc.stderr


def test_accept_all_control_comes_out_not_correct():
    """The control: a verify stage that never rejects. The probe's planted
    items come back accepted and the exact comparison fails."""
    proc, line = rehearse("f1-sig-wal.closed", 3, 0, "--control", "accept-all")
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    bad = [ln for ln in proc.stderr.splitlines() if "NOT OK" in ln]
    assert len(bad) == 1 and "probe_verdicts_differing_from_reference" in bad[0]


@pytest.mark.parametrize("key,fails", [
    ("wal_fsync", {"fewest_wal_fsyncs_per_verify_batch"}),
    ("wal", {"votes_missing_from_a_wal", "fewest_wal_fsyncs_per_verify_batch"}),
])
def test_a_flush_or_the_log_left_out_comes_out_not_correct(tmp_path, key, fails):
    """The timed path broken underneath: the same run with the WAL's fsync,
    or the WAL, switched off in a copy of the configuration (a rarer flush
    is a different result, not a faster one). Everything else is driven as
    in a run; ``correct`` has to come out false, by the WAL's comparisons."""
    path, bench = _copy_benchmark(tmp_path)
    cfg_path = tmp_path / "chipbench" / "configs" / "f1-sig-wal.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["cluster"][key] = False
    cfg_path.write_text(json.dumps(cfg))
    proc, line = rehearse("f1-sig-wal.closed", 3, 0, "--benchmark", path, "--root", tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert line["correct"] is False
    bad = [ln.split()[2].rstrip(":") for ln in proc.stderr.splitlines() if "NOT OK" in ln]
    assert set(bad) == fails


def test_a_reply_altered_on_the_way_is_never_counted():
    """The client's quorum rule: a reply whose result was altered after it
    was signed fails its signature check, so the generator never counts it
    and the request stays unacknowledged (a fake gateway answers here)."""
    import socket
    import threading
    import time

    sys.path.insert(0, str(BENCH_DIR))
    import loadgen
    from reference import ed25519_ref as ref

    seeds = [bytes([i + 1]) * 32 for i in range(4)]
    pubs = [ref.public_key(s) for s in seeds]
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(8)

    def serve(alter):
        conn, _ = srv.accept()
        fh = conn.makefile("rb")
        for raw in fh:
            req = json.loads(raw)
            for rid in range(2):
                reply = {"type": "client-reply", "view": 0, "timestamp": req["timestamp"],
                         "client": req["client"], "replica": rid, "result": "awesome!"}
                reply["sig"] = ref.sign(seeds[rid], loadgen.reply_signable(reply)).hex()
                if alter:
                    reply["result"] = "altered!"
                conn.sendall(json.dumps(reply).encode() + b"\n")
            break
        conn.close()

    out = {}
    for alter in (False, True):
        t = threading.Thread(target=serve, args=(alter,), daemon=True)
        t.start()
        now = time.monotonic()
        spec = {"gateway": "127.0.0.1:%d" % srv.getsockname()[1], "n": 4, "f": 1,
                "pubkeys": [p.hex() for p in pubs], "seed": 7, "start_at": now,
                "t1": now + 0.3, "drain_s": 0.5,
                "traffic": {"kind": "closed", "identities": 1, "outstanding_per_identity": 1}}
        gen = loadgen.Generator(spec, ref.verify)
        gen.connect()
        try:
            gen.run()
        except ConnectionError:
            pass
        out[alter] = gen.result()
        gen.close()
        t.join(5)
    assert out[False]["done"][0] is not None and out[False]["bad_signature"] == 0
    assert out[True]["done"][0] is None and out[True]["bad_signature"] == 2


def test_run_py_gives_no_result_without_a_chip():
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "f1-sig-wal.closed",
         "--seed", "3000000019", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, env=ENV, cwd=ROOT, timeout=240,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_alone_in_a_directory_there_is_no_result(tmp_path):
    _copy_benchmark(tmp_path)
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "f1-sig-wal.closed",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        capture_output=True, text=True, env=ENV, cwd=tmp_path, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def _copy_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path / "BENCHMARK.json", json.loads((tmp_path / "BENCHMARK.json").read_text())


def test_new_cell_traffic_and_metric_are_found_by_name(tmp_path):
    """A later PR adds files and entries and edits no file that is there."""
    path, bench = _copy_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (tmp_path / "chipbench").rglob("*") if p.is_file()}
    d = tmp_path / "chipbench"
    (d / "traffic" / "closed-64.json").write_text(json.dumps({
        "kind": "closed", "identities": 4, "outstanding_per_identity": 16, "ramp_s": 1,
        "drain_s": 20, "sample_every": 8, "probe": {"every_ms": 250, "items": 16}}))
    (d / "reducers" / "launch_count.py").write_text(
        "def reduce(run, args):\n    return float(len(run['launches']))\n")
    (d / "metrics" / "launches.closed.json").write_text(json.dumps(
        {"name": "launches.closed", "reducer": "launch_count", "args": {}}))
    bench["workloads"].append({"name": "f1-sig-wal.small", "config": "f1-sig-wal",
                               "traffic": "closed-64", "chips": 1, "why": "a test's cell"})
    for m in bench["end_to_end"]:
        if m["name"] == "commit_rate":
            m["workloads"].append("f1-sig-wal.small")
    bench["per_layer"].append({"name": "launches.closed", "unit": "count", "better": "lower",
                               "source": "program_counter", "layer": "verifyd dispatcher",
                               "moves": "commit_rate", "workloads": ["f1-sig-wal.small"]})
    path.write_text(json.dumps(bench))
    proc, line = rehearse("f1-sig-wal.small", 3, 1, "--benchmark", path, "--root", tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert set(line["metrics"]) == {"launches.closed"}
    assert line["metrics"]["launches.closed"]["value"] > 0 and line["correct"] is True
    assert all(p.read_bytes() == blob for p, blob in before.items())


@pytest.mark.parametrize("field,value", [("name", "commit rate"), ("name", "p95,ms"),
                                         ("unit", "req per s"), ("unit", "µs")])
def test_a_name_or_unit_outside_the_allowed_set_is_refused(tmp_path, field, value):
    path, bench = _copy_benchmark(tmp_path)
    bench["end_to_end"][0][field] = value
    path.write_text(json.dumps(bench))
    proc, line = rehearse("f1-sig-wal.closed", 2, 0, "--benchmark", path, "--root", tmp_path)
    assert proc.returncode != 0 and line is None
    assert "is not a" in proc.stderr

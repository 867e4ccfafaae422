"""The net loop's stage clock (ISSUE 38): where a pass of ``pbftd`` goes by
kind of work, on served clusters in both authentication modes; the
benchmark's eleven readers of it; the operator's column in
``scripts/pbft_top.py``; and ``scripts/trace_report.py``'s idle-interval
table on a hand-made pair of logs. ``core_test`` holds the clock itself
(nested scopes, exclusive time, nothing moving while it is off)."""

import importlib.util
import json
import sys
import threading
import time
from pathlib import Path

import pytest

from pbft_tpu.net import VerifyServiceDaemon
from pbft_tpu.utils import trace_schema

from tests.test_verify_spans import _fetch, _read

ROOT = Path(__file__).resolve().parent.parent
CHIPBENCH = ROOT / "chipbench"
STAGES = trace_schema.LOOP_STAGES

sys.path.insert(0, str(CHIPBENCH))
try:
    import stats
finally:
    sys.path.pop(0)


def _script(name: str):
    spec = importlib.util.spec_from_file_location(name, ROOT / "scripts" / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _scrape(cluster) -> list:
    """Each replica's parsed /metrics and /status, stamped as it came back."""
    out = []
    for port in cluster.metrics_ports:
        metrics = stats.parse_prometheus(_fetch(port, "/metrics"))
        at = time.monotonic()
        out.append({"t": at, "metrics": metrics, "status": json.loads(_fetch(port, "/status"))})
    return out


def _serve(cluster, clients: int, each: int, tag: str) -> None:
    from pbft_tpu.net import PbftClient

    errors: list = []

    def one(k: int) -> None:
        client = PbftClient(cluster.config)
        try:
            sent = [client.request(f"{tag}-{k}-{i}") for i in range(each)]
            for req in sent:
                assert client.wait_result(req.timestamp, timeout=60) == "awesome!"
        except Exception as e:  # noqa: BLE001 - shown by the main thread
            errors.append(e)
        finally:
            client.close()

    threads = [threading.Thread(target=one, args=(k,)) for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors


def _settled(cluster) -> list:
    deadline = time.monotonic() + 30
    while True:  # trailing commits and checkpoints land
        final = [json.loads(_fetch(port, "/status")) for port in cluster.metrics_ports]
        if len({d["chain_digest"] for d in final}) == 1 and all(d["inbox_depth"] == 0 for d in final):
            return final
        assert time.monotonic() < deadline, [d["executed"] for d in final]
        time.sleep(0.2)


@pytest.mark.parametrize("mode", ["sig", "mac"])
def test_the_stage_clock_accounts_for_a_served_replicas_whole_loop(mode):
    """Between two scrapes of every replica the seven stages gain what the
    total gains, to the microsecond, and the total gains the wall time
    between them; the primary worked (protocol, send) and waited; behind a
    verify service every batch's apply is clocked once; every reply was
    signed. In MAC mode no replica touches the verify inbox once its links
    are up, and the protocol stage runs all the same."""
    from pbft_tpu import native

    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    from pbft_tpu.net import LocalCluster

    more = {"fastpath": "mac", "tentative": True} if mode == "mac" else {}
    clients, each = 4, 10
    daemon = VerifyServiceDaemon(backend="native").start()
    try:
        with LocalCluster(
            n=4, verifier=daemon.address, metrics_ports=True, wal=True, **more
        ) as cluster:
            _serve(cluster, 1, 2, "links-up")  # every link's lanes exist after this
            _settled(cluster)
            first = _scrape(cluster)
            _serve(cluster, clients, each, mode)
            time.sleep(1.0)  # a stretch of plain waiting too
            _settled(cluster)
            last = _scrape(cluster)
    finally:
        daemon.stop()
    for rid, (a, b) in enumerate(zip(first, last)):
        gain = {
            name: stats.counter_delta(a["metrics"], b["metrics"], f"pbft_loop_{name}_us_total")
            for name in STAGES
        }
        total = stats.counter_delta(a["metrics"], b["metrics"], "pbft_loop_us_total")
        for edge in (a, b):  # one rendering is one instant: exact there
            assert sum(edge["metrics"][(f"pbft_loop_{s}_us_total", "")] for s in STAGES) == (
                edge["metrics"][("pbft_loop_us_total", "")]
            )
        assert sum(gain.values()) == total
        wall_us = 1e6 * (b["t"] - a["t"])
        assert abs(total - wall_us) < 0.05 * wall_us, (rid, total, wall_us)
        assert gain["wait"] > 0 and gain["read"] > 0 and gain["protocol"] > 0, (rid, gain)
        assert min(gain.values()) >= 0
        # /status says the same, in one object, with the passes.
        doc = b["status"]["loop_us"]
        assert set(doc) == set(STAGES) | {"passes", "switches"}
        assert doc["passes"] > 0 and doc["switches"] > 0
        assert all(doc[s] >= b["metrics"][(f"pbft_loop_{s}_us_total", "")] for s in STAGES)
        # Every replica signs every reply (and its votes on top).
        signs = stats.counter_delta(a["metrics"], b["metrics"], "pbft_signs_total")
        assert signs >= clients * each, (rid, signs)
        batches = stats.counter_delta(a["metrics"], b["metrics"], "pbft_verify_batches_total")
        applied = stats.hist_delta(a["metrics"], b["metrics"], "pbft_verdict_apply_seconds")
        if mode == "sig":
            assert gain["verify"] > 0 and gain["wal"] > 0, (rid, gain)
            # The async branch: every batch is a kept span, clocked once.
            assert applied[1] == batches > 0 and 0 < applied[0] < 30
            assert b["status"]["verify_apply"]["batches"] == (
                b["metrics"][("pbft_verdict_apply_seconds_count", "")]
            )
        else:
            assert gain["verify"] == 0 and batches == 0 and applied[1] == 0, (rid, gain, batches)
        # One flush a connection an emit (ISSUE 41): every send() carried a
        # frame or more, and the flush that ends an emit is inside `send`
        # (the seven still sum to the total, above).
        frames = stats.counter_delta(a["metrics"], b["metrics"], "pbft_frames_out_total")
        calls = stats.counter_delta(a["metrics"], b["metrics"], "pbft_send_calls_total")
        assert frames >= calls > 0 and gain["send"] > 0, (rid, frames, calls, gain)
    primary = last[0]["status"]["view"] % 4
    sends = stats.counter_delta(
        first[primary]["metrics"], last[primary]["metrics"], "pbft_loop_send_us_total")
    assert sends > 0
    # The two counters nothing read are gone; the three that were looked up
    # by name once a frame are folded from the loop's integers at the scrape.
    m, d = last[primary]["metrics"], last[primary]["status"]
    assert not [k for k in m if "codec" in k[0]]
    assert 0 < m[("pbft_frames_in_total", "")] <= d["frames_in"]  # /status was read after
    assert 0 < m[("pbft_epoll_wakeups_total", "")] <= d["loop_us"]["passes"]
    if mode == "mac":
        assert 0 < m[("pbft_mac_frames_total", "")] <= d["mac_frames"]


# -- the benchmark's readers ------------------------------------------------------

# The cells of PR 38, and behind them the one PR 40 appended to every list
# its twin is on (f1-sig-wal-mt.closed), and the one PR 42 appended wherever
# f5-sig-wal.closed is (f10-sig-wal.closed).
CLOSED4 = ["f1-sig-wal.closed", "f5-sig-wal.closed", "f1-mac-tentative.closed", "f5-sig-wal-x4.closed",
           "f1-sig-wal-mt.closed", "f10-sig-wal.closed"]
SIG3 = [c for c in CLOSED4 if "mac" not in c]
RATE = ["f1-sig-wal.rate"]
NEW = {
    "loop_wait_share.closed": ("ratio", "higher", "program_counter", "commit_rate", CLOSED4),
    "loop_wait_share.rate": ("ratio", "higher", "program_counter", "reply_p50_ms", RATE),
    **{
        f"loop_{stage}_us_per_req.closed": ("us/req", "lower", "program_counter", "commit_rate", CLOSED4)
        for stage in STAGES if stage != "wait"
    },
    "verdict_apply_ms_mean.closed": ("ms", "lower", "program_span", "commit_rate", SIG3),
    "verdict_apply_ms_mean.rate": ("ms", "lower", "program_span", "reply_p50_ms", RATE),
    "signs_per_req.closed": ("count", "lower", "program_counter", "commit_rate", CLOSED4),
}


def _hand_run(old: bool = False) -> dict:
    """Two scrapes of four replicas, replica 0 the primary: over a window in
    which 1,000 requests completed its loop spent 10 s, 4 of them waiting."""
    spent = {"wait": 4_000_000, "read": 1_500_000, "protocol": 2_500_000, "wal": 500_000,
             "send": 1_000_000, "verify": 300_000, "other": 200_000}
    after = {("pbft_loop_us_total", ""): 5_000_000.0 + sum(spent.values()),
             ("pbft_signs_total", ""): 100.0 + 1_250,
             ("pbft_verdict_apply_seconds_sum", ""): 1.0 + 0.8,
             ("pbft_verdict_apply_seconds_count", ""): 10.0 + 40}
    before = {("pbft_loop_us_total", ""): 5_000_000.0, ("pbft_signs_total", ""): 100.0,
              ("pbft_verdict_apply_seconds_sum", ""): 1.0, ("pbft_verdict_apply_seconds_count", ""): 10.0}
    for stage, us in spent.items():
        before[(f"pbft_loop_{stage}_us_total", "")] = 700_000.0
        after[(f"pbft_loop_{stage}_us_total", "")] = 700_000.0 + us
    if old:  # a program from before the clock, as the parent commit is
        before, after = {}, {("pbft_verify_batches_total", ""): 3.0}
    status = [{"view": 0}] * 4
    done = [10.0 + 0.01 * i for i in range(1000)]
    return {
        "t0": 10.0, "t1": 20.0, "gen": {"due": done, "done": done},
        "edge_a": {"metrics": [before] * 4, "status": status},
        "edge_b": {"metrics": [after] * 4, "status": status},
    }


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_reader_names_what_exists_and_reads_the_hand_made_run(name):
    unit, better, source, moves, cells = NEW[name]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # Its entry: appended after everything the benchmark had, under the one
    # new layer, in cells the benchmark has and that report what it moves.
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == [{
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": "net loop (core/net.cc)", "moves": moves, "workloads": cells,
    }]
    # (PR 40's ten readers of the shard tier came behind them, and PR 41's
    # frames_per_send.closed behind those, PR 42's four behind that, and
    # PR 43's two fused_launch_share readers last.)
    assert bench["per_layer"].index(entry[0]) >= len(bench["per_layer"]) - len(NEW) - 17
    known = {c["name"] for c in bench["workloads"]}
    reporting = next(m for m in bench["end_to_end"] if m["name"] == moves)["workloads"]
    assert set(cells) <= known and set(cells) <= set(reporting)
    # Its reader: a reducer that is there, on series the manifest has, pbftd's alone.
    spec = json.loads((CHIPBENCH / "metrics" / f"{name}.json").read_text())
    assert spec["name"] == name and set(spec) == {"name", "reducer", "args"}
    assert spec["reducer"] in ("counter_delta_ratio", "hist_delta_mean")
    assert (CHIPBENCH / "reducers" / f"{spec['reducer']}.py").is_file()
    series = [spec["args"].get(k) for k in ("counter", "histogram", "over")]
    series = [s for s in series if s and s.startswith("pbft_")]
    assert series
    for s in series:
        kind, emitters = trace_schema.METRIC_SCHEMAS[s]
        assert emitters == {"net.cc"}
        assert kind == ("histogram" if s == spec["args"].get("histogram") else "counter")
    # What it reads on the hand-made run, and nothing (no error) on a
    # program that has no such counter.
    want = {
        "loop_wait_share": 0.4, "loop_read_us_per_req": 1500.0, "loop_protocol_us_per_req": 2500.0,
        "loop_wal_us_per_req": 500.0, "loop_send_us_per_req": 1000.0, "loop_verify_us_per_req": 300.0,
        "loop_other_us_per_req": 200.0, "verdict_apply_ms_mean": 20.0, "signs_per_req": 1.25,
    }[name.rsplit(".", 1)[0]]
    assert _read(name, _hand_run()) == pytest.approx(want, rel=1e-12)
    assert _read(name, _hand_run(old=True)) is None


def test_frames_per_send_is_a_data_file_on_the_reducer_that_is_there():
    """ISSUE 41's one reader: appended last (PR 42's four came behind it), in the closed cells, the
    gain of one counter over the gain of the other on the primary; nothing
    (and no error) on a program that has neither, as the parent commit is."""
    name = "frames_per_send.closed"
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert bench["per_layer"][-7] == {  # PR 42's four and PR 43's two stand behind it
        "name": name, "unit": "count", "better": "higher", "source": "program_counter",
        "layer": "net loop (core/net.cc)", "moves": "commit_rate", "workloads": CLOSED4,
    }
    spec = json.loads((CHIPBENCH / "metrics" / f"{name}.json").read_text())
    assert spec == {"name": name, "reducer": "counter_delta_ratio", "args": {
        "counter": "pbft_frames_out_total", "over": "pbft_send_calls_total", "replicas": "primary"}}
    for series in ("pbft_frames_out_total", "pbft_send_calls_total"):
        assert trace_schema.METRIC_SCHEMAS[series] == ("counter", {"net.cc", "net_shard.cc"})
    run = _hand_run()
    for edge, frames, calls in (("edge_a", 500.0, 400.0), ("edge_b", 500.0 + 2_400, 400.0 + 200)):
        run[edge]["metrics"] = [{**run[edge]["metrics"][0], ("pbft_frames_out_total", ""): frames,
                                 ("pbft_send_calls_total", ""): calls}] * 4
    assert _read(name, run) == pytest.approx(12.0, rel=1e-12)
    assert _read(name, _hand_run()) is None  # a scrape without the counters
    assert _read(name, _hand_run(old=True)) is None


# -- the operator's column ----------------------------------------------------------


def test_pbft_top_prints_the_loops_busy_share():
    top = _script("pbft_top")

    def snap(t, wait, rest):
        doc = {"view": 0, "executed": int(100 * t), "loop_us": {
            "wait": wait, "read": rest, "protocol": rest, "wal": 0, "send": rest,
            "verify": 0, "other": 0, "passes": 10, "switches": 99}}
        return {"t": t, "replicas": {0: doc, 1: {"view": 0, "executed": 0}}}

    # Two seconds in which the loop waited 0.5 s and worked 1.5: busy 0.75.
    history = [snap(0.0, 1_000_000, 0), snap(2.0, 1_500_000, 500_000)]
    assert top.loop_busy(history, 0) == pytest.approx(0.75)
    assert top.loop_busy(history, 1) is None  # a runtime without the clock
    assert top.loop_busy(history[:1], 0) is None
    lines = top.render(history, []).splitlines()
    assert lines[1].split()[:7] == ["id", "view", "executed", "committed", "floor", "req/s", "loop"]
    assert lines[2].split()[6] == "0.75" and lines[3].split()[6] == "-"


# -- the idle-interval table, on a hand-made pair of logs -------------------------


def _launch(t_dev, busy, **more):
    return {"ts": t_dev + busy, "ev": "verify_batch", "replica": "service", "size": 40,
            "requests": 2, "rejected": 0, "secs": busy, "t_dev": t_dev, "dispatch_s": 0.001,
            "wait_s": busy - 0.001, "queue_s": 0.0005, "slot_s": 0.0, "pad_s": 0.0002,
            "hold_s": 0.0, **more}


def _batch(rid, ts, totals):
    return {"ts": ts, "ev": "verify_batch", "replica": rid, "size": 20, "rejected": 0,
            "secs": 0.008, "ahead": 1, "apply_s": 0.004, "loop_us": list(totals)}


def test_trace_report_puts_verifyds_idle_intervals_down_to_what_the_replicas_did(tmp_path, capsys):
    report = _script("trace_report")
    # Three launches: 100.000-100.008, 100.010-100.018 (a 2 ms gap: no row),
    # then nothing in flight for 12 ms until the third, at 100.030.
    launches = [_launch(100.000, 0.008), _launch(100.010, 0.008), _launch(100.030, 0.008)]
    # Replica 0 spent the bracket (100.016 .. 100.034) at work, most of it in
    # `protocol`; replica 1 (100.017 .. 100.031) waiting.
    r0 = [_batch(0, 100.001, (1000, 0, 0, 0, 0, 0, 0)),
          _batch(0, 100.016, (9000, 2000, 3000, 500, 1000, 400, 100)),
          _batch(0, 100.034, (10000, 4000, 14000, 1500, 3000, 1000, 500)),
          _batch(0, 100.050, (20000, 5000, 16000, 1600, 3300, 1100, 600))]
    r1 = [_batch(1, 100.017, (12000, 1000, 2000, 0, 500, 300, 200)),
          _batch(1, 100.031, (24000, 1500, 2800, 0, 800, 500, 400))]
    assert report.idle_intervals(launches) == [(pytest.approx(100.018), 100.030, launches[2])]
    (tmp_path / "verifyd.jsonl").write_text("".join(json.dumps(e) + "\n" for e in launches))
    (tmp_path / "replica-0.jsonl").write_text("".join(json.dumps(e) + "\n" for e in r0))
    (tmp_path / "replica-1.jsonl").write_text("".join(json.dumps(e) + "\n" for e in r1))
    total = report.report(report.expand_trace_args([str(tmp_path)]))
    out = capsys.readouterr().out
    (row,) = total["idle_intervals"]
    assert row["gap_s"] == pytest.approx(0.012) and row["held_s"] == pytest.approx(0.0007)
    secs0, split0 = row["loops"][0]
    assert secs0 == pytest.approx(0.018)
    assert split0 == {"wait": 1000, "read": 2000, "protocol": 11000, "wal": 1000,
                      "send": 2000, "verify": 600, "other": 400}
    assert row["loops"][1][1]["wait"] == 12000
    # Pooled, the replicas worked 17.9 of 31.9 ms, most of it in `protocol`.
    assert row["verdict"] == "replicas at work: protocol"
    assert "verifyd idle: 1 intervals of 5 ms or more with nothing in flight, 0.012s of 0.038s" in out
    assert "idle   12.00 ms, ended by a launch of 40 items from 2" in out
    assert "replicas at work: protocol" in out
    assert "replica 0:   18.00 ms  0.06 0.11 0.61 0.06 0.11 0.03 0.02" in out
    assert "a batch's verdicts worked through in p50=4.00ms" in out
    # The same gap with a request sitting in verifyd for 9 of its 12 ms: the hold's.
    launches[2] = _launch(100.030, 0.008, queue_s=0.009, hold_s=0.0073, held_out=1)
    (held,) = report.idle_table(launches, {0: r0, 1: r1})
    assert held["verdict"] == "verifyd's hold" and held["held_s"] == pytest.approx(0.0092)
    # And with both replicas asleep in their pollers: the chip waits for the clients.
    asleep = {1: r1, 2: [dict(e, replica=2) for e in r1]}
    launches[2] = _launch(100.030, 0.008)
    (waiting,) = report.idle_table(launches, asleep)
    assert waiting["verdict"].startswith("replicas in wait")

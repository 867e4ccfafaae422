"""The multi-core front end's own clocks (ISSUE 40), in ``test_loop_clock.py``'s
manner: on a served cluster of ``pbftd --net-threads 2`` every shard thread's
and every pipeline thread's four stages gain, between two scrapes, the wall
time between them (a thread publishes its clock where a wait ends, so a
reading lags by at most one wait's timeout); the hand-off's histogram observes
once a drain of the shard inbox that found something; nothing is dropped; the
consensus thread keeps its seven stages. Then the benchmark's ten readers of
them on a hand-made run, the operator's line in ``scripts/pbft_top.py``, and
``scripts/trace_report.py``'s line from the ``--trace`` batch lines."""

import json
import time
from pathlib import Path

import pytest

from pbft_tpu.utils import trace_schema

from tests.test_loop_clock import _scrape, _script, _settled, stats
from tests.test_verify_spans import _read

ROOT = Path(__file__).resolve().parent.parent
CHIPBENCH = ROOT / "chipbench"
SHARD, PIPE = trace_schema.SHARD_STAGES, trace_schema.PIPE_STAGES
CELL = "f1-sig-wal-mt.closed"

def _serve_in_order(cluster, clients: int, each: int, tag: str) -> None:
    """``clients`` dial-back clients, each with ONE request outstanding. (A
    ``PbftClient`` sends every request over a connection of its own; the
    sharded front end keeps order a connection, not across connections, so
    a client that wants several outstanding keeps them on one connection,
    as the gateway does and ``request_many`` does.)"""
    import threading

    from pbft_tpu.net import PbftClient

    errors: list = []

    def one(k: int) -> None:
        client = PbftClient(cluster.config)
        try:
            for i in range(each):
                req = client.request(f"{tag}-{k}-{i}")
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


def test_every_front_end_threads_stages_sum_to_its_elapsed_time_and_a_drain_observes_once(tmp_path):
    from pbft_tpu import native

    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    from pbft_tpu.net import LocalCluster

    threads = 2
    with LocalCluster(n=4, net_threads=threads, metrics_ports=True, wal=True,
                      trace_dir=str(tmp_path)) as cluster:
        _serve_in_order(cluster, 1, 2, "links-up")
        _settled(cluster)
        time.sleep(0.3)  # every thread has published since its clock came on
        first = _scrape(cluster)
        _serve_in_order(cluster, 4, 10, "sharded")
        time.sleep(1.0)  # a stretch of plain waiting too
        _settled(cluster)
        last = _scrape(cluster)
    for rid, (a, b) in enumerate(zip(first, last)):
        wall_us = 1e6 * (b["t"] - a["t"])
        for prefix, stages, key in (("pbft_shard", SHARD, "shard_us"), ("pbft_pipe", PIPE, "pipe_us")):
            gain = {s: stats.counter_delta(a["metrics"], b["metrics"], f"{prefix}_{s}_us_total")
                    for s in stages}
            assert min(gain.values()) >= 0 and gain["wait"] > 0, (rid, prefix, gain)
            # N threads' elapsed time, to a twentieth and the two waits a
            # thread may be behind at either scrape (100 ms each).
            assert abs(sum(gain.values()) - threads * wall_us) < 0.05 * threads * wall_us + 4e5, (
                rid, prefix, gain, wall_us)
            # /status says the same a thread (it was read after /metrics).
            doc = b["status"][key]
            assert len(doc) == threads and all(set(t) == set(stages) for t in doc)
            for s in stages:
                assert sum(t[s] for t in doc) >= b["metrics"][(f"{prefix}_{s}_us_total", "")] - threads
        shard = {s: stats.counter_delta(a["metrics"], b["metrics"], f"pbft_shard_{s}_us_total") for s in SHARD}
        pipe = {s: stats.counter_delta(a["metrics"], b["metrics"], f"pbft_pipe_{s}_us_total") for s in PIPE}
        assert shard["read"] > 0 and shard["send"] > 0, (rid, shard)
        # The shards' frames and send() calls reach the scrape under the
        # loop's two names (ISSUE 41; the consensus thread sends nothing
        # itself here), and the flush behind a drained stretch is `send`'s:
        # the four stages still sum to the threads' time, above.
        frames_out = stats.counter_delta(a["metrics"], b["metrics"], "pbft_frames_out_total")
        send_calls = stats.counter_delta(a["metrics"], b["metrics"], "pbft_send_calls_total")
        assert frames_out >= send_calls > 0, (rid, frames_out, send_calls)
        assert pipe["decode"] > 0 and pipe["encode"] > 0, (rid, pipe)
        # The hand-off: once a drain that found something, so no more often
        # than the consensus thread made passes, and never negative.
        waited, drains = stats.hist_delta(a["metrics"], b["metrics"], "pbft_shard_handoff_seconds")
        passes = b["status"]["loop_us"]["passes"] - a["status"]["loop_us"]["passes"]
        frames = stats.counter_delta(a["metrics"], b["metrics"], "pbft_frames_in_total")
        assert 0 < drains <= passes and drains <= frames and 0 <= waited < 0.5 * drains, (
            rid, drains, passes, frames, waited)
        assert b["status"]["shard_handoff"]["drains"] >= b["metrics"][("pbft_shard_handoff_seconds_count", "")]
        # Nothing lost between the threads; wakes counted; two shards seen.
        assert stats.counter_delta(a["metrics"], b["metrics"], "pbft_shard_dropped_total") == 0
        assert b["status"]["shard_dropped"] == {"pipeline": 0, "inbox": 0, "replies": 0}
        assert stats.counter_delta(a["metrics"], b["metrics"], "pbft_cross_thread_wakes_total") > 0
        assert b["status"]["net_threads"] == threads
        # The consensus thread's seven stages are its own and still exact.
        loop = {s: stats.counter_delta(a["metrics"], b["metrics"], f"pbft_loop_{s}_us_total")
                for s in trace_schema.LOOP_STAGES}
        total = stats.counter_delta(a["metrics"], b["metrics"], "pbft_loop_us_total")
        assert sum(loop.values()) == total and abs(total - wall_us) < 0.05 * wall_us
        assert loop["protocol"] > 0 and loop["wait"] > 0
    # The batch lines of --trace carry the front end's running totals, and
    # trace_report prints a line from them.
    report = _script("trace_report")
    lines = [json.loads(ln) for ln in (tmp_path / "replica-0.jsonl").read_text().splitlines()]
    batches = [e for e in lines if e.get("ev") == "verify_batch"]
    assert batches and all(
        len(e["shard_us"]) == 4 and len(e["pipe_us"]) == 4 and len(e["handoff"]) == 2 for e in batches)
    assert batches[-1]["handoff"][0] >= batches[0]["handoff"][0] >= 0
    said = report.front_end_summary(batches)
    assert "shards busy" in said and "pipelines busy" in said and "hand-off mean" in said


# -- the benchmark's readers ------------------------------------------------------

SH, PI, HO = ("loop shards (core/net_shard.cc)", "crypto pipelines (core/net_shard.cc)",
              "shard hand-off (core/net_shard.cc)")
NEW = {
    "net_threads_seen.closed": ("threads", "higher", "program_counter", SH, "status_field_stat"),
    "shard_busy_share.closed": ("ratio", "lower", "program_counter", SH, "counter_sums_ratio"),
    "pipe_busy_share.closed": ("ratio", "lower", "program_counter", PI, "counter_sums_ratio"),
    "shard_read_us_per_req.closed": ("us/req", "lower", "program_counter", SH, "counter_delta_ratio"),
    "shard_send_us_per_req.closed": ("us/req", "lower", "program_counter", SH, "counter_delta_ratio"),
    "pipe_decode_us_per_req.closed": ("us/req", "lower", "program_counter", PI, "counter_delta_ratio"),
    "pipe_encode_us_per_req.closed": ("us/req", "lower", "program_counter", PI, "counter_delta_ratio"),
    "handoff_ms_mean.closed": ("ms", "lower", "program_span", HO, "hist_delta_mean"),
    "cross_wakes_per_req.closed": ("count", "lower", "program_counter", HO, "counter_delta_ratio"),
    "shard_dropped.closed": ("count", "lower", "program_counter", HO, "counter_delta_ratio"),
}


def _hand_run(old: bool = False) -> dict:
    """Two scrapes of four replicas, replica 0 the primary: over a window in
    which 1,000 requests completed its two shard threads spent 20 s, 14 of
    them waiting, its two pipelines 20 s, 17 waiting; 400 drains of the
    inbox whose oldest entries had waited 0.6 s in all; 250 wakes; no drop."""
    spent = {"pbft_shard_wait_us_total": 14_000_000, "pbft_shard_read_us_total": 1_500_000,
             "pbft_shard_send_us_total": 4_000_000, "pbft_shard_other_us_total": 500_000,
             "pbft_pipe_wait_us_total": 17_000_000, "pbft_pipe_decode_us_total": 1_000_000,
             "pbft_pipe_encode_us_total": 1_750_000, "pbft_pipe_other_us_total": 250_000,
             "pbft_cross_thread_wakes_total": 250, "pbft_shard_dropped_total": 0,
             "pbft_shard_handoff_seconds_sum": 0.6, "pbft_shard_handoff_seconds_count": 400}
    before = {(name, ""): 7_000.0 for name in spent}
    after = {(name, ""): 7_000.0 + gain for name, gain in spent.items()}
    status = [{"view": 0, "net_threads": 2}] * 4
    if old:  # a program from before the clocks, as the parent commit is
        before, after = {}, {("pbft_verify_batches_total", ""): 3.0}
        status = [{"view": 0}] * 4
    done = [10.0 + 0.01 * i for i in range(1000)]
    return {
        "t0": 10.0, "t1": 20.0, "gen": {"due": done, "done": done},
        "edge_a": {"metrics": [before] * 4, "status": status},
        "edge_b": {"metrics": [after] * 4, "status": status},
    }


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_reader_of_the_shard_tier_names_what_exists_and_reads_the_hand_made_run(name):
    unit, better, source, layer, reducer = NEW[name]
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [m for m in bench["per_layer"] if m["name"] == name]
    assert entry == [{
        "name": name, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": "commit_rate", "workloads": [CELL],
    }]
    # (PR 41's one reader of the send() calls came behind them, PR 42's four
    # behind that, and PR 43's two behind those.)
    assert bench["per_layer"].index(entry[0]) >= len(bench["per_layer"]) - len(NEW) - 7
    assert CELL in next(m for m in bench["end_to_end"] if m["name"] == "commit_rate")["workloads"]
    spec = json.loads((CHIPBENCH / "metrics" / f"{name}.json").read_text())
    assert spec["name"] == name and set(spec) == {"name", "reducer", "args"}
    assert spec["reducer"] == reducer and (CHIPBENCH / "reducers" / f"{reducer}.py").is_file()
    args = spec["args"]
    series = [args.get(k) for k in ("counter", "histogram", "over")] + args.get("top", []) + args.get("bottom", [])
    for s in [s for s in series if s and s.startswith("pbft_")]:
        kind, emitters = trace_schema.METRIC_SCHEMAS[s]
        assert kind == ("histogram" if s == args.get("histogram") else "counter")
        # What ISSUE 40 added is pbftd's alone; the wakes' counter is older.
        assert emitters == ({"net.cc"} if s == "pbft_cross_thread_wakes_total"
                            else {"net.cc", "net_shard.cc"})
    want = {
        "net_threads_seen": 2.0, "shard_busy_share": 0.3, "pipe_busy_share": 0.15,
        "shard_read_us_per_req": 1500.0, "shard_send_us_per_req": 4000.0,
        "pipe_decode_us_per_req": 1000.0, "pipe_encode_us_per_req": 1750.0,
        "handoff_ms_mean": 1.5, "cross_wakes_per_req": 0.25, "shard_dropped": 0.0,
    }[name.rsplit(".", 1)[0]]
    assert _read(name, _hand_run()) == pytest.approx(want, rel=1e-12, abs=1e-12)
    # A program that has no such counter or field: nothing, and no error.
    assert _read(name, _hand_run(old=True)) is None


def test_the_least_thread_count_over_the_replicas_is_what_is_seen():
    run = _hand_run()
    run["edge_b"]["status"] = [{"view": 0, "net_threads": n} for n in (2, 2, 1, 2)]
    assert _read("net_threads_seen.closed", run) == 1.0


# -- the operator's line -------------------------------------------------------------


def test_pbft_top_prints_a_sharded_replicas_front_end():
    top = _script("pbft_top")

    def snap(t, shard_wait, shard_work, drains, seconds):
        doc = {"view": 0, "executed": int(100 * t), "net_threads": 2,
               "shard_us": [{"wait": shard_wait, "read": shard_work, "send": shard_work, "other": 0},
                            {"wait": shard_wait, "read": 0, "send": 0, "other": 0}],
               "pipe_us": [{"wait": shard_wait, "decode": 0, "encode": 0, "other": 0}] * 2,
               "shard_handoff": {"drains": drains, "seconds": seconds},
               "shard_dropped": {"pipeline": 0, "inbox": 0, "replies": 0}}
        return {"t": t, "replicas": {0: doc, 1: {"view": 0, "executed": 0}}}

    # Two seconds of two shard threads (4 s): one worked 1 s, so busy 0.25;
    # the pipelines only waited (and gained nothing else): busy 0.
    history = [snap(0.0, 1_000_000, 0, 10, 0.010), snap(2.0, 2_500_000, 500_000, 110, 0.260)]
    assert top.front_busy(history, 0, "shard_us") == pytest.approx(0.25)
    assert top.front_busy(history, 0, "pipe_us") == pytest.approx(0.0)
    assert top.front_busy(history, 1, "shard_us") is None  # one loop, or another runtime
    assert top.handoff_ms(history, 0) == pytest.approx(2.5)
    assert top.handoff_ms(history, 1) is None and top.handoff_ms(history[:1], 0) is None
    text = top.render(history, [])
    assert "0: net_threads=2 shards busy 0.25 pipelines busy 0.00 hand-off 2.50ms dropped 0" in text
    assert "1: net_threads" not in text

"""Per-request latency waterfalls (ISSUE 9): the pure join logic over
synthetic events, the real-cluster join through consensus_timeline
--waterfall, and the verify_status introspection CLI."""

import json
import pathlib
import subprocess
import sys
import time

import pytest

from pbft_tpu import native
from pbft_tpu.utils import waterfall

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- the join, on synthetic events -------------------------------------------


def _synthetic_events():
    """One request through the whole pipeline with known segment times:
    client_queue 10ms, batch_wait 20ms, prepared 30ms, committed 40ms,
    execute 50ms, reply 60ms (e2e 210ms)."""
    send = 100.0
    events = [
        {"ts": send + 0.010, "ev": "request_rx", "replica": 0,
         "client": "c:1", "req_ts": 7},
        {"ts": send + 0.030, "ev": "batch_sealed", "replica": 0, "view": 0,
         "seq": 3, "batch": 2, "wait_s": 0.02, "reqs": [["c:1", 7], ["c:2", 4]]},
        {"ts": send + 0.150, "ev": "consensus_span", "replica": 0, "view": 0,
         "seq": 3, "request": send + 0.030, "pre_prepare": send + 0.030,
         "prepared": send + 0.060, "committed": send + 0.100,
         "executed": send + 0.150},
    ]
    client = [{"client": "c:1", "req_ts": 7, "send": send,
               "first_reply": send + 0.190, "quorum": send + 0.210}]
    return events, client


def test_build_waterfall_segments():
    events, client = _synthetic_events()
    wf = waterfall.build_waterfall(events, client)
    assert wf["requests"] == 1
    assert wf["mean_batch"] == 2.0
    seg = wf["segments_ms"]
    assert seg["client_queue"]["p50"] == pytest.approx(10.0, abs=0.01)
    assert seg["batch_wait"]["p50"] == pytest.approx(20.0, abs=0.01)
    assert seg["prepared"]["p50"] == pytest.approx(30.0, abs=0.01)
    assert seg["committed"]["p50"] == pytest.approx(40.0, abs=0.01)
    assert seg["execute"]["p50"] == pytest.approx(50.0, abs=0.01)
    assert seg["reply"]["p50"] == pytest.approx(60.0, abs=0.01)
    assert wf["e2e_ms"]["p50"] == pytest.approx(210.0, abs=0.01)
    # Render covers every segment row.
    text = waterfall.render(wf)
    for name in waterfall.SEGMENTS + ("e2e",):
        assert name in text


def test_build_waterfall_partial_evidence_degrades_gracefully():
    """A request with client stamps but no replica trace contributes
    nothing; one with only request_rx still yields client_queue."""
    events = [{"ts": 5.0, "ev": "request_rx", "replica": 0,
               "client": "c:9", "req_ts": 1}]
    client = [
        {"client": "c:9", "req_ts": 1, "send": 4.99, "quorum": 5.2},
        {"client": "ghost:0", "req_ts": 8, "send": 1.0},
    ]
    wf = waterfall.build_waterfall(events, client)
    assert wf["requests"] == 1
    assert wf["segments_ms"]["client_queue"]["count"] == 1
    assert wf["segments_ms"]["prepared"]["count"] == 0


# -- real cluster -> consensus_timeline --waterfall ---------------------------


@pytest.mark.skipif(not native.available(), reason="native core not built")
@pytest.mark.parametrize("net_threads", [1, 2])
def test_waterfall_from_real_cluster_traces(tmp_path, net_threads):
    """Drive a batching cluster (either socket layer) with traces on, write the
    client trace next to the replica traces, and require
    consensus_timeline --waterfall to join them: every segment populated,
    requests joined, mean batch surfaced."""
    from pbft_tpu.net import LocalCluster, PbftClient

    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    with LocalCluster(
        n=4,
        verifier="cpu",
        net_threads=net_threads,
        trace_dir=str(trace_dir),
        batch_max_items=4,
        batch_flush_us=2000,
    ) as cluster:
        client = PbftClient(cluster.config)
        try:
            results = client.request_many(
                [f"op-{i}" for i in range(24)], window=8, timeout=30
            )
            assert results == ["awesome!"] * 24
        finally:
            client.write_trace(str(trace_dir / "client-0.jsonl"))
            client.close()
        time.sleep(0.3)  # let the last trace lines flush

    sys.path.insert(0, str(REPO / "scripts"))
    import consensus_timeline

    res = consensus_timeline.main([str(trace_dir), "--waterfall", "--json"])
    wf = res["waterfall"]
    assert wf["requests"] >= 20
    assert wf["mean_batch"] > 1.0  # the batching knobs actually batched
    seg = wf["segments_ms"]
    for name in ("client_queue", "batch_wait", "prepared", "committed",
                 "execute", "reply"):
        assert seg[name]["count"] > 0, f"segment {name} never measured"
        assert seg[name]["p99"] >= seg[name]["p50"] >= 0.0
    assert res.get("mean_batch") and res["mean_batch"] > 1.0


# -- verify_status CLI (satellite) -------------------------------------------


def test_verify_status_cli_against_live_service():
    from pbft_tpu.net import VerifierService

    svc = VerifierService(backend="cpu").start()
    try:
        out = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "verify_status.py"),
                svc.address,
            ],
            capture_output=True,
            text=True,
        )
        assert out.returncode == 0, out.stdout + out.stderr
        assert "state" in out.stdout
        js = subprocess.run(
            [
                sys.executable,
                str(REPO / "scripts" / "verify_status.py"),
                svc.address,
                "--json",
            ],
            capture_output=True,
            text=True,
        )
        assert js.returncode == 0
        status = json.loads(js.stdout)
        assert "state" in status
    finally:
        svc.stop()


def test_verify_status_cli_unreachable_exits_1():
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "verify_status.py"),
            "127.0.0.1:1",  # nothing listens here
            "--timeout",
            "0.3",
        ],
        capture_output=True,
        text=True,
    )
    assert out.returncode == 1

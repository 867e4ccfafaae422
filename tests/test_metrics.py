"""The observability layer (ISSUE 1): registry semantics, consensus-phase
span lifecycle, the /metrics scrape surface of pbftd and the verify service, the
cross-replica timeline analyzer against the checked-in r5 fixtures, and
the Tracer hot-loop hardening."""

import io
import json
import pathlib
import socket
import subprocess
import sys
import time
import urllib.request

import pytest

from pbft_tpu.consensus.config import ClusterConfig, make_local_cluster
from pbft_tpu.utils import ConsensusSpans, MetricsRegistry, Tracer
from pbft_tpu.utils import trace_schema

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- registry semantics ------------------------------------------------------


def test_histogram_bucket_edges_le_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("pbft_verify_batch_size")
    assert h.edges == trace_schema.BATCH_SIZE_BUCKETS
    h.observe(1)  # exactly the first edge -> first bucket (le)
    h.observe(2)  # exactly the second edge
    h.observe(3)  # between 2 and 4 -> third bucket
    h.observe(5000)  # above the last edge -> +Inf slot
    assert h.counts[0] == 1 and h.counts[1] == 1 and h.counts[2] == 1
    assert h.counts[-1] == 1
    assert h.count == 4 and h.sum == 1 + 2 + 3 + 5000


def test_render_prometheus_shape():
    reg = MetricsRegistry(labels={"replica": "7"})
    reg.counter("pbft_frames_in_total").inc(3)
    reg.gauge("pbft_verify_queue_depth").set(2)
    h = reg.histogram("pbft_verify_seconds")
    h.observe(0.0004)
    h.observe(99.0)
    text = reg.render_prometheus()
    assert '# TYPE pbft_frames_in_total counter' in text
    assert 'pbft_frames_in_total{replica="7"} 3' in text
    assert 'pbft_verify_queue_depth{replica="7"} 2' in text
    # Cumulative buckets: the 0.0004 observation is in every le >= 0.0005
    # bucket; 99.0 only in +Inf.
    assert 'pbft_verify_seconds_bucket{replica="7",le="0.0005"} 1' in text
    assert 'pbft_verify_seconds_bucket{replica="7",le="10"} 1' in text
    assert 'pbft_verify_seconds_bucket{replica="7",le="+Inf"} 2' in text
    assert 'pbft_verify_seconds_count{replica="7"} 2' in text
    assert text.endswith("\n")


def test_disabled_registry_is_inert_and_unknown_names_fail():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("pbft_frames_in_total")
    h = reg.histogram("pbft_verify_seconds")
    c.inc(5)
    h.observe(1.0)
    assert c.value == 0 and h.count == 0  # one attribute check, no work
    reg.set_enabled(True)
    c.inc(5)
    assert c.value == 5
    with pytest.raises(KeyError):
        reg.counter("pbft_not_in_manifest_total")
    with pytest.raises(KeyError):
        reg.histogram("pbft_frames_in_total")  # wrong type for the name


# -- consensus-phase span lifecycle ------------------------------------------


def test_span_lifecycle_over_simulated_three_phase_commit():
    """A 4-replica simulated cluster commits one request; every replica's
    spans must close with per-phase observations, the primary's span must
    carry the request stamp, and the consensus_span events must match the
    manifest schema."""
    from pbft_tpu.consensus.simulation import Cluster

    sink = io.StringIO()
    tracer = Tracer(sink)
    cluster = Cluster(n=4)
    registries = []
    for i, replica in enumerate(cluster.replicas):
        reg = MetricsRegistry(labels={"replica": str(i)})
        replica.phase_hook = ConsensusSpans(
            reg, tracer=tracer, replica=i
        ).on_phase
        registries.append(reg)
    cluster.submit("op", timestamp=1)
    cluster.run()
    assert cluster.committed_result(1) == "awesome!"
    for i, reg in enumerate(registries):
        assert reg.counter("pbft_executed_total").value == 1
        assert reg.histogram("pbft_phase_prepare_seconds").count == 1
        assert reg.histogram("pbft_phase_commit_seconds").count == 1
        assert reg.histogram("pbft_phase_reply_seconds").count == 1
        assert reg.histogram("pbft_request_reply_seconds").count == 1
        # request -> pre-prepare exists only on the primary (replica 0).
        expected = 1 if i == 0 else 0
        assert reg.histogram("pbft_phase_pre_prepare_seconds").count == expected
    events = [json.loads(line) for line in sink.getvalue().splitlines()]
    spans = [e for e in events if e["ev"] == "consensus_span"]
    assert len(spans) == 4  # one closed span per replica
    schema = trace_schema.EVENT_SCHEMAS["consensus_span"]
    for e in spans:
        fields = set(e)
        assert schema["required"] <= fields
        assert fields <= schema["required"] | schema["optional"]
        assert (e["view"], e["seq"]) == (0, 1)
    assert sum("request" in e for e in spans) == 1  # primary only


def test_span_tracker_bounds_open_spans():
    reg = MetricsRegistry()
    spans = ConsensusSpans(reg, max_open=8)
    for seq in range(1, 50):
        spans.on_phase("pre_prepare", 0, seq)
    assert len(spans._open) == 8  # oldest evicted, no leak
    spans.on_phase("executed", 0, 1)  # evicted slot: closing is a no-op
    assert reg.counter("pbft_executed_total").value == 0


def test_span_clock_injection_measures_phase_deltas():
    t = [100.0]
    reg = MetricsRegistry()
    spans = ConsensusSpans(reg, clock=lambda: t[0])
    spans.on_phase("request", 0, 1)
    t[0] = 100.25
    spans.on_phase("pre_prepare", 0, 1)
    t[0] = 100.5
    spans.on_phase("prepared", 0, 1)
    t[0] = 101.0
    spans.on_phase("committed", 0, 1)
    t[0] = 101.5
    spans.on_phase("executed", 0, 1)
    for name, want in (
        ("pbft_phase_pre_prepare_seconds", 0.25),
        ("pbft_phase_prepare_seconds", 0.25),
        ("pbft_phase_commit_seconds", 0.5),
        ("pbft_phase_reply_seconds", 0.5),
        ("pbft_request_reply_seconds", 1.5),
    ):
        h = reg.histogram(name)
        assert h.count == 1 and abs(h.sum - want) < 1e-9, name


# -- /metrics scrape surface -------------------------------------------------


def _scrape(port: int) -> str:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=5
    ) as resp:
        assert resp.status == 200
        assert resp.headers["Content-Type"].startswith("text/plain")
        return resp.read().decode()


def test_cluster_metrics_endpoint_end_to_end():
    """A 4-replica pbftd cluster with --metrics-port: one committed client
    request must surface per-phase latency histograms and verify counters
    on the scrape endpoint, with manifest names."""
    from pbft_tpu.net import LocalCluster, PbftClient

    with LocalCluster(n=4, verifier="cpu", metrics_ports=True) as cluster:
        client = PbftClient(cluster.config)
        try:
            req = client.request("observe me")
            assert client.wait_result(req.timestamp, timeout=20) == "awesome!"
        finally:
            client.close()
        # The client's quorum is f+1 replies: wait for the slowest replica.
        deadline = time.monotonic() + 10
        while True:
            texts = [_scrape(port) for port in cluster.metrics_ports]
            if all(
                'pbft_executed_total{replica="%d"} 1' % i in text
                for i, text in enumerate(texts)
            ):
                break
            assert time.monotonic() < deadline, texts
            time.sleep(0.1)
    for i, text in enumerate(texts):
        label = '{replica="%d"}' % i
        assert f"pbft_request_reply_seconds_count{label} 1" in text
        assert f"pbft_phase_prepare_seconds_count{label} 1" in text
        assert f"pbft_phase_commit_seconds_count{label} 1" in text
        assert "# TYPE pbft_verify_batches_total counter" in text
        assert f"pbft_executed_total{label} 1" in text
    # The request stamp exists only on the primary.
    assert 'pbft_phase_pre_prepare_seconds_count{replica="0"} 1' in texts[0]
    assert 'pbft_phase_pre_prepare_seconds_count{replica="1"} 0' in texts[1]


def test_verifier_service_metrics_endpoint():
    """The service's scrape surface: one wire batch must show up in the
    verify counters/histograms under replica="service"."""
    from pbft_tpu.net.service import VerifierService

    svc = VerifierService(backend="cpu", metrics_port=0).start()
    try:
        host, port = svc.address.rsplit(":", 1)
        with socket.create_connection((host, int(port)), timeout=5) as s:
            s.sendall(
                (2).to_bytes(4, "big") + bytes(128) * 2
            )  # two zero items: invalid, rejected
            verdicts = s.recv(2)
        assert verdicts == b"\x00\x00"
        text = _scrape(svc.metrics_listen_port)
    finally:
        svc.stop()
    label = '{replica="service"}'
    assert f"pbft_verify_batches_total{label} 1" in text
    assert f"pbft_verify_items_total{label} 2" in text
    assert f"pbft_verify_rejected_total{label} 2" in text
    assert f"pbft_verify_batch_size_count{label} 1" in text


# -- the timeline analyzer against the checked-in r5 fixtures ----------------


def test_consensus_timeline_on_r5_fixture():
    """scripts/consensus_timeline.py must produce a per-(view, seq) phase
    breakdown from benchmarks/traces_r5_svc_cfg2 WITHOUT modification
    (acceptance criterion: the legacy executed-counter estimates)."""
    fixture = REPO / "benchmarks" / "traces_r5_svc_cfg2"
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "consensus_timeline.py"),
            str(fixture),
            "--json",
        ],
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    assert result["replicas"] == [0, 1, 2, 3, 4, 5, 6]
    assert len(result["slots"]) >= 100
    first = result["slots"][0]
    assert first["view"] == 0 and first["seq"] == 1
    # Every reporting replica carries an executed stamp (the estimate).
    for rep in first["replicas"].values():
        assert "executed" in rep and rep.get("estimated") is True
    assert "executed_spread_ms" in first


def test_consensus_timeline_merges_span_events(tmp_path):
    """Span-bearing traces get full per-phase durations and straggler
    flags across replicas."""
    base = 1000.0
    for rid, lag in ((0, 0.0), (1, 0.5)):  # replica 1 lags 500ms
        path = tmp_path / f"replica-{rid}.jsonl"
        ev = {
            "ts": base + lag + 0.04,
            "ev": "consensus_span",
            "replica": rid,
            "view": 0,
            "seq": 1,
            "pre_prepare": base + lag,
            "prepared": base + lag + 0.01,
            "committed": base + lag + 0.03,
            "executed": base + lag + 0.04,
        }
        path.write_text(json.dumps(ev) + "\n")
    out = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "consensus_timeline.py"),
            str(tmp_path),
            "--json",
        ],
        capture_output=True,
        text=True,
        timeout=60,
    )
    assert out.returncode == 0, out.stderr
    result = json.loads(out.stdout)
    (slot,) = result["slots"]
    assert slot["stragglers"] == [1]
    assert abs(slot["executed_spread_ms"] - 500.0) < 1.0
    assert slot["replicas"]["0"]["durations"]["prepared->committed"] == 0.02
    assert result["straggler_counts"] == {"1": 1}


# -- wedged-async-verifier deadline (core/net.cc) ----------------------------


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_pbftd_verify_deadline_unwedges_cluster(tmp_path):
    """A verifier service that accepts batches but never replies used to
    stall pbftd forever (verify_inflight_ stuck true). With
    --verify-deadline-ms the daemon drops the wedged connection, re-verifies
    on the CPU safety net, commits anyway, and records
    verify_deadline_fired (trace event + counter)."""
    from pbft_tpu import native
    from pbft_tpu.net.client import PbftClient
    from pbft_tpu.net.launcher import free_ports, pbftd_path

    if not native.available():
        pytest.skip("native core not built")

    # The black hole: accepts connections, reads requests, never answers.
    blackhole = socket.socket()
    blackhole.bind(("127.0.0.1", 0))
    blackhole.listen(16)
    blackhole.settimeout(0.2)
    import threading

    wedged = True
    accepted = []

    def swallow():
        while wedged:
            try:
                conn, _ = blackhole.accept()
                accepted.append(conn)  # keep alive: no EOF, no reply
            except socket.timeout:
                continue
            except OSError:
                return

    t = threading.Thread(target=swallow, daemon=True)
    t.start()

    config, seeds = make_local_cluster(4, base_port=0)
    ports = free_ports(4)
    config = ClusterConfig(
        replicas=[
            type(r)(r.replica_id, r.host, ports[i], r.pubkey)
            for i, r in enumerate(config.replicas)
        ]
    )
    cfg_path = tmp_path / "network.json"
    cfg_path.write_text(config.to_json())
    target = "127.0.0.1:%d" % blackhole.getsockname()[1]
    procs = []
    try:
        for i in range(4):
            procs.append(
                subprocess.Popen(
                    [
                        str(pbftd_path()),
                        "--config", str(cfg_path),
                        "--id", str(i),
                        "--seed", seeds[i].hex(),
                        "--verifier", target,
                        "--verify-deadline-ms", "300",
                        "--trace", str(tmp_path / f"trace-{i}.jsonl"),
                    ],
                    stderr=subprocess.DEVNULL,
                )
            )
        client = PbftClient(config)
        try:
            # Commits despite every replica's verifier being wedged: each
            # batch unwedges via the 300 ms deadline + CPU safety net.
            assert client.request_with_retry("unwedge", timeout=60) == "awesome!"
        finally:
            client.close()
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            p.wait(timeout=10)
        wedged = False
        blackhole.close()
    fired = []
    for i in range(4):
        for line in (tmp_path / f"trace-{i}.jsonl").read_text().splitlines():
            e = json.loads(line)
            if e["ev"] == "verify_deadline_fired":
                fired.append(e)
                assert e["size"] >= 1 and e["age_secs"] >= 0.3
    assert fired, "no replica recorded a verify_deadline_fired event"


# -- Tracer hot-loop hardening (satellite) -----------------------------------


def test_tracer_survives_non_serializable_fields():
    class Weird:
        def __repr__(self):
            return "<weird>"

        __str__ = __repr__

    sink = io.StringIO()
    tracer = Tracer(sink)
    tracer.event("verify_batch", replica=0, size=1, rejected=0, secs=0.1,
                 oops=Weird(), raw=b"\xff")
    rec = json.loads(sink.getvalue())
    assert rec["oops"] == "<weird>"  # degraded via default=str, no throw
    assert rec["ev"] == "verify_batch"

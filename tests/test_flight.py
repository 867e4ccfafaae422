"""Black-box flight recorder (ISSUE 9): cross-runtime dump format parity,
the branch-cheap-when-disabled overhead guard, and the end-to-end
contract — a replica killed mid-run ships a dump that decodes into
ordered protocol events, and a failing chaos-soak seed ships one per
replica."""

import re
import pathlib
import subprocess
import sys
import time

import pytest

from pbft_tpu import native
from pbft_tpu.utils import flight, trace_schema

REPO = pathlib.Path(__file__).resolve().parent.parent


# -- format + overhead guard (satellite: tier-1, no cluster) -----------------


def test_python_recorder_roundtrip_byte_exact(tmp_path):
    rec = flight.FlightRecorder(capacity=8)
    for i in range(1, 6):
        rec.record("executed", view=0, seq=i, peer=-1, t_ns=1000 + i)
    rec.record("view_change_sent", view=1, t_ns=2000)
    path = tmp_path / "py.flight"
    assert rec.dump(str(path)) == 6
    raw = path.read_bytes()
    decoded = flight.decode_bytes(raw)
    assert [r["seq"] for r in decoded[:5]] == [1, 2, 3, 4, 5]
    assert decoded[5]["event"] == "view_change_sent"
    assert decoded[5]["view"] == 1
    # Byte-exact round trip: decode -> re-encode reproduces the file.
    rows = [(r["t_ns"], r["ev"], r["peer"], r["view"], r["seq"]) for r in decoded]
    assert flight.encode_records(rows) == raw


def test_python_recorder_ring_evicts_oldest():
    rec = flight.FlightRecorder(capacity=4)
    for i in range(1, 11):
        rec.record("committed", seq=i)
    snap = rec.snapshot()
    assert len(snap) == 4
    assert [r[4] for r in snap] == [7, 8, 9, 10]


def test_python_recorder_disabled_is_noop():
    rec = flight.FlightRecorder(capacity=4, enabled=False)
    rec.record("executed", seq=1)
    rec.record_phase("executed", 0, 1)
    assert len(rec) == 0


def test_decode_rejects_garbage(tmp_path):
    bad = tmp_path / "bad.flight"
    bad.write_bytes(b"NOTAFLIGHTDUMP....")
    with pytest.raises(ValueError):
        flight.decode_file(str(bad))
    truncated = tmp_path / "trunc.flight"
    rec = flight.FlightRecorder(capacity=4)
    rec.record("executed", seq=1)
    truncated.write_bytes(rec.encode()[:-5])
    with pytest.raises(ValueError):
        flight.decode_file(str(truncated))


def test_cxx_record_path_checks_enabled_first():
    """The overhead guard's source half (mirrors the metrics rule: one
    attribute check when disabled): FlightRecorder::record must branch on
    the enabled flag BEFORE doing any work."""
    src = (REPO / "core" / "flight.cc").read_text()
    body = re.search(
        r"void FlightRecorder::record\([^)]*\)\s*\{(.*?)\n\}", src, re.S
    )
    assert body, "FlightRecorder::record not found"
    first_stmt = body.group(1).strip().splitlines()[0]
    assert "enabled_.load" in first_stmt and "return" in first_stmt, (
        "record() must open with the disabled check, got: " + first_stmt
    )


@pytest.mark.skipif(not native.available(), reason="native core not built")
def test_native_recorder_disabled_and_roundtrip(tmp_path):
    """The native ring through capi: disabled record is a no-op; an
    enabled ring dump decodes with the PYTHON decoder (cross-runtime
    format parity) and re-encodes byte-exactly."""
    lib = native.lib()
    for fn in ("pbft_flight_configure", "pbft_flight_dump"):
        if not hasattr(lib, fn):
            pytest.fail(f"stale libpbftcore.so: missing {fn}; rebuild")
    native.flight_configure(0)  # disabled
    native.flight_record(trace_schema.FLIGHT_EVENT_IDS["executed"], 0, 1, -1)
    assert native.flight_total() == 0
    try:
        native.flight_configure(8)
        for i in range(1, 13):  # wraps the ring: only the last 8 survive
            native.flight_record(
                trace_schema.FLIGHT_EVENT_IDS["executed"], 0, i, -1
            )
        path = tmp_path / "native.flight"
        assert native.flight_dump(str(path)) == 8
        decoded = flight.decode_file(str(path))
        assert [r["seq"] for r in decoded] == list(range(5, 13))
        assert all(r["event"] == "executed" for r in decoded)
        assert all(
            b["t_ns"] >= a["t_ns"] for a, b in zip(decoded, decoded[1:])
        )
        rows = [
            (r["t_ns"], r["ev"], r["peer"], r["view"], r["seq"])
            for r in decoded
        ]
        assert flight.encode_records(rows) == path.read_bytes()
    finally:
        native.flight_configure(0)


def test_flight_dump_cli(tmp_path):
    rec = flight.FlightRecorder(capacity=16)
    rec.record("pre_prepare", view=0, seq=1)
    rec.record("executed", view=0, seq=1)
    path = tmp_path / "cli.flight"
    rec.dump(str(path))
    out = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "flight_dump.py"), str(path)],
        capture_output=True,
        text=True,
    )
    assert out.returncode == 0, out.stderr
    assert "pre_prepare" in out.stdout and "executed" in out.stdout
    bad = subprocess.run(
        [
            sys.executable,
            str(REPO / "scripts" / "flight_dump.py"),
            str(tmp_path / "missing.flight"),
        ],
        capture_output=True,
        text=True,
    )
    assert bad.returncode == 2


# -- the black-box contract against real daemons ------------------------------


PHASE_RANK = {
    "pre_prepare": 0,
    "prepared": 1,
    "committed": 2,
    "executed": 3,
}


def _assert_protocol_order(records):
    """Chronological ring + per-sequence phase ordering."""
    assert records, "empty black box"
    assert all(
        b["t_ns"] >= a["t_ns"] for a, b in zip(records, records[1:])
    ), "flight dump not chronological"
    per_seq = {}
    for r in records:
        if r["event"] in PHASE_RANK:
            per_seq.setdefault((r["view"], r["seq"]), []).append(
                PHASE_RANK[r["event"]]
            )
    assert per_seq, "no consensus-phase records in the black box"
    for key, ranks in per_seq.items():
        assert ranks == sorted(ranks), (
            f"phase order violated at (view, seq)={key}: {ranks}"
        )


@pytest.mark.skipif(not native.available(), reason="native core not built")
@pytest.mark.parametrize("net_threads", [1, 2])
def test_killed_replica_ships_black_box(net_threads, tmp_path):
    """Kill a replica mid-run (SIGTERM, the chaos-soak kill path): its
    flight dump exists, decodes, and shows ordered protocol events —
    request_rx through executed — from the dead process."""
    from pbft_tpu.net import LocalCluster, PbftClient

    flight_dir = tmp_path / "flight"
    with LocalCluster(
        n=4, verifier="cpu", net_threads=net_threads, flight_dir=str(flight_dir)
    ) as cluster:
        client = PbftClient(cluster.config)
        try:
            for i in range(3):
                req = client.request(f"op-{i}")
                assert client.wait_result(req.timestamp, timeout=20) == "awesome!"
            cluster.kill(2)  # a backup: SIGTERM -> dump on the way down
            deadline = time.monotonic() + 10
            dump = flight_dir / "replica-2.flight"
            while not dump.exists() and time.monotonic() < deadline:
                time.sleep(0.05)
            records = flight.decode_file(str(dump))
            _assert_protocol_order(records)
            events = {r["event"] for r in records}
            assert "executed" in events
            # The backup verified batches and replied to the client.
            assert "verify_batch" in events
            assert "reply_tx" in events
        finally:
            client.close()


def test_chaos_soak_failure_ships_black_boxes(tmp_path):
    """A failing soak seed collects one flight dump per replica (the
    acceptance contract: a failing seed ships with its black box). Over
    the fault budget — f+1 colluding equivocators — the run MUST fail
    (safety trip or liveness miss), and every dump must decode."""
    sys.path.insert(0, str(REPO / "scripts"))
    import chaos_soak
    from pbft_tpu.consensus.faults import FaultEvent, FaultSchedule

    schedule = FaultSchedule(
        [
            FaultEvent(1, "set_fault", (0, "equivocate")),
            FaultEvent(1, "set_fault", (1, "equivocate")),
        ]
    )
    res = chaos_soak.run_one(
        seed=1,
        n=4,
        steps=200,
        schedule=schedule,
        submit_every=4,
        recovery_steps=120,
        flight_dir=str(tmp_path / "bb"),
    )
    assert res["ok"] is False, "f+1 equivocators must break the run"
    dumps = res.get("flight_dumps")
    assert dumps and len(dumps) == 4
    saw_events = False
    for path in dumps:
        records = flight.decode_file(path)
        if records:
            saw_events = True
            assert all(
                b["t_ns"] >= a["t_ns"] for a, b in zip(records, records[1:])
            )
    assert saw_events, "no replica recorded any protocol event"

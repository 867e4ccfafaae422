"""chaos_bench (ISSUE 12): perf-under-faults on real clusters.

Tier-1 keeps a fast smoke (fault-free arm end to end: cluster + gateway
+ firehose + bench_compare-shaped row) plus the pure join/latency units;
the full fault schedules (crash+heal, mute primary, gateway kill) run
behind @slow.
"""

from __future__ import annotations

import pathlib
import sys

import pytest

from pbft_tpu import native

sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "scripts"))

import chaos_bench  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native core not built"
)


def test_view_change_latency_join():
    """The cross-replica convergence span: first view_timer_fired opens,
    first new_view_installed closes; interleaved fires (other replicas'
    timers) do not reopen or double-count."""
    events = [
        {"ts": 10.0, "ev": "view_timer_fired", "replica": 1},
        {"ts": 10.1, "ev": "view_timer_fired", "replica": 2},
        {"ts": 10.5, "ev": "new_view_installed", "replica": 1},
        {"ts": 10.6, "ev": "new_view_installed", "replica": 2},  # no span open
        {"ts": 20.0, "ev": "view_timer_fired", "replica": 3},
        {"ts": 20.25, "ev": "new_view_installed", "replica": 3},
        {"ts": 30.0, "ev": "view_timer_fired", "replica": 1},  # never resolves
        {"ts": 31.0, "ev": "verify_batch", "replica": 1},  # ignored
    ]
    lat = chaos_bench.view_change_latencies_ms(events)
    assert lat == [pytest.approx(500.0), pytest.approx(250.0)]
    assert chaos_bench.view_change_latencies_ms([]) == []


def test_completion_bars_cover_every_arm():
    assert set(chaos_bench.COMPLETION_BAR) == set(chaos_bench.ARMS)
    assert chaos_bench.COMPLETION_BAR["crash-backup"] == 100.0
    assert chaos_bench.COMPLETION_BAR["gateway-kill"] == 100.0


def _run(arm, **kw):
    args = dict(
        n=4, clients=4, requests_each=15, window=8, batch=32,
        batch_flush_us=2000, gateways=1, vc_timeout_ms=500,
        admission_inflight=0, admission_backlog=0, fault_at_s=0.5,
        heal_at_s=1.5, deadline_s=150.0, seed=7, blackbox_dir=None,
    )
    args.update(kw)
    return chaos_bench.run_arm_traced(
        arm, args["n"], args["clients"], args["requests_each"],
        args["window"], args["batch"], args["batch_flush_us"],
        args["gateways"], args["vc_timeout_ms"],
        args["admission_inflight"], args["admission_backlog"],
        args["fault_at_s"], args["heal_at_s"], args["deadline_s"],
        args["seed"], args["blackbox_dir"],
    )


def test_chaos_bench_smoke_fault_free():
    """Tier-1 smoke: the fault-free arm end to end — a real cluster, a
    real gateway, the failover-capable load driver, and a
    bench_compare-compatible row with the ISSUE 12 fields."""
    row = _run("fault-free")
    assert row["ok"] and row["completed_pct"] == 100.0
    assert row["requests"] == 4 * 15
    for field in (
        "requests_per_sec", "rounds_per_sec", "reply_p50_ms",
        "reply_p99_ms", "view_changes_started", "overload_rejections",
        "gateway_failovers", "client_failovers", "vc_latency_ms",
    ):
        assert field in row, field
    assert row["view_changes_started"] == 0  # fault-free: no storm
    # bench_compare accepts the row (shape contract with scale_curve).
    import json
    import tempfile

    import bench_compare

    with tempfile.TemporaryDirectory() as tmp:
        p = pathlib.Path(tmp)
        (p / "new.jsonl").write_text(json.dumps(row) + "\n")
        (p / "old.jsonl").write_text(json.dumps(row) + "\n")
        assert (
            bench_compare.main(
                [str(p / "old.jsonl"), str(p / "new.jsonl")]
            )
            == 0
        )


@pytest.mark.slow
def test_chaos_bench_full_schedules():
    """The full fault schedules: crash-a-backup + heal completes 100%
    with a measured recovery; the mute ("stuttering") primary converges
    with BOUNDED view changes and a reported latency distribution; the
    gateway kill keeps completion at 100% through client failovers."""
    # Loads sized to OUTLAST the fault offsets (~1.4k req/s on this box:
    # 8 x 400 ~= 2.3 s of sustained fire vs a 0.5 s fault) so the fault
    # genuinely lands mid-run.
    crash = _run(
        "crash-backup", clients=8, requests_each=400, fault_at_s=0.5,
        heal_at_s=1.2,
    )
    assert crash["ok"] and crash["completed_pct"] == 100.0
    assert crash["killed_replica"] == 3

    storm = _run("stutter-primary", clients=8, requests_each=40)
    assert storm["ok"]
    assert storm["view_changes_started"] >= 1
    # Bounded: backoff + retransmission + forwarded-request re-aim —
    # never an unbounded escalation storm (generous bound; each of 3
    # honest replicas suspects once or twice).
    assert storm["view_changes_started"] <= 24
    assert storm["vc_latency_ms"]["count"] >= 1

    kill = _run(
        "gateway-kill", clients=8, requests_each=400, gateways=2,
        fault_at_s=0.5,
    )
    assert kill["ok"] and kill["completed_pct"] == 100.0
    assert kill["client_failovers"] >= 1

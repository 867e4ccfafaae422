"""Cluster-health introspection (ISSUE 16).

Two halves:

1. The detector library is pure — every detector must TRIP on a
   synthetic bad history (a silent stall, an fd ramp, a forked chain
   digest, a wedged view change, a saturated inbox) and stay QUIET on a
   healthy one. The synthetic histories are built from the same
   health-document shape pbftd serves on /status.
2. Live smoke: ``pbft_top --gate --once`` against a real LocalCluster —
   exit 0 on a healthy loaded cluster, exit 1 with a machine-readable
   silent-stall verdict when the primary is muted and holds sealed work
   it can never execute.
"""

from __future__ import annotations

import json
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from pbft_tpu import native  # noqa: E402
from pbft_tpu.analysis import health  # noqa: E402

PBFT_TOP = REPO / "scripts" / "pbft_top.py"


# -- synthetic history builders ----------------------------------------------

def _doc(executed=0, committed=None, inbox=0, sealed=0, waiting=0,
         view=0, in_vc=False, rss=100 << 20, fds=20, wal=4096,
         digest="aa" * 32):
    return {
        "health_version": 1,
        "executed_upto": executed,
        "committed_upto": executed if committed is None else committed,
        "inbox_depth": inbox,
        "sealed_unexecuted": sealed,
        "waiting_requests": waiting,
        "view": view,
        "in_view_change": in_vc,
        "rss_bytes": rss,
        "open_fds": fds,
        "wal_disk_bytes": wal,
        "chain_digest": digest,
    }


def _history(per_tick, n=4, dt=1.0):
    """history from per_tick(t_index, rid) -> doc (or None to omit)."""
    out = []
    t = 0.0
    i = 0
    while True:
        docs = {}
        for rid in range(n):
            doc = per_tick(i, rid)
            if doc is not None:
                docs[rid] = doc
        if not docs and i > 0:
            break
        out.append({"t": t, "replicas": docs})
        t += dt
        i += 1
    return out


def _healthy_history(ticks=12, n=4):
    """Steady execution, flat resources, matching digests."""
    return _history(
        lambda i, rid: _doc(executed=10 * i, inbox=2 if i % 3 else 0)
        if i < ticks else None,
        n=n,
    )


# -- 1. detectors ------------------------------------------------------------

def test_detectors_quiet_on_healthy_history():
    assert health.run_detectors(_healthy_history()) == []


def test_silent_stall_trips_and_names_the_replica():
    """Replica 2's executed_upto goes flat with sealed work pending; the
    others keep executing. One verdict, pinned to replica 2."""
    def tick(i, rid):
        if i >= 10:
            return None
        if rid == 2:
            return _doc(executed=30, sealed=4)
        return _doc(executed=30 + 10 * i)
    verdicts = health.detect_silent_stall(_history(tick), stall_seconds=5)
    assert [v["replica"] for v in verdicts] == [2]
    v = verdicts[0]
    assert v["detector"] == "silent-stall"
    assert v["evidence"]["flat_seconds"] >= 5
    assert v["evidence"]["pending"] == 4


def test_silent_stall_quiet_when_idle():
    """Flat executed_upto with NOTHING pending is an idle cluster, not a
    stall — and a momentarily-drained queue resets the clock."""
    hist = _history(lambda i, rid: _doc(executed=30) if i < 10 else None)
    assert health.detect_silent_stall(hist, stall_seconds=5) == []
    # pending blips that never span the threshold: quiet too
    hist = _history(
        lambda i, rid: _doc(executed=30, inbox=1 if i % 2 else 0)
        if i < 10 else None)
    assert health.detect_silent_stall(hist, stall_seconds=5) == []


def test_resource_leak_trips_on_fd_ramp():
    """Six fds/second, forever climbing: robust slope over the floor."""
    def tick(i, rid):
        if i >= 12:
            return None
        return _doc(executed=10 * i, fds=20 + (6 * i if rid == 1 else 0))
    verdicts = health.detect_resource_leak(_history(tick))
    assert [v["replica"] for v in verdicts] == [1]
    assert verdicts[0]["evidence"]["metric"] == "open_fds"
    assert verdicts[0]["evidence"]["slope_per_s"] > 0


def test_resource_leak_quiet_on_noise_and_transients():
    # breathing RSS around a flat baseline
    def breathe(i, rid):
        if i >= 12:
            return None
        return _doc(executed=10 * i, rss=(100 << 20) + (i % 3) * (1 << 20))
    assert health.detect_resource_leak(_history(breathe)) == []
    # one wild reading cannot fake a trend past the median slope
    def spike(i, rid):
        if i >= 12:
            return None
        return _doc(executed=10 * i, fds=200 if i == 6 else 20)
    assert health.detect_resource_leak(_history(spike)) == []
    # zero readings mean "no data", never a growth baseline
    def zeros(i, rid):
        if i >= 12:
            return None
        return _doc(executed=10 * i, rss=0, wal=0)
    assert health.detect_resource_leak(_history(zeros)) == []


def test_divergence_trips_on_forked_digest():
    """Same committed_upto, different chain digests — a safety violation
    the moment it appears, reported once per (floor, grouping)."""
    def tick(i, rid):
        if i >= 6:
            return None
        return _doc(executed=50, digest="bb" * 32 if rid == 3 else "aa" * 32)
    verdicts = health.detect_divergence(_history(tick))
    assert len(verdicts) == 1  # deduped across the 6 identical snapshots
    v = verdicts[0]
    assert v["detector"] == "divergence"
    groups = v["evidence"]["groups"]
    assert groups[0]["replicas"] == ["0", "1", "2"]  # majority first
    assert groups[1]["replicas"] == ["3"]


def test_divergence_quiet_on_lag():
    """A replica BEHIND the others (different committed_upto) is lag,
    not divergence."""
    def tick(i, rid):
        if i >= 6:
            return None
        return _doc(executed=20 if rid == 3 else 50,
                    digest="cc" * 32 if rid == 3 else "aa" * 32)
    assert health.detect_divergence(_history(tick)) == []


def test_stuck_view_change_trips():
    def tick(i, rid):
        if i >= 10:
            return None
        return _doc(executed=30, view=4, in_vc=(rid == 0))
    verdicts = health.detect_stuck_view_change(_history(tick), stall_seconds=5)
    assert [v["replica"] for v in verdicts] == [0]
    assert verdicts[0]["evidence"]["view"] == 4


def test_stuck_view_change_quiet_when_views_advance():
    """in_view_change held but the view number climbing = the backoff
    ladder doing its job, not a wedge."""
    def tick(i, rid):
        if i >= 10:
            return None
        return _doc(executed=30, view=i // 2, in_vc=True)
    assert health.detect_stuck_view_change(
        _history(tick), stall_seconds=5) == []


def test_queue_saturation_trips_and_clears():
    def tick(i, rid):
        if i >= 10:
            return None
        return _doc(executed=10 * i, inbox=600 if rid == 1 else 3)
    verdicts = health.detect_queue_saturation(_history(tick))
    assert [v["replica"] for v in verdicts] == [1]
    # dips below the watermark reset the sustain clock
    def dip(i, rid):
        if i >= 10:
            return None
        return _doc(executed=10 * i, inbox=600 if i % 3 else 10)
    assert health.detect_queue_saturation(_history(dip)) == []


def test_run_detectors_concatenates_and_threads_thresholds():
    """One history carrying a stall AND a fork yields both verdicts; a
    looser stall threshold silences the stall but not the fork."""
    def tick(i, rid):
        if i >= 10:
            return None
        return _doc(executed=30, sealed=2 if rid == 0 else 0,
                    digest="dd" * 32 if rid == 1 else "aa" * 32)
    verdicts = health.run_detectors(_history(tick), stall_seconds=5)
    assert {v["detector"] for v in verdicts} == {"silent-stall", "divergence"}
    loose = health.run_detectors(_history(tick), stall_seconds=100)
    assert {v["detector"] for v in loose} == {"divergence"}


def test_theil_sen_slope():
    assert health.theil_sen_slope([]) is None
    assert health.theil_sen_slope([(0, 1)]) is None
    assert health.theil_sen_slope(
        [(0, 0), (1, 2), (2, 4), (3, 6)]) == pytest.approx(2.0)
    # median robustness: one outlier does not drag the slope
    pts = [(0, 0), (1, 1), (2, 2), (3, 3), (4, 1000)]
    assert health.theil_sen_slope(pts) < 10


def test_dead_replica_is_no_data_not_zeros():
    """Snapshots missing a replica (down mid-poll) contribute no points:
    no detector may fabricate a verdict from absence."""
    def tick(i, rid):
        if i >= 10:
            return None
        if rid == 3 and i >= 3:
            return None  # replica 3 dies after t=2
        return _doc(executed=10 * i, inbox=1)
    assert health.run_detectors(_history(tick)) == []


def _sim_history(mute_primary, ticks=60):
    """Drive the deterministic simulator and snapshot the same document
    shape chaos_soak's --health-gate builds (sealed_unexecuted is the
    primary's assigned-but-unexecuted watermark)."""
    from pbft_tpu.consensus.simulation import Cluster

    c = Cluster(n=4, seed=16, app=lambda op, seq: op)
    if mute_primary:
        c.set_fault(0, "mute")
    c.submit("sim-doomed", to_replica=0)
    history = []
    for t in range(ticks):
        c.run(max_steps=5)
        history.append({
            "t": float(t),
            "replicas": {
                r.id: {
                    "executed_upto": r.executed_upto,
                    "committed_upto": r.committed_upto,
                    "inbox_depth": r.pending_count(),
                    "sealed_unexecuted": max(
                        0, r.seq_counter - r.executed_upto),
                    "waiting_requests": 0,
                    "chain_digest": r.committed_chain.hex(),
                }
                for r in c.replicas
            },
        })
    return history


def test_silent_stall_trips_on_simulated_muted_primary():
    """The injected-stall validity check: a muted sim primary seals a
    targeted request it can never broadcast — the detector must trip on
    replica 0, and the identical un-muted run must stay quiet."""
    stalled = _sim_history(mute_primary=True)
    verdicts = health.detect_silent_stall(stalled, stall_seconds=20)
    assert any(v["replica"] == 0 for v in verdicts), verdicts
    assert health.detect_divergence(stalled) == []

    clean = _sim_history(mute_primary=False)
    assert health.detect_silent_stall(clean, stall_seconds=20) == []
    assert health.detect_divergence(clean) == []


# -- 2. live pbft_top gate smoke ---------------------------------------------

pytestmark_live = pytest.mark.skipif(
    not native.available(), reason="native core not built")


def _run_top_gate(ports, stall_seconds=2, window_s=4):
    targets = ",".join(f"127.0.0.1:{p}" for p in ports)
    return subprocess.run(
        [sys.executable, str(PBFT_TOP), "--targets", targets,
         "--gate", "--once", "--interval", "0.5",
         "--stall-seconds", str(stall_seconds), "--window-s", str(window_s)],
        capture_output=True, text=True, timeout=120,
    )


@pytestmark_live
def test_pbft_top_gate_passes_healthy_cluster():
    from pbft_tpu.net.client import PbftClient
    from pbft_tpu.net.launcher import LocalCluster

    with LocalCluster(n=4, metrics_ports=True) as c:
        cl = PbftClient(c.config)
        req = cl.request("health-smoke")
        assert cl.wait_result(req.timestamp, timeout=30) is not None
        proc = _run_top_gate(c.metrics_ports)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is True and verdict["verdicts"] == []
    assert verdict["snapshots"] >= 2


@pytestmark_live
def test_pbft_top_gate_catches_muted_primary_stall():
    """The acceptance scenario: primary muted at launch seals a request
    it can never execute — completion metrics are silent, but the gate
    must exit 1 with a silent-stall verdict naming replica 0."""
    from pbft_tpu.net.client import PbftClient
    from pbft_tpu.net.launcher import LocalCluster

    with LocalCluster(n=4, metrics_ports=True,
                      faults={0: "mute"}) as c:
        cl = PbftClient(c.config)
        cl.request("doomed", to_replica=0)  # sealed by 0, never executed
        proc = _run_top_gate(c.metrics_ports)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False
    stalls = [v for v in verdict["verdicts"]
              if v["detector"] == "silent-stall"]
    assert any(str(v["replica"]) == "0" for v in stalls), verdict

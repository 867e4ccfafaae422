"""Durable replica recovery (ISSUE 15): the write-ahead log, the
no-contradiction (amnesia) guards, crash-restart in the simulator with
the S5 invariant, and kill -9 -> restart-from-disk on real daemons.

The on-disk format is the cross-runtime contract: the golden-bytes test
pins the Python encoder, core_test.cc pins the same goldens for the C++
encoder, and the real-cluster tests replay pbftd-written logs with the
Python decoder — byte identity by construction, checked three ways.
"""

import json
import re
import time
from pathlib import Path

import pytest

from pbft_tpu.consensus import wal as W
from pbft_tpu.consensus.config import make_local_cluster
from pbft_tpu.consensus.invariants import InvariantChecker, InvariantViolation
from pbft_tpu.consensus.simulation import Cluster


# -- the on-disk format -------------------------------------------------------


def test_record_golden_bytes(tmp_path):
    """Pin the exact file image (header + view + checkpoint + vote): the
    same goldens are asserted by core_test.cc test_wal_roundtrip, so the
    two encoders cannot drift without one of the pins going red."""
    p = tmp_path / "replica-0.wal"
    w = W.WriteAheadLog(str(p))
    w.note_view(3, True, 4)
    w.note_vote(W.WAL_VOTE_PREPARE, 3, 17, "ab" * 32)
    w.note_checkpoint(16, "PAYLOAD", "[]")
    w.flush()  # checkpoint -> compaction: canonical ordering on disk
    data = p.read_bytes()
    assert data[:8] == b"PBFTWAL1"
    assert data[8:12] == (1).to_bytes(4, "little")
    # view record: tag 0x01, len 17, i64 view, u8 ivc, i64 pending
    off = 12
    assert data[off] == W.WAL_REC_VIEW
    assert data[off + 1 : off + 5] == (17).to_bytes(4, "little")
    assert data[off + 5 : off + 13] == (3).to_bytes(8, "little")
    assert data[off + 13] == 1
    assert data[off + 14 : off + 22] == (4).to_bytes(8, "little")
    off += 5 + 17
    # checkpoint record: tag 0x03, seq 16, "PAYLOAD", "[]"
    assert data[off] == W.WAL_REC_CHECKPOINT
    body = data[off + 5 :]
    assert body[:8] == (16).to_bytes(8, "little")
    assert body[8:12] == (7).to_bytes(4, "little")
    assert body[12:19] == b"PAYLOAD"
    assert body[19:23] == (2).to_bytes(4, "little")
    assert body[23:25] == b"[]"
    off += 5 + 8 + 4 + 7 + 4 + 2
    # vote record: tag 0x02, kind prepare, view 3, seq 17, raw digest
    assert data[off] == W.WAL_REC_VOTE
    assert data[off + 5] == W.WAL_VOTE_PREPARE
    assert data[off + 6 : off + 14] == (3).to_bytes(8, "little")
    assert data[off + 14 : off + 22] == (17).to_bytes(8, "little")
    assert data[off + 22 : off + 54] == bytes.fromhex("ab" * 32)
    assert off + 54 == len(data)


def test_replay_contradiction_and_compaction(tmp_path):
    p = tmp_path / "replica-1.wal"
    w = W.WriteAheadLog(str(p))
    assert w.note_vote(W.WAL_VOTE_PREPARE, 0, 1, "11" * 32)
    assert w.note_vote(W.WAL_VOTE_PREPARE, 0, 1, "11" * 32)  # idempotent
    assert not w.note_vote(W.WAL_VOTE_PREPARE, 0, 1, "22" * 32)  # refused
    assert w.note_vote(W.WAL_VOTE_COMMIT, 0, 20, "33" * 32)
    w.note_checkpoint(16, '{"seq":16}', '[{"replica":0}]')
    w.flush()
    st = W.replay(str(p))
    assert st.checkpoint == (16, '{"seq":16}', '[{"replica":0}]')
    # the seq-1 vote fell beneath the checkpoint; seq-20 survives
    assert st.votes == {(W.WAL_VOTE_COMMIT, 0, 20): "33" * 32}
    # reopening replays + compacts; the guards stay armed
    w2 = W.WriteAheadLog(str(p))
    assert not w2.note_vote(W.WAL_VOTE_COMMIT, 0, 20, "44" * 32)
    assert w2.recovered.checkpoint == st.checkpoint
    assert st.max_pre_prepare_seq() == 0


def test_torn_tail_tolerated(tmp_path):
    p = tmp_path / "replica-2.wal"
    w = W.WriteAheadLog(str(p))
    w.note_vote(W.WAL_VOTE_PREPARE, 0, 5, "aa" * 32)
    w.flush()
    whole = W.replay(str(p))
    with open(p, "ab") as fh:  # a kill -9 mid-append: partial record
        fh.write(bytes([W.WAL_REC_VOTE]) + (49).to_bytes(4, "little") + b"xx")
    torn = W.replay(str(p))
    assert torn.votes == whole.votes
    # ...and reopening heals the tear (recovery compaction)
    W.WriteAheadLog(str(p))
    healed = W.replay(str(p))
    assert healed.votes == whole.votes
    with pytest.raises(ValueError):
        W.decode_bytes(b"NOTAWAL0" + bytes(8))


# -- simulator crash-restart + S5 --------------------------------------------


def _wal_cluster(n=4, checkpoint_interval=4):
    config, seeds = make_local_cluster(n)
    config.checkpoint_interval = checkpoint_interval
    return Cluster(config=config, seeds=seeds, wal=True)


def test_sim_restart_from_disk_rejoins_without_revoting():
    c = _wal_cluster()
    checker = InvariantChecker(c)
    for i in range(6):
        c.submit(f"op-{i + 1}")
        c.run()
        checker.check()
    assert c.replicas[3].low_mark == 4  # a stable checkpoint exists
    votes_before = dict(c.wals[3].state.votes)
    assert votes_before  # votes above the checkpoint floor persist
    c.crash(3)
    c.submit("op-7")
    c.run()
    checker.check()
    c.restart(3, from_disk=True)
    r3 = c.replicas[3]
    # Re-joined the SAME view at the stable-checkpoint floor.
    assert r3.view == 0
    assert r3.executed_upto == r3.low_mark == 4
    assert r3.wal is c.wals[3]
    # Catch up through the ordinary protocol; S5 holds throughout.
    for i in range(7, 12):
        c.submit(f"op-{i + 1}")
        c.run()
        checker.check()
    assert r3.executed_upto == c.replicas[0].executed_upto
    assert r3.state_digest == c.replicas[0].state_digest
    # "Without re-voting": every pre-crash persisted vote kept its digest.
    for key, digest in votes_before.items():
        after = c.wals[3].state.votes.get(key)
        assert after is None or after == digest  # None = checkpoint-pruned


def test_sim_fresh_restart_absorbed_by_quorum():
    """Satellite 1's other half: an AMNESIAC restart mid-round is
    absorbed by the quorum (it spends fault budget — the <= f window the
    old revive() silently relied on, now documented)."""
    c = _wal_cluster()
    checker = InvariantChecker(c)
    for i in range(4):
        c.submit(f"op-{i + 1}")
        c.run()
        checker.check()
    c.crash(3)
    c.restart(3, from_disk=False)  # blank disk, blank state
    r3 = c.replicas[3]
    assert r3.executed_upto == 0 and r3.view == 0
    for i in range(4, 10):
        c.submit(f"op-{i + 1}")
        c.run()
        checker.check()  # S1-S3 hold: 3 honest survivors carry it
    assert c.replicas[0].executed_upto == 10
    # the amnesiac caught up via state transfer like any fresh replica
    assert r3.executed_upto == 10


def test_s5_checker_validity():
    """A checker that can't fail is not a checker: fabricate a persisted
    pre-crash vote that contradicts what replica 1 is about to send —
    the S5 pass must trip on the very next prepare."""
    c = _wal_cluster()
    checker = InvariantChecker(c)
    c.restart_votes[1] = {(W.WAL_VOTE_PREPARE, 0, 1): "00" * 32}
    c.submit("op-1")
    with pytest.raises(InvariantViolation, match="restart-vote"):
        for _ in range(40):
            c.step()
            checker.check()
    assert checker.violations


def test_chaos_soak_crash_restart_smoke():
    import pathlib
    import sys

    sys.path.insert(
        0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts")
    )
    import chaos_soak

    res = chaos_soak.run_one(3, 4, 120, crash_restart=True)
    assert res["ok"], res


@pytest.mark.slow
def test_chaos_soak_crash_restart_matrix():
    """The acceptance matrix (ISSUE 15): >= 10 seeds x {n=4, n=7} x
    {sig, mac} crash-restart schedules with zero S1-S3/L1/S5
    violations."""
    import pathlib
    import sys

    sys.path.insert(
        0, str(pathlib.Path(__file__).resolve().parent.parent / "scripts")
    )
    import chaos_soak

    for seed in range(10):
        for n in (4, 7):
            for mode in ("sig", "mac"):
                res = chaos_soak.run_one(
                    seed, n, 300, mode=mode, crash_restart=True
                )
                assert res["ok"], res


# -- real daemons: kill -9 and restart from disk ------------------------------


def _metrics_lines(cluster, rid):
    log = Path(cluster.tmpdir.name) / f"replica-{rid}.log"
    return [
        json.loads(x)
        for x in re.findall(
            r"^\{.*\}$", log.read_text(errors="replace"), re.M
        )
        if '"replica"' in x
    ]


def _drive(client, lo, hi):
    for i in range(lo, hi):
        client.request(f"op-{i}")


def _drive_one_connection(client, lo, hi):
    """The same requests over ONE connection, a window in flight: what a
    dial-back client has to do in front of the shard tier, which keeps
    order a connection and not across connections (ROADMAP D2)."""
    client.request_many([f"op-{i}" for i in range(lo, hi)], window=8, timeout=60)


def _wait_metric(cluster, rid, pred, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        lines = _metrics_lines(cluster, rid)
        if lines and pred(lines[-1]):
            return lines[-1]
        time.sleep(0.3)
    raise AssertionError(
        f"replica {rid} never satisfied predicate; last: "
        f"{_metrics_lines(cluster, rid)[-1:]}\n{cluster.logs()[-4000:]}"
    )


@pytest.mark.parametrize(
    "net_threads,drive",
    [(1, _drive), (2, _drive_one_connection)],
    ids=["loop", "shards-one-connection"],
)
def test_kill9_restart_from_disk(net_threads, drive):
    """kill -9 a backup mid-run, restart with its WAL: it re-joins the
    SAME view, reports recovered_from_wal, never contradicts a persisted
    vote (checked by replaying the C++-written log with the PYTHON
    decoder — the byte-identity proof against the reference), and catches
    the suffix up via state transfer. On both socket layers."""
    from pbft_tpu.net.client import PbftClient
    from pbft_tpu.net.launcher import LocalCluster

    with LocalCluster(
        n=4, metrics_every=1, wal=True, vc_timeout_ms=2000, net_threads=net_threads
    ) as cluster:
        client = PbftClient(cluster.config)
        drive(client, 1, 41)  # checkpoints at 16 and 32
        wal_path = Path(cluster.tmpdir.name) / "wal" / "replica-3.wal"
        # Killed once its first checkpoint is stable (the metrics line says
        # so: low_mark), not 0.6 s after the last request: on a busy machine
        # a replica may not have got there and the log would hold none.
        _wait_metric(cluster, 3, lambda m: m["low_mark"] >= 16)
        cluster.kill(3, hard=True)
        st = W.replay(str(wal_path))
        assert st.checkpoint is not None and st.checkpoint[0] >= 16
        votes_before = dict(st.votes)
        pre_lines = len(_metrics_lines(cluster, 3))
        cluster.revive(3, from_disk=True)
        last = _wait_metric(
            cluster,
            3,
            lambda m: m.get("recovered_from_wal") is True
            and len(_metrics_lines(cluster, 3)) > pre_lines,
        )
        assert last["wal_enabled"] is True
        assert last["view"] == 0  # the SAME view
        assert last["executed_upto"] >= st.checkpoint[0]
        drive(client, 41, 61)
        last = _wait_metric(
            cluster, 3, lambda m: m.get("executed_upto", 0) >= 60
        )
        # No re-voting: the post-restart log still holds the pre-crash
        # digests for every surviving (kind, view, seq).
        st_after = W.replay(str(wal_path))
        for key, digest in votes_before.items():
            after = st_after.votes.get(key)
            assert after is None or after == digest


def test_revive_fresh_default_and_from_disk_guard():
    """Satellite 1 regression: the DEFAULT revive stays fresh-state even
    on a wal-enabled cluster (the log is wiped so replay finds nothing),
    the quorum absorbs the amnesiac while it catches up, and
    from_disk=True on a wal-less cluster refuses loudly."""
    from pbft_tpu.net.client import PbftClient
    from pbft_tpu.net.launcher import LocalCluster

    with LocalCluster(
        n=4, metrics_every=1, wal=True, vc_timeout_ms=2000
    ) as cluster:
        client = PbftClient(cluster.config)
        _drive(client, 1, 25)
        time.sleep(0.6)
        cluster.kill(3, hard=True)
        pre_lines = len(_metrics_lines(cluster, 3))
        cluster.revive(3)  # DEFAULT: fresh state, wal wiped
        last = _wait_metric(
            cluster,
            3,
            lambda m: len(_metrics_lines(cluster, 3)) > pre_lines,
        )
        assert last["recovered_from_wal"] is False
        # The amnesiac rejoined; the cluster (quorum of 3) kept serving
        # and the fresh replica catches up via checkpoint/state transfer.
        _drive(client, 25, 45)
        _wait_metric(cluster, 3, lambda m: m.get("executed_upto", 0) >= 32)

    with LocalCluster(n=4) as cluster2:
        cluster2.kill(1)
        with pytest.raises(ValueError, match="wal=True"):
            cluster2.revive(1, from_disk=True)
        cluster2.revive(1)  # fresh revive still fine

"""Unit tests for the deterministic replica core + in-memory cluster sims.

Covers SURVEY.md §4 items 1-2: message-in/message-out truth tables, the
4-replica happy path, quorum thresholds, duplicate/conflicting pre-prepares,
exactly-once timestamps, reordering, Byzantine signers, and checkpoint GC.
"""

import dataclasses

import pytest

from pbft_tpu.consensus import (
    Checkpoint,
    ClientRequest,
    Commit,
    Prepare,
    PrePrepare,
    from_wire,
    to_wire,
)
from pbft_tpu.consensus.config import make_local_cluster
from pbft_tpu.consensus.replica import Broadcast, Replica, Reply, Send
from pbft_tpu.consensus.simulation import Cluster, cpu_verifier
from pbft_tpu.crypto import ref


def mk_request(op="op", t=1, client="127.0.0.1:9000"):
    return ClientRequest(operation=op, timestamp=t, client=client)


def test_wire_roundtrip():
    req = mk_request()
    for msg in [
        req,
        PrePrepare(view=0, seq=1, digest=req.digest(), requests=(req,), replica=0, sig="ab"),
        Prepare(view=0, seq=1, digest="d", replica=2, sig="cd"),
        Commit(view=0, seq=1, digest="d", replica=3, sig="ef"),
        Checkpoint(seq=16, digest="s", replica=1, sig="01"),
    ]:
        frame = to_wire(msg)
        assert int.from_bytes(frame[:4], "big") == len(frame) - 4
        assert from_wire(frame[4:]) == msg


def test_signable_excludes_signature():
    p1 = Prepare(view=0, seq=1, digest="d", replica=2, sig="")
    p2 = Prepare(view=0, seq=1, digest="d", replica=2, sig="aabb")
    assert p1.signable() == p2.signable()
    assert p1.signable() != Prepare(view=0, seq=2, digest="d", replica=2).signable()


def fresh_replica(n=4, rid=0):
    config, seeds = make_local_cluster(n)
    return Replica(config, rid, seeds[rid]), config, seeds


def test_primary_pre_prepare_broadcast():
    r, config, _ = fresh_replica(rid=0)
    actions = r.on_client_request(mk_request())
    # Exactly one PrePrepare broadcast; the primary sends NO prepare — its
    # pre-prepare stands in for it (PBFT §4.2), so prepared certificates
    # always contain 2f+1 distinct replicas. (The reference had the primary
    # log its own prepare, reference src/behavior.rs:63-124, which shrinks
    # the certificate to 2f distinct members.)
    assert [type(a).__name__ for a in actions] == ["Broadcast"]
    assert isinstance(actions[0].msg, PrePrepare)
    assert r.pre_prepares[(0, 1)].digest == actions[0].msg.digest
    assert (0, 1) not in r.prepares


def test_backup_forwards_request_to_primary():
    r, _, _ = fresh_replica(rid=1)
    actions = r.on_client_request(mk_request())
    assert actions == [Send(0, mk_request())]


def test_quorum_thresholds_exact():
    """prepared needs 2f PREPAREs; committed-local needs 2f+1 COMMITs."""
    r, config, seeds = fresh_replica(n=4, rid=1)  # backup; f=1
    primary = Replica(config, 0, seeds[0])
    [pp_bcast] = primary.on_client_request(mk_request())
    pp = pp_bcast.msg

    out = r._dispatch(pp)
    assert any(isinstance(a.msg, Prepare) for a in out if isinstance(a, Broadcast))
    key = (0, 1)
    assert not r._prepared(key)  # own prepare only: 1 < 2f=2

    def signed_prepare(rid):
        other = Replica(config, rid, seeds[rid])
        return other._sign(Prepare(view=0, seq=1, digest=pp.digest, replica=rid))

    out = r._dispatch(signed_prepare(2))
    # second matching prepare reaches 2f -> replica multicasts COMMIT
    assert r._prepared(key)
    assert any(isinstance(a.msg, Commit) for a in out if isinstance(a, Broadcast))
    assert not r._committed_local(key)  # 1 own commit < 2f+1

    def signed_commit(rid):
        other = Replica(config, rid, seeds[rid])
        return other._sign(Commit(view=0, seq=1, digest=pp.digest, replica=rid))

    r._dispatch(signed_commit(0))
    assert not r._committed_local(key)  # 2 < 3
    out = r._dispatch(signed_commit(3))
    assert r._committed_local(key)  # 3 == 2f+1
    assert [a for a in out if isinstance(a, Reply)], "execution must reply"


def test_conflicting_pre_prepare_rejected():
    r, config, seeds = fresh_replica(n=4, rid=1)
    primary = Replica(config, 0, seeds[0])
    [pp_bcast] = primary.on_client_request(mk_request(op="first"))
    r._dispatch(pp_bcast.msg)
    # Equivocation: same (v, n), different digest.
    req2 = mk_request(op="second", t=2)
    evil = primary._sign(
        PrePrepare(view=0, seq=1, digest=req2.digest(), requests=(req2,), replica=0)
    )
    assert r._dispatch(evil) == []
    assert r.pre_prepares[(0, 1)].digest == pp_bcast.msg.digest


def test_pre_prepare_from_non_primary_rejected():
    r, config, seeds = fresh_replica(n=4, rid=2)
    backup = Replica(config, 1, seeds[1])
    req = mk_request()
    fake = backup._sign(
        PrePrepare(view=0, seq=1, digest=req.digest(), requests=(req,), replica=1)
    )
    assert r._dispatch(fake) == []
    assert (0, 1) not in r.pre_prepares


def test_watermark_rejects_out_of_window():
    r, config, seeds = fresh_replica(n=4, rid=1)
    primary = Replica(config, 0, seeds[0])
    req = mk_request()
    beyond = primary._sign(
        PrePrepare(
            view=0,
            seq=config.watermark_window + 1,
            digest=req.digest(),
            requests=(req,),
            replica=0,
        )
    )
    assert r._dispatch(beyond) == []


def test_bad_signature_dropped_via_verdicts():
    r, config, seeds = fresh_replica(n=4, rid=1)
    primary = Replica(config, 0, seeds[0])
    [pp_bcast] = primary.on_client_request(mk_request())
    tampered = dataclasses.replace(pp_bcast.msg, sig="00" * 64)
    r.receive(tampered)
    items = r.pending_items()
    verdicts = cpu_verifier(items)
    assert verdicts == [False]
    assert r.deliver_verdicts(verdicts) == []
    assert r.counters["sig_rejected"] == 1
    assert (0, 1) not in r.pre_prepares


# -- cluster simulations ----------------------------------------------------


def test_happy_path_f1():
    c = Cluster(n=4)
    req = c.submit("deposit 100")
    c.run()
    assert c.committed_result(req.timestamp) == "awesome!"
    # every replica executed once, identical state digests
    assert [r.executed_upto for r in c.replicas] == [1, 1, 1, 1]
    digests = {r.state_digest for r in c.replicas}
    assert len(digests) == 1
    # all 4 replicas replied (client needs only f+1=2 to match)
    assert len(c.replies_for(req.timestamp)) == 4


def test_happy_path_f2_multiple_requests():
    c = Cluster(n=7)
    reqs = [c.submit(f"op-{i}", client=f"127.0.0.1:{9000+i%4}") for i in range(5)]
    c.run(max_steps=500)
    for req in reqs:
        c.committed_result(req.timestamp)
    assert all(r.executed_upto == 5 for r in c.replicas)
    assert len({r.state_digest for r in c.replicas}) == 1


def test_request_to_backup_is_forwarded():
    c = Cluster(n=4)
    req = c.submit("via-backup", to_replica=2)
    c.run()
    assert c.committed_result(req.timestamp) == "awesome!"


def test_duplicate_request_cached_reply():
    c = Cluster(n=4)
    req = c.submit("pay", timestamp=7)
    c.run()
    first_replies = len(c.replies_for(7))
    c.submit("pay", timestamp=7)  # exact retransmission
    c.run()
    assert c.replicas[0].counters["duplicate_requests"] >= 1
    # primary resends its cached reply; no replica re-executes
    assert len(c.replies_for(7)) == first_replies + 1
    assert all(r.executed_upto == 1 for r in c.replicas)


def test_reordered_delivery_still_commits():
    # Distinct clients: a PBFT client has one outstanding request at a time;
    # concurrent requests from one client may legitimately be deduplicated
    # by the timestamp guard when reordered.
    c = Cluster(n=4, shuffle=True, seed=1234)
    reqs = [c.submit(f"op-{i}", client=f"127.0.0.1:{9100+i}") for i in range(4)]
    c.run(max_steps=500)
    for req in reqs:
        c.committed_result(req.timestamp)
    assert len({r.state_digest for r in c.replicas}) == 1


def test_byzantine_signer_isolated():
    """BASELINE.json config 5 in miniature: replica 3 corrupts every signature;
    consensus proceeds (f=1 tolerates it) and rejections are counted."""
    c = Cluster(n=4)

    def corrupt(src, msg):
        if src == 3 and getattr(msg, "sig", ""):
            return dataclasses.replace(msg, sig="ff" * 64)
        return msg

    c.outbound_mutator = corrupt
    req = c.submit("survive")
    c.run()
    assert c.committed_result(req.timestamp) == "awesome!"
    rejected = sum(r.counters["sig_rejected"] for r in c.replicas)
    assert rejected > 0


def test_crashed_replica_tolerated():
    c = Cluster(n=4)
    for dst in range(4):
        c.dropped_links.add((3, dst))
        c.dropped_links.add((dst, 3))
    req = c.submit("minority-crash")
    c.run()
    assert c.committed_result(req.timestamp) == "awesome!"
    assert c.replicas[3].executed_upto == 0


def test_checkpoint_advances_watermark_and_truncates():
    c = Cluster(n=4)
    interval = c.config.checkpoint_interval
    for i in range(interval):
        c.submit(f"op-{i}")
        c.run(max_steps=500)
    for r in c.replicas:
        assert r.executed_upto == interval
        assert r.low_mark == interval
        assert all(k[1] > interval for k in r.pre_prepares)
        assert all(k[1] > interval for k in r.prepares)
        assert all(k[1] > interval for k in r.commits)
        assert r.counters["checkpoints_stable"] == 1


def test_prepared_certificate_excludes_primary_prepare():
    """A forged 'prepare' claiming to be from the primary must not count
    toward the 2f threshold (quorum-intersection regression)."""
    r, config, seeds = fresh_replica(n=4, rid=1)
    primary = Replica(config, 0, seeds[0])
    [pp_bcast] = primary.on_client_request(mk_request())
    pp = pp_bcast.msg
    r._dispatch(pp)  # r logs its own prepare (1 backup prepare)
    key = (0, 1)
    # A prepare from the primary (even correctly signed) does not count.
    primary_prep = primary._sign(
        Prepare(view=0, seq=1, digest=pp.digest, replica=0)
    )
    r._dispatch(primary_prep)
    assert not r._prepared(key)
    # A second *backup* prepare does.
    other = Replica(config, 2, seeds[2])
    r._dispatch(other._sign(Prepare(view=0, seq=1, digest=pp.digest, replica=2)))
    assert r._prepared(key)


def test_lagging_replica_adopts_stable_checkpoint():
    """Watermark advancement past unexecuted seqs must not deadlock
    execution (regression: pruning pending_execution without adopting the
    proven checkpoint left executed_upto stuck forever)."""
    c = Cluster(n=4)
    interval = c.config.checkpoint_interval
    # Replica 3 misses everything up to the checkpoint.
    for dst in range(3):
        c.dropped_links.add((dst, 3))
        c.dropped_links.add((3, dst))
    for i in range(interval):
        c.submit(f"op-{i}")
        c.run(max_steps=500)
    assert c.replicas[3].executed_upto == 0
    # Reconnect and run through the NEXT checkpoint boundary: checkpoints
    # are broadcast at execution time, so the healed replica adopts the
    # stable checkpoint (state-transfer-lite) when the cluster next
    # checkpoints — the lag is bounded by one interval instead of forever.
    c.dropped_links.clear()
    reqs = [c.submit(f"healed-{i}") for i in range(interval)]
    for _ in range(interval):
        c.run(max_steps=1000)
    for req in reqs:
        c.committed_result(req.timestamp)
    r3 = c.replicas[3]
    assert r3.low_mark == 2 * interval
    assert r3.executed_upto == 2 * interval
    assert r3.state_digest == c.replicas[0].state_digest


@pytest.mark.slow  # compiles the batch verifier inside the sim (~3 min cold)
def test_jax_verifier_cluster_equivalence():
    """Same scenario through the JAX batch verifier: identical outcome
    (SURVEY.md §7 'determinism at the FFI boundary')."""
    c = Cluster(n=4, verifier="jax")
    req = c.submit("tpu-arm")
    c.run()
    assert c.committed_result(req.timestamp) == "awesome!"
    assert len({r.state_digest for r in c.replicas}) == 1

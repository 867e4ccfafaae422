"""View change (PBFT §4.4) — the capability the reference stubbed entirely
(its View was a constant with no mutation API, reference src/view.rs:1-13).

Covers: primary failure -> new view elects primary 1 and in-flight requests
survive; O-computation with prepared certificates and null gaps; the f+1
join rule; Byzantine new-primary rejection (forged O); checkpoint-anchored
view changes."""

import dataclasses

from pbft_tpu.consensus.config import make_local_cluster
from pbft_tpu.consensus.messages import (
    Message,
    NewView,
    PrePrepare,
    ViewChange,
    null_request,
)
from pbft_tpu.consensus.replica import Broadcast, Replica
from pbft_tpu.consensus.simulation import Cluster


def test_view_change_after_primary_crash():
    c = Cluster(n=4)
    c.crash(0)
    # Backups' request timers fire (runtime responsibility) -> view change.
    c.trigger_view_change([1, 2, 3])
    c.run(max_steps=500)
    live = [c.replicas[i] for i in (1, 2, 3)]
    assert all(r.view == 1 for r in live)
    assert all(not r.in_view_change for r in live)
    assert c.primary_id == 1
    # The cluster commits client requests in the new view.
    req = c.submit("after view change")
    c.run(max_steps=500)
    assert c.committed_result(req.timestamp) == "awesome!"
    assert len({r.state_digest for r in live}) == 1


def test_in_flight_prepared_request_survives_view_change():
    """A request prepared (but not committed) in view 0 must be re-issued
    in view 1 and execute exactly once (PBFT §4.4 safety across views)."""
    c = Cluster(n=4)
    req = c.submit("survivor")
    # Deliver pre-prepares + prepares, but drop every COMMIT so the round
    # prepares without committing anywhere.
    c.outbound_mutator = lambda src, msg: (
        None if type(msg).__name__ == "Commit" else msg
    )
    c.run(max_steps=500)
    assert all(r.executed_upto == 0 for r in c.replicas)
    prepared_somewhere = [
        r.id for r in c.replicas if r._prepared((0, 1))
    ]
    assert prepared_somewhere, "at least one replica must have prepared"
    # Primary goes silent; commits flow again in the new view.
    c.outbound_mutator = None
    c.crash(0)
    c.trigger_view_change([1, 2, 3])
    c.run(max_steps=500)
    live = [c.replicas[i] for i in (1, 2, 3)]
    assert all(r.view == 1 for r in live)
    # The survivor executed in the new view, exactly once.
    assert c.committed_result(req.timestamp) == "awesome!"
    assert all(r.executed_upto >= 1 for r in live)
    assert all(r.counters["executed"] == 1 for r in live)
    assert len({r.state_digest for r in live}) == 1


def test_join_rule_f_plus_one():
    """A replica whose timer never fired joins once f+1 others moved
    (PBFT §4.5.2): only replicas 1 and 2 trigger; replica 3 follows."""
    c = Cluster(n=4)
    c.crash(0)
    c.trigger_view_change([1, 2])  # f+1 = 2 explicit triggers
    c.run(max_steps=500)
    live = [c.replicas[i] for i in (1, 2, 3)]
    assert all(r.view == 1 for r in live)
    assert c.replicas[3].counters["view_changes_started"] == 1


def test_new_view_with_forged_o_rejected():
    """A Byzantine new primary cannot smuggle an unprepared request into O:
    backups recompute O from V and drop a mismatched NEW-VIEW."""
    config, seeds = make_local_cluster(4)
    replicas = [Replica(config, i, seeds[i]) for i in range(4)]
    # Gather legitimate VIEW-CHANGE messages for view 1 from replicas 2, 3
    # plus primary-elect 1's own.
    vcs = []
    for rid in (1, 2, 3):
        acts = replicas[rid].start_view_change()
        for a in acts:
            if isinstance(a, Broadcast) and isinstance(a.msg, ViewChange):
                vcs.append(a.msg)
    assert len(vcs) == 3
    # Replica 1 (new primary) would send O = [] (nothing prepared). Forge a
    # NEW-VIEW that injects a pre-prepare for an invented request.
    evil_req = null_request()
    forged_pp = replicas[1]._sign(
        PrePrepare(view=1, seq=1, digest=evil_req.digest(), requests=(evil_req,), replica=1)
    )
    forged = replicas[1]._sign(
        NewView(
            new_view=1,
            view_changes=tuple(vc.to_dict() for vc in vcs),
            pre_prepares=(forged_pp.to_dict(),),
            replica=1,
        )
    )
    out = replicas[2]._on_new_view(forged)
    assert out == []
    assert replicas[2].in_view_change  # still waiting for a valid NEW-VIEW
    assert replicas[2].view == 0


def test_view_change_after_checkpoint_anchors_min_s():
    """View change above a stable checkpoint: min-s comes from C and the
    new view resumes after it."""
    c = Cluster(n=4)
    interval = c.config.checkpoint_interval
    for i in range(interval):
        c.submit(f"op-{i}")
        c.run(max_steps=500)
    assert all(r.low_mark == interval for r in c.replicas)
    c.crash(0)
    c.trigger_view_change([1, 2, 3])
    c.run(max_steps=500)
    live = [c.replicas[i] for i in (1, 2, 3)]
    assert all(r.view == 1 for r in live)
    req = c.submit("post-checkpoint-vc")
    c.run(max_steps=500)
    assert c.committed_result(req.timestamp) == "awesome!"
    assert all(r.executed_upto == interval + 1 for r in live)


def test_cascading_view_change_skips_failed_primary():
    """If the new primary is also dead, a second view change reaches
    replica 2 (view 2). Needs f=2 (n=7) so two crashed replicas stay
    within the fault budget."""
    c = Cluster(n=7)
    c.crash(0)
    c.crash(1)
    live_ids = [2, 3, 4, 5, 6]
    c.trigger_view_change(live_ids, new_view=1)
    c.run(max_steps=1000)
    # View 1's primary (replica 1) is dead: no NEW-VIEW arrives; timers
    # fire again for view 2.
    c.trigger_view_change(live_ids, new_view=2)
    c.run(max_steps=1000)
    live = [c.replicas[i] for i in live_ids]
    assert all(r.view == 2 for r in live)
    assert all(not r.in_view_change for r in live)
    assert c.primary_id == 2
    req = c.submit("two hops later")
    c.run(max_steps=1000)
    assert c.committed_result(req.timestamp) == "awesome!"


def test_watermark_jump_adopts_checkpoint_certificate():
    """Chaos-soak regression (ISSUE 5, seed 13): a replica whose watermark
    advances through a NEW-VIEW's min-s (not its own 2f+1 checkpoint
    collection) must ADOPT the certifying checkpoint proof. Before the
    fix it kept the stale pre-jump proof, so its next VIEW-CHANGE claimed
    last_stable_seq = min_s with a certificate for the OLD seq — honest
    validators reject that, and with two such replicas in an f=1 cluster
    no view change can ever gather 2f+1 valid votes again (a permanent
    liveness loss)."""
    c = Cluster(n=4)
    interval = c.config.checkpoint_interval
    # Replica 3 misses a whole checkpoint interval.
    c.crash(3)
    for i in range(interval):
        c.submit(f"op-{i}")
        c.run(max_steps=500)
    assert all(c.replicas[i].low_mark == interval for i in (0, 1, 2))
    assert c.replicas[3].low_mark == 0
    # It returns and joins a view change: min-s (= interval) reaches it
    # via the NEW-VIEW evidence, not via 2f+1 checkpoints of its own.
    c.uncrash(3)
    c.trigger_view_change([1, 2, 3])
    c.run(max_steps=500)
    r3 = c.replicas[3]
    assert r3.view == 1 and r3.low_mark == interval
    # The adopted certificate must certify the NEW stable seq...
    assert r3.stable_proof, "no certificate adopted on the watermark jump"
    assert all(d["seq"] == interval for d in r3.stable_proof)
    assert len(r3.stable_proof) >= 2 * c.config.f + 1
    # ...so its next VIEW-CHANGE validates at its peers.
    acts = r3.start_view_change()
    vcs = [
        a.msg
        for a in acts
        if isinstance(a, Broadcast) and isinstance(a.msg, ViewChange)
    ]
    assert vcs and vcs[0].last_stable_seq == interval
    assert c.replicas[1]._validate_view_change(vcs[0])


def test_view_change_message_roundtrip():
    config, seeds = make_local_cluster(4)
    r = Replica(config, 1, seeds[1])
    [bcast] = [
        a
        for a in r.start_view_change()
        if isinstance(a, Broadcast) and isinstance(a.msg, ViewChange)
    ]
    from pbft_tpu.consensus.messages import from_wire, to_wire

    frame = to_wire(bcast.msg)
    back = from_wire(frame[4:])
    assert back == bcast.msg
    assert back.signable() == bcast.msg.signable()


def test_stable_digest_ignores_byzantine_first_checkpoint():
    """A view-change proof may carry extra correctly-signed checkpoints with
    a bogus digest; the adopted stable digest must be the one with a 2f+1
    majority, not whichever entry the (possibly Byzantine) sender listed
    first (PBFT §4.4 / §5.3 — digest adoption during the watermark jump)."""
    from pbft_tpu.consensus.messages import Checkpoint

    config, seeds = make_local_cluster(4)
    replicas = [Replica(config, i, seeds[i]) for i in range(4)]
    good = "ab" * 32
    evil = "cd" * 32
    # Replicas 1..3 certify `good` at seq 10; Byzantine replica 0 signs
    # `evil` for the same seq. All four signatures are genuine.
    proof = [
        replicas[0]._sign(Checkpoint(seq=10, digest=evil, replica=0)).to_dict()
    ] + [
        replicas[i]._sign(Checkpoint(seq=10, digest=good, replica=i)).to_dict()
        for i in (1, 2, 3)
    ]
    vc = replicas[1]._sign(
        ViewChange(
            new_view=1,
            last_stable_seq=10,
            checkpoint_proof=tuple(proof),
            prepared_proofs=(),
            replica=1,
        )
    )
    # The proof as a whole is valid (a 2f+1 majority on `good` exists)...
    assert replicas[2]._validate_view_change(vc)
    # ...but the stable digest must be the majority one, not proof[0]'s —
    # and the adopted certificate must carry ONLY the majority entries.
    digest, proof = replicas[2]._stable_cert_for([vc], 10)
    assert digest == good
    assert len(proof) == 3
    assert all(d["digest"] == good for d in proof)


def _signed_reply_dict(seeds, rid, ts, result="awesome!", view=0, client="c:1"):
    from pbft_tpu.consensus.messages import ClientReply
    from pbft_tpu.crypto import ref

    rep = ClientReply(
        view=view, timestamp=ts, client=client, replica=rid, result=result
    )
    return {**rep.to_dict(), "sig": ref.sign(seeds[rid], rep.signable()).hex()}


def test_client_reply_quorum_one_vote_per_replica():
    """f+1 reply quorum must count distinct replicas: duplicate replies from
    one replica (retransmissions) do not satisfy it (PBFT §4.1)."""
    import pytest

    from pbft_tpu.net.client import PbftClient

    config, seeds = make_local_cluster(4)
    client = PbftClient.__new__(PbftClient)
    client.config = config
    import threading

    client._new_reply = threading.Condition()
    # Three copies of replica 2's reply: one vote, no quorum.
    client.replies = [_signed_reply_dict(seeds, 2, 7)] * 3
    with pytest.raises(TimeoutError):
        client.wait_result(7, timeout=0.2)
    # A second distinct replica completes the f+1 = 2 quorum.
    client.replies.append(_signed_reply_dict(seeds, 3, 7))
    assert client.wait_result(7, timeout=0.2) == "awesome!"


def test_client_reply_quorum_rejects_forged_signatures():
    """The dial-back channel is forgeable; votes only count with a valid
    signature from the claimed replica. A forger who controls one replica
    (or none) cannot mint the f+1 quorum (PBFT §4.1, done for real —
    the reference had no signatures anywhere, src/behavior.rs:127)."""
    import pytest

    from pbft_tpu.net.client import PbftClient

    config, seeds = make_local_cluster(4)
    client = PbftClient.__new__(PbftClient)
    client.config = config
    import threading

    client._new_reply = threading.Condition()
    good = _signed_reply_dict(seeds, 2, 9)
    # Forgeries: replica 3's vote signed with replica 2's key; an unsigned
    # vote; a garbage signature. None may complete the quorum.
    wrong_key = dict(_signed_reply_dict(seeds, 2, 9))
    wrong_key["replica"] = 3
    unsigned = {**_signed_reply_dict(seeds, 3, 9), "sig": ""}
    garbage = {**_signed_reply_dict(seeds, 3, 9), "sig": "ab" * 64}
    client.replies = [good, wrong_key, unsigned, garbage]
    with pytest.raises(TimeoutError):
        client.wait_result(9, timeout=0.2)
    # The genuine second vote still works.
    client.replies.append(_signed_reply_dict(seeds, 3, 9))
    assert client.wait_result(9, timeout=0.2) == "awesome!"


def test_view_change_span_ordering_via_timeline(tmp_path):
    """View-change spans end to end in the simulator (ISSUE 9): wire each
    replica's phase/view hooks to per-replica tracers, crash the primary,
    and require consensus_timeline --check-invariants to (a) see the
    view events and (b) certify view_timer_fired -> view_change_sent ->
    new_view_installed ordering."""
    import pathlib
    import sys as _sys

    from pbft_tpu.utils.metrics import ConsensusSpans, MetricsRegistry
    from pbft_tpu.utils.trace import Tracer

    c = Cluster(n=4)
    files, tracers = {}, {}
    for r in c.replicas:
        fh = open(tmp_path / f"replica-{r.id}.jsonl", "w")
        files[r.id] = fh
        tracer = Tracer(fh)
        tracers[r.id] = tracer
        spans = ConsensusSpans(
            MetricsRegistry(enabled=False), tracer=tracer, replica=r.id
        )
        r.phase_hook = spans.on_phase

        def view_hook(ev, v, _t=tracer, _rid=r.id):
            if ev == "view_change_sent":
                _t.event("view_change_sent", replica=_rid, pending_view=v)
            else:
                _t.event("new_view_installed", replica=_rid, view=v)

        r.view_hook = view_hook
    # A committed request in view 0 produces spans on every replica.
    req0 = c.submit("before")
    c.run(max_steps=500)
    assert c.committed_result(req0.timestamp) == "awesome!"
    # Primary dies; the runtime-owned timers fire (emitted here, as the
    # real daemons do) and the view change runs.
    c.crash(0)
    for rid in (1, 2, 3):
        tracers[rid].event(
            "view_timer_fired", replica=rid, view=c.replicas[rid].view,
            backoff=2,
        )
    c.trigger_view_change([1, 2, 3])
    c.run(max_steps=500)
    req1 = c.submit("after")
    c.run(max_steps=500)
    assert c.committed_result(req1.timestamp) == "awesome!"
    for fh in files.values():
        fh.close()

    _sys.path.insert(0, str(pathlib.Path(__file__).parent.parent / "scripts"))
    import consensus_timeline

    result = consensus_timeline.main(
        [str(tmp_path), "--check-invariants", "--json", "--no-spread"]
    )
    assert result["invariant_problems"] == []
    assert result["view_events"] >= 9  # 3 fired + 3 sent + >=3 installed
    # Every live replica both campaigned and installed view 1.
    import json as _json

    events = []
    for p in sorted(tmp_path.glob("replica-*.jsonl")):
        events += [_json.loads(line) for line in p.read_text().splitlines()]
    installed = {
        e["replica"] for e in events if e["ev"] == "new_view_installed"
    }
    assert installed == {1, 2, 3}


def test_view_event_ordering_violations_flagged():
    """The checker is not vacuous: installed-before-fired and a backwards
    pending_view both trip check_view_events."""
    from pbft_tpu.consensus.invariants import check_view_events

    clean = [
        {"ts": 1.0, "ev": "view_timer_fired", "replica": 1, "view": 0,
         "backoff": 2},
        {"ts": 1.1, "ev": "view_change_sent", "replica": 1,
         "pending_view": 1},
        {"ts": 1.5, "ev": "new_view_installed", "replica": 1, "view": 1},
    ]
    assert check_view_events(clean) == []
    backwards = [
        {"ts": 0.5, "ev": "new_view_installed", "replica": 1, "view": 1},
        {"ts": 1.0, "ev": "view_timer_fired", "replica": 1, "view": 0,
         "backoff": 2},
    ]
    assert check_view_events(backwards)
    regressing = [
        {"ts": 1.0, "ev": "view_change_sent", "replica": 2,
         "pending_view": 3},
        {"ts": 2.0, "ev": "view_change_sent", "replica": 2,
         "pending_view": 2},
    ]
    assert check_view_events(regressing)
    installed_before_sent = [
        {"ts": 1.0, "ev": "view_change_sent", "replica": 3,
         "pending_view": 2},
        {"ts": 0.4, "ev": "new_view_installed", "replica": 3, "view": 2},
    ]
    assert check_view_events(installed_before_sent)


# -- view-change retransmission (ISSUE 12) ------------------------------------
#
# pbftd's timer policy (arm at T x level, retransmit before escalating, double
# a no-progress expiry, cap at 64: core/net.cc check_progress_timer) is held on
# real clusters by tests/test_integration.py
# test_mute_primary_bounded_view_change_storm and
# test_view_change_fires_under_accumulation_window.


def _direct_replicas(n=4):
    config, seeds = make_local_cluster(n, base_port=0)
    return [Replica(config, i, seeds[i]) for i in range(n)], config


def _deliver(replica, msg):
    """Feed one replica-to-replica message through the verify queue."""
    out = list(replica.receive(msg))
    out += replica.deliver_verdicts([True] * replica.pending_count())
    return out


def _own_view_change(actions):
    for a in actions:
        if isinstance(a, Broadcast) and isinstance(a.msg, ViewChange):
            return a.msg
    raise AssertionError("no ViewChange broadcast in actions")


def test_retransmit_view_change_is_verbatim_and_free():
    """retransmit_view_change re-broadcasts the SAME signed message: no
    counter moves, no re-signing, and outside a view change it is a
    no-op (ISSUE 12)."""
    replicas, _ = _direct_replicas()
    r = replicas[2]
    assert r.retransmit_view_change() == []  # not in a view change
    vc = _own_view_change(r.start_view_change())
    started = r.counters["view_changes_started"]
    out = r.retransmit_view_change()
    assert len(out) == 1 and isinstance(out[0], Broadcast)
    assert out[0].msg == vc  # verbatim: same content, same signature
    assert r.counters["view_changes_started"] == started


def test_primary_resends_cached_new_view_to_laggard():
    """A VIEW-CHANGE arriving for a view the receiver already LEADS is a
    laggard signalling it missed the NEW-VIEW broadcast: the primary
    answers with the cached NEW-VIEW, point-to-point, without
    recomputing O or re-broadcasting (ISSUE 12)."""
    replicas, config = _direct_replicas()
    r1, r2, r3 = replicas[1], replicas[2], replicas[3]
    vc2 = _own_view_change(r2.start_view_change())
    vc3 = _own_view_change(r3.start_view_change())
    out = list(r1.start_view_change())  # r1 logs its own VC
    out += _deliver(r1, vc2)
    out += _deliver(r1, vc3)  # 2f+1 = 3 -> NEW-VIEW built + view entered
    assert r1.view == 1 and not r1.in_view_change
    nv_broadcasts = [
        a
        for a in out
        if isinstance(a, Broadcast) and isinstance(a.msg, NewView)
    ]
    assert len(nv_broadcasts) == 1
    # Laggard r2 retransmits its VIEW-CHANGE (its timer fired again):
    # the primary resends the cached NEW-VIEW to r2 alone.
    from pbft_tpu.consensus.replica import Send

    resend = _deliver(r1, vc2)
    sends = [a for a in resend if isinstance(a, Send)]
    assert len(sends) == 1
    assert sends[0].dest == 2
    assert isinstance(sends[0].msg, NewView)
    assert sends[0].msg == nv_broadcasts[0].msg  # cached, not recomputed
    # No second broadcast, no double-entry.
    assert not any(
        isinstance(a, Broadcast) and isinstance(a.msg, NewView)
        for a in resend
    )
    assert r1.counters["view_changes_completed"] == 1
    # The resent NEW-VIEW actually installs the view on the laggard.
    for a in _deliver(r2, vc3):
        pass
    entered = _deliver(r2, sends[0].msg)
    del entered
    assert r2.view == 1 and not r2.in_view_change

"""Chaos layer (ISSUE 5): seeded chaotic transport, Byzantine behavior
modes, fault schedules, and the machine-checked safety/liveness invariants.

The structural claim under test: with AT MOST f faulty replicas — whatever
combination of crash, partition, link chaos, and Byzantine mode — the S1-S3
safety invariants hold at every scheduler step, and liveness returns once
the network heals. And the checker itself is VALID: an over-budget f+1
collusion must trip it (a checker that cannot fail proves nothing)."""

import sys
from pathlib import Path

import pytest

from pbft_tpu.consensus.faults import FaultEvent, FaultSchedule, random_schedule
from pbft_tpu.consensus.invariants import (
    InvariantChecker,
    InvariantViolation,
    check_spans,
)
from pbft_tpu.consensus.simulation import FAULT_MODES, Cluster, LinkChaos

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "scripts"))

from chaos_soak import run_one, validate_checker  # noqa: E402


def _echo(operation, seq):
    return operation


def _drive(cluster, checker, submitted, steps=300, stall_window=20):
    """Step until every submitted request is replied (or steps exhaust),
    checking safety each step. The two liveness actors the sim leaves to
    its driver run DECOUPLED, like their real counterparts: the client
    retransmits unreplied requests on a short cadence, and the replicas'
    view-change timers fire only on a full stall window — retransmitting
    and view-changing in the same breath would feed every retransmission
    into a round the new view immediately kills."""
    last = (0, -1)
    for t in range(steps):
        cluster.step()
        checker.check()
        if not checker.unreplied(submitted):
            return True
        if t % 8 == 5:  # client retransmission cadence (PBFT §4.1)
            for req in checker.unreplied(submitted):
                for rid in range(cluster.config.n):
                    if rid not in cluster.crashed:
                        cluster.submit(req.operation, client=req.client,
                                       timestamp=req.timestamp, to_replica=rid)
        executed = max(
            (r.executed_upto for r in cluster.replicas
             if r.id in checker.honest() and r.id not in cluster.crashed),
            default=0,
        )
        if executed > last[1]:
            last = (t, executed)
        elif t - last[0] >= stall_window:
            last = (t, executed)
            # Common target view (see chaos_soak.py): skewed per-replica
            # floors chasing +1 independently can livelock below 2f+1.
            target = 1 + max(
                (r.pending_view if r.in_view_change else r.view)
                for r in cluster.replicas
                if r.id not in cluster.crashed
            )
            cluster.trigger_view_change(new_view=target)
    return not checker.unreplied(submitted)


# -- transport upgrade ------------------------------------------------------


def test_chaos_transport_deterministic_replay():
    """Same seed => same delivery schedule => same final state, with
    delays, drops, and duplication all active."""
    outcomes = []
    for _ in range(2):
        c = Cluster(n=4, seed=42, shuffle=True, app=_echo)
        c.set_chaos(LinkChaos(drop_pct=0.1, dup_pct=0.1, delay_min=0, delay_max=3))
        checker = InvariantChecker(c)
        submitted = [c.submit(f"op-{i}", client=f"10.0.0.{i}:9") for i in range(5)]
        assert _drive(c, checker, submitted)
        outcomes.append(
            (
                tuple(r.executed_upto for r in c.replicas),
                tuple(r.state_digest.hex() for r in c.replicas),
                c.chaos_dropped,
                c.sig_verifications,
            )
        )
    assert outcomes[0] == outcomes[1]


def test_delayed_and_duplicated_delivery_still_commits():
    """Reordering (delay + per-step shuffle) and duplication are absorbed
    by the protocol's dedup rules; exactly-once holds."""
    c = Cluster(n=4, seed=7, shuffle=True, app=_echo)
    c.set_chaos(LinkChaos(dup_pct=0.3, delay_min=0, delay_max=4))
    checker = InvariantChecker(c)
    submitted = [c.submit(f"dup-{i}", client=f"10.0.0.{i}:9") for i in range(4)]
    assert _drive(c, checker, submitted)
    # Chain digests agree among replicas at EQUAL execution height (a
    # replica may legitimately lag behind the f+1 reply quorum); no
    # replica ever executes a duplicate.
    by_height = {}
    for r in c.replicas:
        by_height.setdefault(r.executed_upto, set()).add(r.state_digest)
        assert r.counters["executed"] <= 4  # exactly-once despite dups
    assert all(len(s) == 1 for s in by_height.values())
    assert any(
        r.executed_upto >= 4 and r.counters["executed"] == 4
        for r in c.replicas
    )


def test_asymmetric_partition_via_dropped_links():
    """One-directional cut (0 can send to 1, 1 cannot answer 0): the
    protocol still commits — 1's votes reach 2 and 3, and 0 only needs
    2f+1 of the remaining voices."""
    c = Cluster(n=4, seed=3, app=_echo)
    c.dropped_links.add((1, 0))
    checker = InvariantChecker(c)
    submitted = [c.submit("asym")]
    assert _drive(c, checker, submitted)


def test_partition_blocks_quorum_then_heals():
    c = Cluster(n=4, seed=5, app=_echo)
    checker = InvariantChecker(c)
    c.partition([{0, 1}, {2, 3}])
    req = c.submit("split")
    c.run(max_steps=120)
    checker.check()
    assert all(r.executed_upto == 0 for r in c.replicas)  # no side has 2f+1
    assert checker.unreplied([req])
    c.heal()
    assert _drive(c, checker, [req])
    assert c.committed_result(req.timestamp) == "split"


def test_crash_realism_no_inbox_drain_no_verify_no_submit():
    """Satellite: a crashed replica must not drain its inbox, run
    signature verification, or accept targeted submissions."""
    c = Cluster(n=4, seed=9, app=_echo)
    req = c.submit("warm")
    c.run(max_steps=60)
    assert c.committed_result(req.timestamp) == "warm"
    before = c.sig_verifications
    c.crash(3)
    assert c.inboxes[3] == [] and c.replicas[3]._inbox == []
    # Targeted submission to the crashed replica goes nowhere.
    dead = c.submit("to the dead", to_replica=3)
    c.run(max_steps=40)
    assert c.inboxes[3] == []
    with pytest.raises(AssertionError):
        c.committed_result(dead.timestamp)
    # The other three keep committing; replica 3 verified NOTHING while
    # down (its old counter inflation bug).
    verified_at_3 = c.replicas[3].counters["sig_verified"]
    live_req = c.submit("while down")
    c.run(max_steps=80)
    assert c.committed_result(live_req.timestamp) == "while down"
    assert c.replicas[3].counters["sig_verified"] == verified_at_3
    assert c.replicas[3].executed_upto == 1
    assert c.sig_verifications > before  # the live replicas did verify


# -- Byzantine behavior modes, <= f faulty => safety + liveness -------------


@pytest.mark.parametrize("mode", FAULT_MODES)
def test_fault_mode_on_primary_preserves_invariants(mode):
    """Each fault mode on the PRIMARY (the worst seat in the house), f=1:
    every safety invariant holds at every step, and the cluster reaches
    liveness — for the stalling modes via view change."""
    c = Cluster(n=4, seed=11, shuffle=True, app=_echo)
    checker = InvariantChecker(c)
    c.set_fault(0, mode)
    submitted = [c.submit(f"{mode}-{i}", client=f"10.0.0.{i}:9") for i in range(3)]
    assert _drive(c, checker, submitted), (
        f"{mode} primary: liveness never recovered"
    )
    assert checker.violations == []
    if mode in ("mute", "equivocate"):
        # These stall view 0 outright: progress implies a view change
        # voted the faulty primary out.
        assert max(r.view for r in c.replicas) >= 1
    if mode != "mute":
        assert c.faults_injected > 0


@pytest.mark.parametrize("mode", ["equivocate", "mute", "stutter"])
def test_fault_mode_on_backup_preserves_invariants(mode):
    c = Cluster(n=4, seed=13, shuffle=True, app=_echo)
    checker = InvariantChecker(c)
    c.set_fault(2, mode)
    submitted = [c.submit(f"b-{mode}-{i}", client=f"10.0.0.{i}:9") for i in range(3)]
    assert _drive(c, checker, submitted)
    assert checker.violations == []
    # Honest replicas at equal execution height agree byte-for-byte (a
    # replica may lag behind the f+1 reply quorum).
    by_height = {}
    for rid in (0, 1, 3):
        r = c.replicas[rid]
        by_height.setdefault(r.executed_upto, set()).add(r.state_digest)
    assert all(len(s) == 1 for s in by_height.values())


def test_equivocation_with_f2_cluster():
    """n=7 (f=2): an equivocating primary PLUS a crashed backup — still
    within budget — and the 5 honest survivors keep both safety and
    liveness."""
    c = Cluster(n=7, seed=17, shuffle=True, app=_echo)
    checker = InvariantChecker(c)
    c.set_fault(0, "equivocate")
    c.crash(5)
    submitted = [c.submit(f"f2-{i}", client=f"10.0.0.{i}:9") for i in range(3)]
    assert _drive(c, checker, submitted, steps=400)
    assert checker.violations == []


# -- checker validity (f+1 faulty MUST trip it) -----------------------------


def test_checker_trips_on_f_plus_one_equivocators():
    res = validate_checker()
    assert res["tripped"], "f+1 colluding equivocators ran clean: the " \
        "safety checker is vacuous"
    assert "chain-digest-divergence" in res["violation"]


def test_checker_trips_on_forged_reply_stream():
    """S2 sanity: a fabricated double-reply from an 'honest' replica is
    caught by the exactly-once check."""
    from pbft_tpu.consensus.messages import ClientReply

    c = Cluster(n=4, seed=1)
    checker = InvariantChecker(c)
    c.client_replies.append(
        ClientReply(view=0, timestamp=1, client="x:1", replica=1, result="a")
    )
    checker.check()
    c.client_replies.append(
        ClientReply(view=0, timestamp=1, client="x:1", replica=1, result="b")
    )
    with pytest.raises(InvariantViolation, match="exactly-once"):
        checker.check()


# -- fault schedules --------------------------------------------------------


def test_fault_schedule_round_trip_and_replay_determinism():
    s1 = random_schedule(123, 7, 200)
    s2 = random_schedule(123, 7, 200)
    assert s1.to_json() == s2.to_json()  # same seed, same schedule
    back = FaultSchedule.from_json(s1.to_json())
    assert back.to_json() == s1.to_json()
    assert random_schedule(124, 7, 200).to_json() != s1.to_json()


def test_random_schedule_respects_fault_budget():
    """At no point may the generated schedule have more than f replicas
    simultaneously crashed or Byzantine, and it must end clean."""
    for seed in range(6):
        n, f = 7, 2
        sched = random_schedule(seed, n, 300)
        crashed, faulty = set(), set()
        for ev in sched.events:
            if ev.action == "crash":
                crashed.add(ev.args[0])
            elif ev.action == "revive":
                crashed.discard(ev.args[0])
            elif ev.action == "set_fault":
                faulty.add(ev.args[0])
            elif ev.action == "clear_fault":
                faulty.discard(ev.args[0])
            assert len(crashed | faulty) <= f, (seed, ev)
        assert not crashed and not faulty  # trailing cleanup revives all


def test_fault_schedule_apply_fires_each_event_once():
    c = Cluster(n=4, seed=0)
    sched = FaultSchedule(
        [
            FaultEvent(2, "crash", (3,)),
            FaultEvent(4, "partition", ([[0, 1], [2, 3]],)),
            FaultEvent(6, "heal", ()),
            FaultEvent(6, "revive", (3,)),
        ]
    )
    fired = []
    for t in range(1, 8):
        fired += [e.action for e in sched.apply_due(c, t)]
    assert fired == ["crash", "partition", "heal", "revive"]
    assert not c.crashed and not c.partitions
    assert sched.apply_due(c, 99) == []


# -- the soak itself (tier-1 smoke; the full 25x400 soak is the slow tier) --


def test_chaos_soak_smoke_f1():
    for seed in (0, 1):
        res = run_one(seed, 4, 100)
        assert res["ok"], res


def test_chaos_soak_smoke_f2():
    res = run_one(2, 7, 80)
    assert res["ok"], res


@pytest.mark.parametrize("n, f, seed", [(16, 5, 5), (31, 10, 12), (31, 10, 13)])
def test_chaos_soak_smoke_at_the_benchmark_cluster_sizes(n, f, seed):
    """S1-S3, L1 and (every recovery a restart from the write-ahead log) S5
    on the seeded simulator at the sizes the benchmark's deployments run:
    n=16 (f=5) and n=31 (f=10, ISSUE 42). Seed 12 crashes and restarts a
    replica across a partition under link chaos, seed 13 a crash, a restart
    and two Byzantine replicas (inside the budget of 10); seed 5 drops
    messages at n=16."""
    res = run_one(seed, n, 80, crash_restart=True)
    assert res["ok"], res
    assert res["n"] == n == 3 * f + 1 and res["executed"] >= res["submitted"] >= 10
    assert res["faults_injected"] + res["chaos_dropped"] > 0  # the schedule did something
    if seed != 5:
        assert {"crash", "restart"} <= {e.action for e in res["schedule"].events}


@pytest.mark.slow
def test_chaos_soak_long():
    """The acceptance-criteria soak: 25 seeds x 400 steps at f=1 and f=2."""
    for seed in range(25):
        for n in (4, 7):
            res = run_one(seed, n, 400)
            assert res["ok"], res


# -- trace-span invariants --------------------------------------------------


def test_check_spans_clean_and_violating():
    clean = {
        (0, 1): {0: {"pre_prepare": 1.0, "prepared": 1.1, "committed": 1.2,
                     "executed": 1.3}},
        (0, 2): {0: {"pre_prepare": 1.4, "executed": 1.6}},
    }
    assert check_spans(clean) == []
    bad_order = {
        (0, 1): {0: {"pre_prepare": 2.0, "prepared": 1.0, "executed": 2.5}},
    }
    assert any("precedes" in p for p in check_spans(bad_order))
    out_of_order_exec = {
        (0, 1): {0: {"pre_prepare": 1.0, "executed": 5.0}},
        (0, 2): {0: {"pre_prepare": 1.1, "executed": 4.0}},
    }
    assert any("out-of-order" in p for p in check_spans(out_of_order_exec))
    double_exec = {
        (0, 3): {1: {"pre_prepare": 1.0, "executed": 2.0}},
        (1, 3): {1: {"pre_prepare": 3.0, "executed": 4.0}},
    }
    assert any("multiple views" in p for p in check_spans(double_exec))

"""The OSDI'99 deployment on its normal path (ISSUE 32): 4 ``pbftd`` replicas
with MAC-vector authenticators and tentative execution, a fsynced WAL, one
gateway and a verify service they are all configured with, and, as its
control, the same cluster in signature mode. The engine is a double (the
host's native verifier behind made-up shapes, ``test_f5_served``'s); the
cluster, the gateway and the service are the program's own.

Every acknowledged request is held to the benchmark's plain reference
(``chipbench/reference``), by both of its quorum rules, each shown tight
both ways: 3 matching tentative replies of one view are a quorum and 2 are
not, 2 committed ones are and 1 is not, 3 tentative ones over two views are
not; each signature checked by the RFC 8032 reference.
"""

from __future__ import annotations

import functools
import random
import sys
import threading
import time
from pathlib import Path

from pbft_tpu.net import VerifyServiceDaemon
from pbft_tpu.net.gateway import GatewayClient
from pbft_tpu.net.launcher import LocalCluster

from test_f5_served import _fetch, _ShapedEngine, _status
from test_gateway import _start_gateway, _stop

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "chipbench"))

import stats  # noqa: E402
from reference import ed25519_ref, state_machine  # noqa: E402

N, F = 4, 1
CLIENTS, EACH = 8, 25  # 200 requests, one outstanding a client: some 30 to 200 sequence numbers
WAIT, LAG = "pbft_request_wait_seconds", "pbft_tentative_commit_lag_seconds"


def _serve(fastpath: str, tentative: bool) -> dict:
    """The cluster behind one gateway and one verify service, served by 8
    clients to quiescence. Returns what was observed."""
    daemon = VerifyServiceDaemon(backend="auto", engine=_ShapedEngine()).start(wait_ready=True)
    acked: dict = {}  # (client, timestamp) -> (operation, the replies at its quorum)
    errors: list = []
    try:
        with LocalCluster(
            n=N, verifier=daemon.address, fastpath=fastpath, tentative=tentative,
            wal=True, wal_fsync=True, batch_max_items=32, batch_flush_us=2000,
            vc_timeout_ms=10000, metrics_ports=True,
            extra_env=[{"PBFT_VERIFY_CONNECT_MS": "5000"} for _ in range(N)],
        ) as cluster:
            assert cluster.config.f == F
            proc, addr = _start_gateway(cluster)

            def serve(k: int) -> None:
                rng = random.Random(3200000000 + k)
                try:
                    client = GatewayClient(cluster.config, addr)
                    for _ in range(EACH):
                        req = client.request(f"op-{k}-{rng.randrange(1 << 30)}")
                        client.wait_result(req.timestamp, timeout=60)
                        with client._lock:
                            replies = [dict(r) for r in client.replies
                                       if r.get("timestamp") == req.timestamp]
                        acked[(client.address, req.timestamp)] = (req.operation, replies)
                    client.close()
                except Exception as e:  # noqa: BLE001 - shown by the main thread
                    errors.append(e)

            try:
                # Every link is dialed and its handshake signed before the
                # first request: what the verify service has been sent by
                # then is all it may ever be sent in MAC mode.
                warm = GatewayClient(cluster.config, addr)
                warm.wait_result(warm.request("warm-up").timestamp, timeout=60)
                warm.close()
                time.sleep(0.5)
                items_before = [_status(port)["verify_items"] for port in cluster.metrics_ports]
                threads = [threading.Thread(target=serve, args=(k,)) for k in range(CLIENTS)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(120)
                assert not errors and len(acked) == CLIENTS * EACH, errors
                deadline = time.monotonic() + 30
                while True:  # trailing commits land, and the last verdicts with them
                    final = [_status(port) for port in cluster.metrics_ports]
                    service = daemon.status_json()
                    # A replica counts an item when it has read its verdict,
                    # the engine when it has run it: a batch on its way back
                    # is in one count and not yet in the other. The cluster
                    # is quiet once the two agree (the tests hold them equal).
                    if len({d["chain_digest"] for d in final}) == 1 and all(
                        d["inbox_depth"] == 0 and d["executed_upto"] == d["committed_upto"]
                        for d in final
                    ) and service["engine_items"] == sum(d["verify_items"] for d in final):
                        break
                    assert time.monotonic() < deadline, (
                        [d["executed"] for d in final], service["engine_items"],
                        [d["verify_items"] for d in final])
                    time.sleep(0.2)
                metrics = [stats.parse_prometheus(_fetch(port, "/metrics"))
                           for port in cluster.metrics_ports]
            finally:
                _stop(proc)
            return {
                "acked": acked, "final": final, "metrics": metrics,
                "items_before": items_before, "seeds": list(cluster.seeds),
                "pubkeys": [bytes.fromhex(r.pubkey) for r in cluster.config.replicas],
                "service": service,
            }
    finally:
        daemon.stop()


def _count(metrics: dict, histogram: str) -> int:
    return int(metrics[(histogram + "_count", "")])


def _resigned(reply: dict, seed: bytes, **changed) -> dict:
    """The reply as its replica would have signed it with ``changed``."""
    out = {k: v for k, v in dict(reply, **changed).items() if k != "sig"}
    out["sig"] = ed25519_ref.sign(seed, state_machine.reply_signable(out)).hex()
    return out


def test_a_served_mac_tentative_cluster_acks_by_the_reference_quorum_and_checks_no_signature():
    run = _serve("mac", True)
    final, metrics, acked = run["final"], run["metrics"], run["acked"]

    # Four replicas, one history, every execution committed, nothing undone.
    assert len(final) == N and {d["view"] for d in final} == {0}
    assert all(d["executed"] >= len(acked) and d["tentative_rollbacks"] == 0 for d in final)
    assert all(d["executed_upto"] == d["committed_upto"] >= 16 for d in final)
    assert all(d["wal_appends"] >= 2 * d["executed_upto"] and d["wal_fsyncs"] > 0 for d in final)
    # Every link ran MAC frames and no vote was sent for verification: not
    # one item more than before the first request, on any replica.
    assert all(d["mode"] == "mac" and d["tentative"] is True for d in final)
    assert all(d["mac_frames"] > 0 and d["mac_rejected"] == 0 and d["mac_verified"] > 0 for d in final)
    assert [d["verify_items"] for d in final] == run["items_before"]
    assert sum(d["verify_service_fallbacks"] + d["verify_deadline_fired"] for d in final) == 0
    assert run["service"]["fallback_items"] == 0
    assert run["service"]["engine_items"] == sum(run["items_before"])
    # What the mode adds, observed: the oldest request's wait on the primary
    # alone, once a batch; a commit lag for every sequence number executed;
    # the checkpoints' embedded signatures checked on the host.
    assert [_count(m, WAIT) > 0 for m in metrics] == [True, False, False, False]
    assert _count(metrics[0], WAIT) == final[0]["executed_upto"]
    assert [_count(m, LAG) for m in metrics] == [d["executed_upto"] for d in final]
    assert all(d["inline_verifies"] > 0 and d["seals_refused"] == 0 for d in final)
    for d, m in zip(final, metrics):
        assert m[("pbft_inline_verifies_total", "")] == d["inline_verifies"]
        assert m[("pbft_tentative_executions_total", "")] == d["tentative_executions"] > 0
        assert m[("pbft_seal_refused_total", "")] == 0

    # Both of the reference's quorum rules on every acknowledged request.
    verify = functools.lru_cache(maxsize=None)(ed25519_ref.verify)
    pubkeys, seeds = run["pubkeys"], run["seeds"]
    assert pubkeys == [ed25519_ref.public_key(s) for s in seeds]
    quorum = functools.partial(state_machine.quorum_result, f=F, n=N, pubkeys=pubkeys,
                               verify=verify)
    by_tentative = 0
    for (client, ts), (operation, replies) in acked.items():
        assert all(r["client"] == client and r["timestamp"] == ts for r in replies)
        one_each = list({r["replica"]: r for r in replies}.values())
        want = state_machine.execute(operation)
        assert quorum(one_each) == want
        tentative = [r for r in one_each if r.get("tentative")]
        committed = [r for r in one_each if not r.get("tentative")]
        assert len(tentative) + len(committed) >= 2 * F + 1 or len(committed) >= F + 1
        if len(tentative) >= 2 * F + 1:
            by_tentative += 1
            three = tentative[: 2 * F + 1]
            assert {r["view"] for r in three} == {0}
            assert quorum(three) == want  # 3 tentative replies of one view: a quorum
            assert quorum(three[:-1]) is None  # 2 are not
            # The same three, one of them as its replica would sign it in
            # view 1: 3 tentative replies over two views are no quorum.
            moved = _resigned(three[0], seeds[three[0]["replica"]], view=1)
            assert quorum([moved, *three[1:]]) is None
            # Two of them as their replicas would sign them once committed
            # (the flag left out): f+1 = 2 committed replies are a quorum,
            # one committed reply beside a tentative one is not.
            firm = [_resigned(r, seeds[r["replica"]], tentative=0) for r in three[: F + 1]]
            assert quorum(firm) == want
            assert quorum([firm[0], three[1]]) is None
        if len(committed) >= F + 1:
            assert quorum(committed[: F + 1]) == want and quorum(committed[:F]) is None
    assert by_tentative > len(acked) / 2  # the replies left at PREPARED


def test_the_same_cluster_in_signature_mode_observes_the_request_wait_and_no_commit_lag():
    run = _serve("sig", False)
    final, metrics = run["final"], run["metrics"]
    assert {d["view"] for d in final} == {0} and all(d["mode"] == "sig" for d in final)
    assert all(d["executed"] >= len(run["acked"]) and d["mac_frames"] == 0 for d in final)
    assert [_count(m, WAIT) > 0 for m in metrics] == [True, False, False, False]
    assert _count(metrics[0], WAIT) == final[0]["executed_upto"]
    assert [_count(m, LAG) for m in metrics] == [0] * N
    # Every vote went to the verify service, none was checked inline.
    assert all(d["verify_items"] > 2 * d["executed_upto"] and d["inline_verifies"] == 0
               for d in final)
    assert run["service"]["engine_items"] == sum(d["verify_items"] for d in final)
    verify = functools.lru_cache(maxsize=None)(ed25519_ref.verify)
    for (client, ts), (operation, replies) in run["acked"].items():
        one_each = list({r["replica"]: r for r in replies}.values())
        assert not any(r.get("tentative") for r in one_each) and len(one_each) >= F + 1
        want = state_machine.execute(operation)
        assert state_machine.quorum_result(one_each[: F + 1], F, N, run["pubkeys"], verify) == want
        assert state_machine.quorum_result(one_each[:F], F, N, run["pubkeys"], verify) is None

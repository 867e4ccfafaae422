"""The f=10 deployment on its normal path (ISSUE 42), after ``test_f5_served``:
31 ``pbftd`` replicas in signature mode with a fsynced WAL, one gateway, and
every signature (some 1,860 a sequence number) sent to ONE verify service.
The engine is a double (the host's native verifier behind three shapes with
made-up launch times); the cluster, the gateway, the dispatcher, its hold and
its cut at ``MAX_WINDOW`` are the program's own.

The double's launch times are long enough that the replicas' batches queue
up past the largest window, which is what the deployment does to the chip:
so some window is cut at ``MAX_WINDOW`` with requests left queued behind it
(``cut_full`` on its launch line, ``windows_cut_full`` / ``overflow_items_max``
in the status JSON), and what was left behind goes next, in arrival order.

Every acknowledged request is held to the benchmark's plain reference
(``chipbench/reference``): f+1 = 11 matching signed replies are a quorum, 10
are not; a seeded sample of them with each signature checked by the RFC 8032
reference, the rest by the host's verifier.
"""

from __future__ import annotations

import functools
import json
import random
import sys
import threading
import time
from pathlib import Path

import pytest

from pbft_tpu import native
from pbft_tpu.net import VerifyServiceDaemon
from pbft_tpu.net.gateway import GatewayClient
from pbft_tpu.net import launcher
from pbft_tpu.net.launcher import LocalCluster
from pbft_tpu.net.service import VerifierService

from test_f5_served import _ShapedEngine, _status
from test_gateway import _start_gateway, _stop

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "chipbench"))

from reference import ed25519_ref, state_machine  # noqa: E402

N, F = 31, 10
CLIENTS, EACH = 16, 32  # 512 requests, all of a client's sent at once: 16 sequence numbers and more in flight


class _SlowShapes(_ShapedEngine):
    """A launch of the largest shape takes long enough for 31 replicas'
    next batches to arrive behind it."""

    LAUNCH_S = {256: 0.005, 1024: 0.02, 4096: 0.12}

    def _fit(self, n: int) -> int:
        # The dispatcher asks hold_s about everything queued, which here
        # passes the largest shape: that shape, and no hold, as the engine says.
        return min((s for s in self.LAUNCH_S if s >= n), default=max(self.LAUNCH_S))


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    trace = tmp_path_factory.mktemp("f10") / "verifyd.jsonl"
    daemon = VerifyServiceDaemon(
        backend="auto", engine=_SlowShapes(), trace_path=str(trace)
    ).start(wait_ready=True)
    acked: dict = {}  # (client, timestamp) -> (operation, the replies at its quorum)
    errors: list = []
    windows: list = []  # (its requests' places in the queue, its waits, the requests) a window
    dispatch = daemon.service._dispatch_window

    class Numbered(list):
        """The service's queue, each request numbered as it is appended
        (under the service's lock: the order the dispatcher sees)."""

        place: dict = {}

        def append(self, pending):
            self.place[id(pending)] = len(self.place)
            super().append(pending)

    def watched(window, waits):  # the window as the dispatcher cut it
        # (the requests are kept: an id is theirs only while they live)
        windows.append(([Numbered.place[id(p)] for p in window], dict(waits), window))
        return dispatch(window, waits)

    with daemon.service._cond:
        daemon.service._pending = Numbered(daemon.service._pending)
    daemon.service._dispatch_window = watched
    try:
        assert daemon.state_name == "ready" and daemon.service.hold_s is not None
        with LocalCluster(
            n=N, verifier=daemon.address, wal=True, wal_fsync=True, batch_max_items=32,
            batch_flush_us=20000, vc_timeout_ms=10000, metrics_ports=True,
            extra_env=[{"PBFT_VERIFY_CONNECT_MS": "5000"} for _ in range(N)],
        ) as cluster:
            assert cluster.config.f == F
            pubkeys = [bytes.fromhex(r.pubkey) for r in cluster.config.replicas]
            proc, addr = _start_gateway(cluster)

            def serve(k: int) -> None:
                rng = random.Random(4200000000 + k)
                try:
                    client = GatewayClient(cluster.config, addr)
                    sent = [client.request(f"op-{k}-{rng.randrange(1 << 30)}") for _ in range(EACH)]
                    for req in sent:
                        client.wait_result(req.timestamp, timeout=90)
                        with client._lock:
                            replies = [dict(r) for r in client.replies
                                       if r.get("timestamp") == req.timestamp]
                        acked[(client.address, req.timestamp)] = (req.operation, replies)
                    client.close()
                except Exception as e:  # noqa: BLE001 - shown by the main thread
                    errors.append(e)

            try:
                threads = [threading.Thread(target=serve, args=(k,)) for k in range(CLIENTS)]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(150)
                assert not errors and len(acked) == CLIENTS * EACH, errors
                deadline = time.monotonic() + 45
                while True:  # trailing commits land, and the last verdicts with them
                    final = [_status(port) for port in cluster.metrics_ports]
                    status = daemon.status_json()
                    # A replica counts an item when it has read its verdict,
                    # the engine when it has run it: the cluster is quiet
                    # once the two agree (and the test holds them equal).
                    if len({d["chain_digest"] for d in final}) == 1 and all(
                        d["inbox_depth"] == 0 for d in final
                    ) and status["engine_items"] == sum(d["verify_items"] for d in final):
                        break
                    assert time.monotonic() < deadline, (
                        [d["executed"] for d in final], status["engine_items"],
                        sum(d["verify_items"] for d in final))
                    time.sleep(0.2)
            finally:
                _stop(proc)
    finally:
        daemon.stop()
    lines = [json.loads(ln) for ln in trace.read_text().splitlines()]
    return {
        "acked": acked, "final": final, "status": status, "pubkeys": pubkeys,
        "windows": windows, "lines": [e for e in lines if e.get("ev") == "verify_batch"],
    }


def test_a_served_f10_cluster_ends_on_one_history_with_every_signature_through_the_service(served):
    final, status, acked = served["final"], served["status"], served["acked"]
    # Thirty-one replicas, one history, no view change, nothing verified on the host.
    assert len(final) == N and {d["view"] for d in final} == {0}
    assert len({d["chain_digest"] for d in final}) == 1
    assert len({d["executed_upto"] for d in final}) == 1
    executed = sorted((d["executed"] for d in final), reverse=True)
    assert executed[F] >= len(acked)  # the (f+1)-th replica executed every acknowledged request
    assert sum(d["verify_service_fallbacks"] + d["verify_deadline_fired"] for d in final) == 0
    # Both votes of every sequence number in a fsynced log, on every replica
    # that executed each request itself.
    assert all(d["wal_appends"] >= 2 * d["executed_upto"] and d["wal_fsyncs"] > 0
               for d in final if d["executed"] == executed[0])
    # What the engine ran is what the replicas sent: not an item more or fewer.
    assert status["fallback_items"] == 0
    assert status["engine_items"] == sum(d["verify_items"] for d in final)
    assert status["engine_items"] == sum(e["size"] for e in served["lines"])
    # Some 60 signatures a sequence number and replica: a PRE-PREPARE, up to
    # 29 PREPAREs and 30 COMMITs; a quorum of 21 needs 2 x 20 of them.
    seqs = final[0]["executed_upto"]
    assert all(d["verify_items"] >= 40 * seqs for d in final)
    assert max(d["verify_items"] for d in final) <= 61 * seqs + 31 * (seqs // 16 + 2)


def test_eleven_matching_signed_replies_are_a_quorum_at_f10_and_ten_are_not(served):
    """The reference's quorum rule at f=10 on EVERY acknowledged request, its
    signatures checked by the host's verifier; and on a seeded sample of them
    by the RFC 8032 reference itself (pure Python: 4 ms a signature)."""
    pubkeys = served["pubkeys"]
    by_host = functools.lru_cache(maxsize=None)(lambda *item: bool(native.verify_batch([item])[0]))
    by_reference = functools.lru_cache(maxsize=None)(ed25519_ref.verify)
    acked = sorted(served["acked"].items())
    assert len(acked) == CLIENTS * EACH
    sample = set(random.Random(4200000042).sample(range(len(acked)), 40))
    for k, ((client, ts), (operation, replies)) in enumerate(acked):
        assert all(r["client"] == client and r["timestamp"] == ts for r in replies)
        one_each = list({r["replica"]: r for r in replies}.values())
        assert len(one_each) >= F + 1
        want = state_machine.execute(operation)
        for verify in (by_host, by_reference) if k in sample else (by_host,):
            assert state_machine.quorum_result(one_each[: F + 1], F, N, pubkeys, verify) == want
            assert state_machine.quorum_result(one_each[:F], F, N, pubkeys, verify) is None
    # The rule is tight on the signatures too: eleven replies of which one is
    # not its replica's are no quorum.
    (client, ts), (operation, replies) = acked[0]
    one_each = list({r["replica"]: r for r in replies}.values())[: F + 1]
    forged = dict(one_each[0], sig=one_each[1]["sig"])
    assert state_machine.quorum_result([forged, *one_each[1:]], F, N, pubkeys, by_reference) is None


def test_the_backlog_passes_the_largest_window_and_what_is_left_behind_goes_next_in_order(served):
    status, lines, windows = served["status"], served["lines"], served["windows"]
    assert len(lines) == len(windows) == status["engine_launches"]
    # The launch line says so, once a window, and the status JSON counts them.
    assert all(e["cut_full"] == int(e["pending_at_cut"] > 0) for e in lines)
    full = [e for e in lines if e["cut_full"]]
    assert full and status["windows_cut_full"] == len(full)
    assert status["overflow_items_max"] == max(e["pending_at_cut"] for e in lines) > 0
    # A window never passes MAX_WINDOW, and one that left requests behind had
    # no room for the next of them (a replica's batch is at most 61 items a
    # sequence number in flight: a few hundred).
    assert max(e["size"] for e in lines) <= VerifierService.MAX_WINDOW
    assert all(e["size"] > VerifierService.MAX_WINDOW // 2 and e["hold_s"] == 0 for e in full)
    assert all(e["rung"] == 4096 for e in full)
    # FIFO, across every cut: one dispatcher takes requests off the head of
    # the queue, so each window is an unbroken run of the queue in the order
    # it was queued, and the windows together take every request once; what
    # a full window left behind therefore leads the next one.
    places = [place for w in sorted(windows, key=lambda w: w[0][0]) for place in w[0]]
    assert places == list(range(len(places))) and len(places) == status["requests"]
    assert all(w[0] == list(range(w[0][0], w[0][0] + len(w[0]))) for w in windows)
    assert sum(w[1]["cut_full"] for w in windows) == len(full)

def test_a_cluster_reserves_its_listen_ports_and_its_scrape_ports_in_one_call(monkeypatch):
    """What the 31-replica cluster turned up in ``LocalCluster``: the scrape
    ports were reserved by a second ``free_ports`` call, after the listen
    ports had been released again, and the kernel hands a released port out
    anew: one n=31 cluster in fourteen (one in sixty at n=16) started with a
    replica whose scrape port was another replica's listen port. Here every
    reservation is handed the same ports again, which is the worst the kernel
    can do; the cluster must ask once, for all 62."""
    asked: list = []

    def handed_again(n: int) -> list:
        asked.append(n)
        return list(range(40001, 40001 + n))

    monkeypatch.setattr(launcher, "free_ports", handed_again)
    cluster = LocalCluster(n=N, metrics_ports=True)  # never entered: nothing is started
    listen = [r.port for r in cluster.config.replicas]
    assert asked == [2 * N]
    assert len(set(listen)) == len(set(cluster.metrics_ports)) == N
    assert not set(listen) & set(cluster.metrics_ports)
    asked.clear()
    assert LocalCluster(n=4).metrics_ports == [] and asked == [4]

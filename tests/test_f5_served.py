"""The f=5 deployment on its normal path (ISSUE 28): 16 ``pbftd`` replicas in
signature mode with a fsynced WAL, one gateway, and every signature sent to
ONE verify service whose engine holds windows for company and serves them by
a table, as ``verifyd`` does on the chip. The engine here is a double (the
host's native verifier behind three shapes with made-up launch times); the
cluster, the gateway, the dispatcher and its hold are the program's own.

Every acknowledged request is held to the benchmark's plain reference
(``chipbench/reference``): f+1 = 6 matching signed replies are a quorum, 5
are not, each signature checked by the RFC 8032 reference.
"""

from __future__ import annotations

import functools
import json
import random
import re
import sys
import threading
import time
import urllib.request
from pathlib import Path

from pbft_tpu import native
from pbft_tpu.net import VerifyServiceDaemon
from pbft_tpu.net.gateway import GatewayClient
from pbft_tpu.net.launcher import LocalCluster
from pbft_tpu.utils.trace import current_span

from test_gateway import _start_gateway, _stop

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "chipbench"))

from reference import ed25519_ref, state_machine  # noqa: E402

N, F = 16, 5
CLIENTS, EACH = 8, 25  # the deployment's 8 client services; 200 requests


class _ShapedEngine:
    """An engine double with what the dispatcher and the status JSON ask of
    one: shapes with a ``launch_s`` each, the identity serving table, a
    ``hold_s`` by the engine's own rule, and ``rung`` in the span."""

    LAUNCH_S = {256: 0.002, 1024: 0.004, 4096: 0.012}

    def __init__(self):
        self.platform = self.device_kind = None
        self.devices_seen = self.device_count = 0
        self.stats: dict = {}
        self.warmed_sizes = ()

    def init_backend(self):
        self.platform, self.device_kind = "cpu", "shaped double"
        self.devices_seen = self.device_count = 1

    def warm(self):
        self.warmed_sizes = tuple(self.LAUNCH_S)
        self.stats = {
            "cold_compile_s": 0.0, "warm_load_s": 0.0,
            "serving_table": {str(s): s for s in self.LAUNCH_S},
        }
        return self.stats

    def _fit(self, n: int) -> int:
        return min(s for s in self.LAUNCH_S if s >= n)

    def hold_s(self, n: int) -> float:
        fit = self._fit(n)
        return self.LAUNCH_S[fit] if n < fit else 0.0

    def verify(self, items):
        fit = self._fit(len(items))
        time.sleep(self.LAUNCH_S[fit])
        span = current_span()
        if span is not None:
            span.update(rung=fit, promoted=0)
        return [bool(v) for v in native.verify_batch(items)]

    def memory_peak_bytes(self):
        return None


def _fetch(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read().decode()


def _status(port: int) -> dict:
    """A replica's health document, with the items it sent for verification."""
    sent = re.search(r"^pbft_verify_items_total(?:\{[^}]*\})? (\S+)$", _fetch(port, "/metrics"), re.M)
    return dict(json.loads(_fetch(port, "/status")), verify_items=int(float(sent.group(1))))


def test_a_served_f5_cluster_commits_and_every_ack_passes_the_reference_quorum():
    daemon = VerifyServiceDaemon(backend="auto", engine=_ShapedEngine()).start(wait_ready=True)
    acked: dict = {}  # (client, timestamp) -> (operation, the replies at its quorum)
    errors: list = []
    try:
        assert daemon.state_name == "ready" and daemon.service.hold_s is not None
        with LocalCluster(
            n=N, verifier=daemon.address, wal=True, wal_fsync=True, batch_max_items=32,
            batch_flush_us=2000, vc_timeout_ms=10000, metrics_ports=True,
            extra_env=[{"PBFT_VERIFY_CONNECT_MS": "5000"} for _ in range(N)],
        ) as cluster:
            assert cluster.config.f == F
            pubkeys = [bytes.fromhex(r.pubkey) for r in cluster.config.replicas]
            proc, addr = _start_gateway(cluster)

            def serve(k: int) -> None:
                rng = random.Random(2800000000 + k)
                try:
                    client = GatewayClient(cluster.config, addr)
                    sent = [client.request(f"op-{k}-{rng.randrange(1 << 30)}") for _ in range(EACH)]
                    for req in sent:
                        client.wait_result(req.timestamp, timeout=60)
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
                    t.join(120)
                assert not errors and len(acked) == CLIENTS * EACH, errors
                deadline = time.monotonic() + 30
                while True:  # trailing commits land; a straggler catches up
                    final = [_status(port) for port in cluster.metrics_ports]
                    status = daemon.status_json()
                    # (and the last verdicts are read: a batch on its way
                    # back is in the engine's count and not yet in a replica's)
                    if len({d["chain_digest"] for d in final}) == 1 and all(
                        d["inbox_depth"] == 0 for d in final
                    ) and status["engine_items"] == sum(d["verify_items"] for d in final):
                        break
                    assert time.monotonic() < deadline, [d["executed"] for d in final]
                    time.sleep(0.2)
            finally:
                _stop(proc)
    finally:
        daemon.stop()

    # Sixteen replicas, one history, no view change, nothing verified on the host.
    assert len(final) == N and {d["view"] for d in final} == {0}
    executed = sorted((d["executed"] for d in final), reverse=True)
    assert executed[F] >= len(acked)  # the (f+1)-th replica executed every acknowledged request
    assert sum(d["verify_service_fallbacks"] + d["verify_deadline_fired"] for d in final) == 0
    assert all(d["wal_appends"] >= 2 * d["executed_upto"] and d["wal_fsyncs"] > 0
               for d in final if d["executed"] == executed[0])
    assert status["fallback_items"] == 0 and status["engine_items"] == sum(
        d["verify_items"] for d in final
    )
    # The windows merged many replicas' batches and ran above the smallest shape.
    by_rung = status["launches_by_rung"]
    assert sum(by_rung.values()) == status["engine_launches"] < status["requests"] / 2
    assert set(by_rung) <= {"256", "1024", "4096"} and set(by_rung) - {"256"}
    assert status["in_step_launches"] + status["held_out_launches"] <= status["engine_launches"]

    # The reference's quorum rule at f=5 on every acknowledged request: six
    # distinct replicas' matching signed replies are accepted, five are not.
    verify = functools.lru_cache(maxsize=None)(ed25519_ref.verify)
    for (client, ts), (operation, replies) in acked.items():
        assert all(r["client"] == client and r["timestamp"] == ts for r in replies)
        one_each = list({r["replica"]: r for r in replies}.values())
        assert len(one_each) >= F + 1
        want = state_machine.execute(operation)
        assert state_machine.quorum_result(one_each[: F + 1], F, N, pubkeys, verify) == want
        assert state_machine.quorum_result(one_each[:F], F, N, pubkeys, verify) is None

"""Gateway tier (ISSUE 10): exactly-once and reply-quorum fan-back through
the client-gateway in front of real daemon clusters.

The tier's contract: a client identity is a ``gw/`` routing token, not a
dialable address; requests multiplex over one gateway connection onto a
few persistent replica links; every replica's reply copy fans BACK over
those links and the client still counts its own f+1 signature-verified
quorum. Duplicate/retransmitted requests must hit the replicas'
per-(client, ts) reply caches — executed exactly once, same result bytes
every time.
"""

from __future__ import annotations

import asyncio
import dataclasses
import json
import os
import re
import socket
import struct
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from pbft_tpu.consensus.config import make_local_cluster
from pbft_tpu.net.gateway import (
    _MAX_WRITE_BUFFER,
    GATEWAY_CLIENT_PREFIX,
    ClientGateway,
    GatewayClient,
    _frame_bytes as _frame,
    _parse,
    next_token,
)
from pbft_tpu.net.launcher import LocalCluster
from pbft_tpu.net.service import _recv_exact

REPO = Path(__file__).resolve().parent.parent


def _start_gateway(cluster: LocalCluster, name: str = "gateway", extra=()):
    """One gateway subprocess in front of ``cluster``; returns
    (Popen, "host:port"). ``name`` keys the log file so several gateways
    can front one cluster; ``extra`` appends CLI flags (admission knobs)."""
    cfg = Path(cluster.tmpdir.name) / "network.json"
    log_path = Path(cluster.tmpdir.name) / f"{name}.log"
    log = open(log_path, "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", "pbft_tpu.net.gateway", "--config", str(cfg),
         "--port", "0", *extra],
        stdout=log, stderr=log, close_fds=True,
        env=dict(os.environ, PYTHONPATH=str(REPO)),
    )
    deadline = time.monotonic() + 20
    while True:
        text = log_path.read_text(errors="replace") if log_path.exists() else ""
        m = re.search(r"gateway listening on (\d+)", text)
        if m:
            return proc, f"127.0.0.1:{m.group(1)}"
        if proc.poll() is not None or time.monotonic() > deadline:
            raise TimeoutError(f"gateway never listened:\n{text}")
        time.sleep(0.05)


def _stop(proc) -> None:
    proc.terminate()
    try:
        proc.wait(timeout=5)
    except subprocess.TimeoutExpired:
        proc.kill()


def _replica_metric(cluster: LocalCluster, rid: int, key: str):
    log = (Path(cluster.tmpdir.name) / f"replica-{rid}.log").read_text(
        errors="replace"
    )
    hits = re.findall(rf'"{key}":\s*(-?\d+)', log)
    return int(hits[-1]) if hits else None


def test_gateway_exactly_once_and_quorum_fan_back():
    """The acceptance pin: duplicates/retransmissions through the gateway
    execute once, the reply quorum is f+1 DISTINCT signature-verified
    replicas, and the reply route is the gateway link (no dial-back)."""
    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, batch_max_items=8,
        batch_flush_us=2000,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            client = GatewayClient(cluster.config, addr)
            assert client.address.startswith(GATEWAY_CLIENT_PREFIX)
            req = client.request("gw-op-1")
            result = client.wait_result(req.timestamp, timeout=30)

            # Retransmit the SAME (token, ts) three times: the replicas'
            # reply caches must answer with the SAME result, and the
            # executed counter must not advance for any of them.
            time.sleep(1.2)  # let a metrics tick capture the first exec
            executed_before = _replica_metric(cluster, 0, "executed")
            for _ in range(3):
                # Clear BEFORE retransmitting: cached replies can land
                # within microseconds of the send, and clearing after
                # would wipe them (then nothing retransmits again inside
                # wait_result — a guaranteed 30 s timeout).
                with client._lock:
                    client.replies.clear()
                client.request("gw-op-1", timestamp=req.timestamp)
                assert client.wait_result(req.timestamp, timeout=30) == result
            time.sleep(1.5)
            executed_after = _replica_metric(cluster, 0, "executed")
            assert executed_before == executed_after, (
                f"duplicates executed: {executed_before} -> {executed_after}"
            )

            # The quorum really was distinct replicas (not one replica's
            # retransmissions): wait_result already requires f+1 distinct
            # ids with valid signatures; double-check the vote spread.
            with client._lock:
                voters = {
                    r.get("replica")
                    for r in client.replies
                    if r.get("timestamp") == req.timestamp
                }
            assert len(voters) >= cluster.config.f + 1
            client.close()
        finally:
            _stop(proc)
        # Replica-side accounting: the primary saw gateway-forwarded
        # requests on a gateway link.
        fwd = _replica_metric(cluster, 0, "gateway_forwarded")
        assert fwd is not None and fwd >= 1


def test_gateway_pipelined_many_and_replica_counters():
    """request_many through the gateway: pipelined submission over ONE
    socket completes every request, and the cluster's connection count
    stays O(n + gateways) — no per-client or per-reply sockets."""
    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, batch_max_items=16,
        batch_flush_us=2000,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            clients = [GatewayClient(cluster.config, addr) for _ in range(4)]
            results = []
            for ci, c in enumerate(clients):
                results.append(
                    c.request_many(
                        [f"gw-{ci}-{k}" for k in range(12)], window=6,
                        timeout=45,
                    )
                )
            assert all(len(r) == 12 for r in results)
            for c in clients:
                c.close()
            time.sleep(1.5)
            # conns on replica 0: 3 dialed peer links + up to 3 accepted
            # peer links + 1 gateway link (+ slack for handshake churn) —
            # NOT 4 clients x anything.
            conns = _replica_metric(cluster, 0, "connections_open")
            assert conns is not None and conns <= 10, conns
        finally:
            _stop(proc)


@pytest.mark.parametrize("net_threads", [1, 2])
def test_gateway_link_trusted_by_every_replica(net_threads):
    """Every replica honors role=gateway links, on both socket layers: the
    cluster serves a gateway client with replies fanning back from the
    primary's side and the backups' alike."""
    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, net_threads=net_threads,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            client = GatewayClient(cluster.config, addr)
            req = client.request("mixed-gw")
            assert client.wait_result(req.timestamp, timeout=40)
            # Replies crossed back from at least one replica of each pair
            # (0/2 and 1/3). The quorum may be met by the fastest f+1, so
            # poll briefly for the slower ones' fan-back instead of
            # asserting on the first snapshot.
            deadline = time.monotonic() + 10
            while True:
                with client._lock:
                    voters = {
                        r.get("replica")
                        for r in client.replies
                        if r.get("timestamp") == req.timestamp
                    }
                if voters & {0, 2} and voters & {1, 3}:
                    break
                assert time.monotonic() < deadline, voters
                time.sleep(0.1)
            client.close()
        finally:
            _stop(proc)


def test_gateway_rejects_non_gateway_identity():
    """A dialable client address through the gateway is dropped (it would
    reopen the per-client socket cost and an unauthenticated redirect
    channel); a gw/ token on the same connection still works."""
    with LocalCluster(n=4, verifier="cpu") as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            host, _, port = addr.rpartition(":")
            s = socket.create_connection((host, int(port)), timeout=10)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            bad = {
                "type": "client-request",
                "operation": "evil",
                "timestamp": 1,
                "client": "127.0.0.1:9999",  # dialable: must be dropped
            }
            s.sendall(json.dumps(bad).encode() + b"\n")
            s.close()
            client = GatewayClient(cluster.config, addr)
            req = client.request("good")
            assert client.wait_result(req.timestamp, timeout=30)
            client.close()
        finally:
            _stop(proc)


def test_gateway_secure_cluster_refused():
    """A gateway link on a secure cluster is rejected by the replicas
    (no replica identity to authenticate) and by the ClientGateway
    constructor itself."""
    config, _ = make_local_cluster(4, base_port=0)
    secure_cfg = dataclasses.replace(config, secure=True)
    with pytest.raises(ValueError):
        ClientGateway(secure_cfg)


def test_token_uniqueness():
    tokens = {next_token() for _ in range(256)}
    assert len(tokens) == 256
    assert all(t.startswith(GATEWAY_CLIENT_PREFIX) for t in tokens)


@pytest.mark.slow
def test_gateway_many_clients_sustained():
    """A few hundred concurrent identities through one gateway on an n=4
    cluster (the 10k shape, sized for CI): sustained traffic, no FD
    exhaustion, every request completes."""
    sys.path.insert(0, str(REPO / "scripts"))
    import scale_curve

    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, batch_max_items=64,
        batch_flush_us=2000,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            _, _, port = addr.rpartition(":")
            done, elapsed, lat = asyncio.run(
                scale_curve.run_load(
                    "127.0.0.1", [int(port)], clients=200, requests_each=3,
                    window=3, quorum=cluster.config.f + 1, deadline_s=240,
                )
            )
            assert done == 200 * 3, f"completed {done}/600"
        finally:
            _stop(proc)


# -- gateway HA + admission control (ISSUE 12) --------------------------------


def test_gateway_client_failover_exactly_once():
    """Kill the gateway a client is attached to MID-REQUEST: the client
    fails over to the second gateway under the SAME gw/ token, replays
    its in-flight lines, and completion stays 100% — with the replicas'
    per-(client, ts) exactly-once guard proving the replay executed
    nothing twice (the ISSUE 12 gateway-HA acceptance pin)."""
    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, batch_max_items=8,
        batch_flush_us=2000,
    ) as cluster:
        proc_a, addr_a = _start_gateway(cluster, name="gateway-a")
        proc_b, addr_b = _start_gateway(cluster, name="gateway-b")
        procs = {addr_a: proc_a, addr_b: proc_b}
        client = None
        try:
            client = GatewayClient(cluster.config, [addr_a, addr_b])
            req1 = client.request("ha-op-1")
            result1 = client.wait_result(req1.timestamp, timeout=30)
            assert result1 == "awesome!"
            time.sleep(1.2)  # one metrics tick captures the execution
            executed_before = _replica_metric(cluster, 0, "executed")
            # Fire a request and kill the attached gateway before waiting:
            # the death lands mid-request, the failover replay (same
            # token, same ts) must complete it through the survivor.
            attached = [addr_a, addr_b][client._addr_idx]
            req2 = client.request("ha-op-2")
            _stop(procs[attached])
            result2 = client.wait_result(req2.timestamp, timeout=45)
            assert result2 == "awesome!"
            assert client.failovers >= 1
            # Exactly-once across the failover: explicitly retransmit
            # req2 (the request that rode the failover replay) through
            # the surviving gateway — the replicas' reply caches answer
            # with the SAME bytes and nothing re-executes. (req2 is the
            # client's LATEST request: PBFT's reply cache holds exactly
            # one reply per client, so only the latest ts can be
            # re-answered.)
            with client._lock:  # clear BEFORE the send (see above test)
                client.replies.clear()
            client.request("ha-op-2", timestamp=req2.timestamp)
            assert client.wait_result(req2.timestamp, timeout=30) == result2
            time.sleep(1.5)
            executed_after = _replica_metric(cluster, 0, "executed")
            # ha-op-2 executed once; neither the failover replay nor the
            # explicit retransmission executed anything more.
            assert executed_after == executed_before + 1, (
                f"replay re-executed: {executed_before} -> {executed_after}"
            )
        finally:
            if client is not None:
                client.close()
            for p in procs.values():
                _stop(p)


def test_gateway_admission_rejects_past_inflight_cap():
    """Admission control at the gateway (ISSUE 12): with --max-inflight 2
    and a cluster that never answers (nothing listening), the third
    fresh request gets an explicit overloaded line back — not silence."""
    import tempfile

    config, _seeds = make_local_cluster(4, base_port=1)  # ports 1-4: dead
    with tempfile.TemporaryDirectory(prefix="gwadm-") as tmp:
        cfg_path = Path(tmp) / "network.json"
        cfg_path.write_text(config.to_json())
        log_path = Path(tmp) / "gateway.log"
        log = open(log_path, "wb")
        proc = subprocess.Popen(
            [sys.executable, "-m", "pbft_tpu.net.gateway", "--config",
             str(cfg_path), "--port", "0", "--max-inflight", "2"],
            stdout=log, stderr=log, close_fds=True,
            env=dict(os.environ, PYTHONPATH=str(REPO)),
        )
        try:
            deadline = time.monotonic() + 20
            port = None
            while port is None:
                text = (
                    log_path.read_text(errors="replace")
                    if log_path.exists()
                    else ""
                )
                m = re.search(r"gateway listening on (\d+)", text)
                if m:
                    port = int(m.group(1))
                elif proc.poll() is not None or time.monotonic() > deadline:
                    raise TimeoutError(f"gateway never listened:\n{text}")
                else:
                    time.sleep(0.05)
            token = next_token("adm")
            s = socket.create_connection(("127.0.0.1", port), timeout=10)
            s.settimeout(10)
            for ts in range(1, 6):  # 5 fresh requests, cap 2
                line = json.dumps({
                    "type": "client-request", "operation": f"op-{ts}",
                    "timestamp": ts, "client": token,
                }, separators=(",", ":")).encode() + b"\n"
                s.sendall(line)
            buf = b""
            overloaded = []
            deadline = time.monotonic() + 15
            while len(overloaded) < 3 and time.monotonic() < deadline:
                try:
                    chunk = s.recv(65536)
                except socket.timeout:
                    break
                if not chunk:
                    break
                buf += chunk
                overloaded = [
                    json.loads(ln)
                    for ln in buf.split(b"\n")
                    if ln.strip()
                    and json.loads(ln).get("type") == "overloaded"
                ]
            s.close()
            # Requests 3, 4, 5 were past the cap (1 and 2 hold the two
            # in-flight slots forever — the cluster is dead).
            assert len(overloaded) == 3, overloaded
            assert {o["timestamp"] for o in overloaded} == {3, 4, 5}
            assert all(o["client"] == token for o in overloaded)
        finally:
            _stop(proc)


@pytest.mark.parametrize("net_threads", [1, 2])
def test_replica_admission_inflight_cap_and_recovery(net_threads):
    """Admission control at the REPLICA (both socket layers, ISSUE 12): with
    admission_inflight=3 in network.json and a long batch-flush window, a
    burst of 10 fresh requests gets explicit overloaded replies past the
    cap — and the rejected requests still complete once the client
    retries after the backlog drains (liveness is never admission-gated,
    retransmissions always pass)."""
    with LocalCluster(
        n=4, verifier="cpu", metrics_every=1, net_threads=net_threads,
        batch_max_items=64, batch_flush_us=500000, admission_inflight=3,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        client = None
        try:
            client = GatewayClient(cluster.config, addr)
            reqs = [client.request(f"burst-{k}") for k in range(10)]
            # The primary's overloaded lines route back over the gateway.
            deadline = time.monotonic() + 20
            while time.monotonic() < deadline:
                with client._lock:
                    rejected = [
                        r for r in client.replies
                        if r.get("type") == "overloaded"
                    ]
                if rejected:
                    break
                time.sleep(0.1)
            assert rejected, "no overloaded reply reached the client"
            assert all(r["timestamp"] > 3 for r in rejected)
            # The admitted prefix completes untouched.
            assert client.wait_result(reqs[0].timestamp, timeout=30) == (
                "awesome!"
            )
            # Rejected requests complete on retry as the backlog drains.
            done = {}
            deadline = time.monotonic() + 90
            while len(done) < 10 and time.monotonic() < deadline:
                for r in reqs:
                    if r.timestamp in done:
                        continue
                    try:
                        done[r.timestamp] = client.wait_result(
                            r.timestamp, timeout=2
                        )
                    except TimeoutError:
                        client.request(r.operation, timestamp=r.timestamp)
            assert len(done) == 10
            time.sleep(1.5)
            rej = _replica_metric(cluster, 0, "overload_rejections")
            assert rej is not None and rej >= 1
        finally:
            if client is not None:
                client.close()
            _stop(proc)


# -- the gateway by the read (ISSUE 33): stub replicas, no cluster ------------
#
# Plain sockets stand in for the replicas (they speak the 4-byte framing and
# nothing else), and the gateway runs in this process on a loop of its own, so
# a case takes milliseconds and can look at the gateway's counters.


def _recv_frames(sock: socket.socket, count: int) -> list:
    out = []
    for _ in range(count):
        (n,) = struct.unpack(">I", _recv_exact(sock, 4))
        out.append(_recv_exact(sock, n))
    return out


def _recv_lines(sock: socket.socket, until: bytes) -> list:
    """The lines a client is sent, up to and with the line ``until``."""
    buf = b""
    while not buf.endswith(until + b"\n"):
        chunk = sock.recv(1 << 16)
        if not chunk:
            raise ConnectionError(f"closed before {until!r}: {buf[-200:]!r}")
        buf += chunk
    return buf.split(b"\n")[:-1]


def _request(token: str, ts: int, op: str = "0/0") -> bytes:
    return json.dumps(
        {"type": "client-request", "operation": op, "timestamp": ts,
         "client": token}, separators=(",", ":"),
    ).encode()


def _reply(token: str, ts: int, replica: int, **extra) -> bytes:
    return json.dumps(
        {"type": "reply", "view": 0, "timestamp": ts, "client": token,
         "replica": replica, "result": "awesome!", "sig": "ab" * 64, **extra},
        separators=(",", ":"),
    ).encode()


class _StubbedGateway:
    """A ClientGateway on its own loop and thread, in front of ``n``
    listening sockets. ``links[r]`` is replica r's end of its link, the
    hello already read."""

    def __init__(self, n: int = 4, **gateway_kw):
        self.listeners = []
        for _ in range(n):
            ls = socket.socket()
            ls.bind(("127.0.0.1", 0))
            ls.listen(8)
            ls.settimeout(10)
            self.listeners.append(ls)
        config, _ = make_local_cluster(n, base_port=1)
        config = dataclasses.replace(config, replicas=[
            dataclasses.replace(ident, port=ls.getsockname()[1])
            for ident, ls in zip(config.replicas, self.listeners)
        ])
        self.gw = ClientGateway(config, host="127.0.0.1", **gateway_kw)
        self.loop = asyncio.new_event_loop()
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._ready.wait(10), "gateway never started"
        self.links = []
        for ls in self.listeners:
            conn, _ = ls.accept()
            conn.settimeout(10)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            (hello,) = _recv_frames(conn, 1)
            assert json.loads(hello)["role"] == "gateway"
            self.links.append(conn)
        self.clients = []

    def _run(self) -> None:
        asyncio.set_event_loop(self.loop)
        self._stop = asyncio.Event()

        async def main():
            await self.gw.start()
            self._ready.set()
            await self._stop.wait()
            await self.gw.stop()

        self.loop.run_until_complete(main())
        self.loop.close()

    def on_loop(self, fn):
        """Run ``fn()`` on the gateway's loop and return what it gives."""
        async def call():
            return fn()

        return asyncio.run_coroutine_threadsafe(
            call(), self.loop
        ).result(10)

    def client(self) -> socket.socket:
        s = socket.create_connection(("127.0.0.1", self.gw.listen_port), timeout=10)
        s.settimeout(10)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.clients.append(s)
        return s

    def registered_client(self, token: str, ts: int = 1) -> socket.socket:
        """A client whose token the gateway has a route for: its first
        request has reached the primary's link."""
        s = self.client()
        line = _request(token, ts)
        s.sendall(line + b"\n")
        assert _recv_frames(self.links[0], 1) == [line]
        return s

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        for s in self.clients + self.links + self.listeners:
            s.close()
        self.loop.call_soon_threadsafe(self._stop.set)
        self._thread.join(10)
        assert not self._thread.is_alive(), "gateway never stopped"


@pytest.mark.parametrize("arrival", ["one-segment", "line-split-in-two"])
def test_gateway_forwards_request_bytes_unchanged_in_order(arrival):
    """Several clients' pipelined lines, each client's in ONE segment or
    with a line cut across two, reach the primary's link as the same bytes
    in each client's order, in a 4-byte frame each and nowhere else."""
    with _StubbedGateway() as stub:
        sent = {}
        for c in range(3):
            token = f"{GATEWAY_CLIENT_PREFIX}order-{c}"
            sent[token] = [
                _request(token, ts, op=f"op-{c}-{ts}-" + "x" * (ts % 7))
                for ts in range(1, 21)
            ]
        socks = {token: stub.client() for token in sent}
        for token, lines in sent.items():
            block = b"".join(line + b"\n" for line in lines)
            if arrival == "one-segment":
                socks[token].sendall(block)
            else:
                cut = len(block) // 2 + 3  # inside a line
                assert block[cut - 1 : cut] != b"\n"
                socks[token].sendall(block[:cut])
                time.sleep(0.05)
                socks[token].sendall(block[cut:])
        got = {token: [] for token in sent}
        for payload in _recv_frames(stub.links[0], 60):
            got[json.loads(payload)["client"]].append(payload)
        assert got == sent
        m = stub.gw.metrics()
        assert m["gateway_forwarded"] == 60 and m["backpressure_events"] == 0
        for link in stub.links[1:]:  # fresh requests go to the primary alone
            link.settimeout(0.05)
            with pytest.raises(socket.timeout):
                link.recv(1)


_ODD_FRAMES = {
    "many-frames-a-segment": [],
    "frame-split-across-segments": [],
    "not-json": [b"\x00\xffnot json at all", b"{\"client\": \"gw/reply-0\""],
    "json-but-no-object": [b"[1,2]", b"\"gw/reply-0\"", b"17", b"null"],
    # Frames that would parse as one document if a reader joined them.
    "parse-only-when-joined": [
        b"1]", b"[2", b"", b"{\"a\":1},{\"b\":2}", b"{\"client\":\"gw/reply-0\",",
        b"\"timestamp\":1}",
    ],
    "unknown-token": [],
}


@pytest.mark.parametrize("case", sorted(_ODD_FRAMES))
def test_gateway_routes_each_reply_byte_for_byte(case):
    """Replies from four links, many frames a segment, with the case's odd
    frames among them: every client receives exactly its own replies'
    bytes and a newline, in each link's order; every replica's copy is
    forwarded; the odd frames are skipped."""
    with _StubbedGateway() as stub:
        tokens = [f"{GATEWAY_CLIENT_PREFIX}reply-{c}" for c in range(2)]
        socks = [stub.registered_client(token) for token in tokens]
        expected = {token: {r: [] for r in range(4)} for token in tokens}
        for r, link in enumerate(stub.links):
            frames = []
            odd = list(_ODD_FRAMES[case])
            for k in range(40):
                token = tokens[(k + r) % 2]
                # A padded reply now and then, so that frames differ in size.
                payload = _reply(token, k, r, pad="p" * (k % 5 * 31))
                frames.append(payload)
                expected[token][r].append(payload)
                if case == "unknown-token" and k % 3 == 0:
                    frames.append(_reply("gw/somebody-elses", k, r))
                if odd and k % 4 == 1:
                    frames.append(odd.pop(0))
            assert not odd
            for token in tokens:  # the fence: this link's last word to each
                payload = _reply(token, 10**6, r)
                frames.append(payload)
                expected[token][r].append(payload)
            block = b"".join(_frame(p) for p in frames)
            if case == "frame-split-across-segments":
                cut = len(block) // 2  # inside a frame, or inside a header
                link.sendall(block[:cut])
                time.sleep(0.05)
                link.sendall(block[cut:])
            else:
                link.sendall(block)
        for token, sock in zip(tokens, socks):
            got = {r: [] for r in range(4)}
            fences = 0
            buf = b""
            while fences < 4:
                chunk = sock.recv(1 << 16)
                assert chunk, "the gateway closed a client's connection"
                buf += chunk
                *lines, buf = buf.split(b"\n")
                for line in lines:
                    reply = json.loads(line)
                    got[reply["replica"]].append(line)
                    fences += reply["timestamp"] == 10**6
            assert buf == b""
            assert got == expected[token]
        assert stub.gw.metrics()["replies_routed"] == 4 * (40 + 2)


def test_gateway_burst_shares_its_writes():
    """Engagement: 64 lines in one segment on one connection leave in
    fewer writes than messages; messages a write is above 1."""
    with _StubbedGateway() as stub:
        token = f"{GATEWAY_CLIENT_PREFIX}burst"
        lines = [_request(token, ts) for ts in range(1, 65)]
        stub.client().sendall(b"".join(line + b"\n" for line in lines))
        assert _recv_frames(stub.links[0], 64) == lines
        m = stub.gw.metrics()
        assert m["gateway_forwarded"] == 64
        assert 1 <= m["writes"] < m["gateway_forwarded"]
        assert (m["gateway_forwarded"] + m["replies_routed"]) / m["writes"] > 1
        assert 1 <= m["reads"] <= 64


def test_gateway_lone_message_waits_on_no_timer():
    """A request with nothing else pending is forwarded, and its reply
    routed, by the turn's own flush: no timer is set anywhere on the way
    (the link keeper, the one sleeper, is stopped first)."""
    with _StubbedGateway() as stub:
        token = f"{GATEWAY_CLIENT_PREFIX}lone"
        sock = stub.registered_client(token, ts=1)
        timers = []

        def watch():
            stub.gw._keeper_task.cancel()
            for name in ("call_later", "call_at"):
                real = getattr(stub.loop, name)

                def spy(when, callback, *args, _real=real, _name=name, **kw):
                    timers.append((_name, when, callback))
                    return _real(when, callback, *args, **kw)

                setattr(stub.loop, name, spy)

        stub.on_loop(watch)
        before = stub.gw.metrics()
        line, reply = _request(token, 2), _reply(token, 2, 0)
        t0 = time.monotonic()
        sock.sendall(line + b"\n")
        assert _recv_frames(stub.links[0], 1) == [line]
        stub.links[0].sendall(_frame(reply))
        assert _recv_lines(sock, reply) == [reply]
        assert time.monotonic() - t0 < 5.0
        assert timers == []
        after = stub.gw.metrics()
        assert after["writes"] - before["writes"] == 2  # one each way
        assert after["replies_routed"] - before["replies_routed"] == 1


def test_gateway_client_that_never_reads_is_bounded():
    """A client that never reads: what the gateway holds for it stays
    under the outbound bound, what it drops is counted, and the other
    client on the same links is served."""
    with _StubbedGateway() as stub:
        deaf_token = f"{GATEWAY_CLIENT_PREFIX}deaf"
        fine_token = f"{GATEWAY_CLIENT_PREFIX}fine"
        deaf = stub.registered_client(deaf_token)
        fine = stub.registered_client(fine_token)
        del deaf  # connected, registered, and never read from
        big = "r" * (1 << 16)
        n = 4 * _MAX_WRITE_BUFFER // len(big)  # four bounds' worth
        for k in range(n):
            stub.links[0].sendall(_frame(_reply(deaf_token, k, 0, pad=big)))
        fence = _reply(fine_token, 1, 0)
        stub.links[0].sendall(_frame(fence))
        assert _recv_lines(fine, fence) == [fence]
        m = stub.gw.metrics()
        assert 0 < m["backpressure_events"] < n
        assert m["replies_routed"] == n + 1
        conn = stub.gw._routes[deaf_token]
        held = stub.on_loop(
            lambda: conn.transport.get_write_buffer_size()
            + sum(map(len, conn.pending))
        )
        assert 0 < held <= _MAX_WRITE_BUFFER


def test_gateway_retransmission_reaches_every_link():
    """A fresh (token, ts) goes to the primary alone; the same line again
    goes to all n links, byte for byte."""
    with _StubbedGateway() as stub:
        token = f"{GATEWAY_CLIENT_PREFIX}again"
        sock = stub.registered_client(token, ts=7)
        line = _request(token, 7)
        sock.sendall(line + b"\n")
        for link in stub.links:
            assert _recv_frames(link, 1) == [line]
        older = _request(token, 3)  # below the high-water mark: the same rule
        sock.sendall(older + b"\n")
        for link in stub.links:
            assert _recv_frames(link, 1) == [older]
        assert stub.gw.metrics()["gateway_forwarded"] == 3


def test_gateway_admission_refuses_with_a_line_and_readmits():
    """With an in-flight cap, requests past it get their ``overloaded``
    line and are not forwarded; a reply retires what it completes, and the
    next request is admitted again. A retransmission always passes."""
    with _StubbedGateway(max_inflight=2) as stub:
        token = f"{GATEWAY_CLIENT_PREFIX}adm"
        sock = stub.client()
        lines = [_request(token, ts) for ts in range(1, 5)]
        sock.sendall(b"".join(line + b"\n" for line in lines))
        assert _recv_frames(stub.links[0], 2) == lines[:2]

        def overloaded(ts):
            return json.dumps(
                {"type": "overloaded", "client": token, "timestamp": ts,
                 "replica": -1}, separators=(",", ":"),
            ).encode()

        assert _recv_lines(sock, overloaded(4)) == [overloaded(3), overloaded(4)]
        assert stub.gw.metrics()["inflight"] == 2
        # The reply for ts=2 completes 1 and 2; then 5 is admitted.
        reply = _reply(token, 2, 0)
        stub.links[0].sendall(_frame(reply))
        assert _recv_lines(sock, reply) == [reply]
        assert stub.gw.metrics()["inflight"] == 0
        sock.sendall(_request(token, 5) + b"\n" + lines[1] + b"\n")
        assert _recv_frames(stub.links[0], 2) == [_request(token, 5), lines[1]]
        for link in stub.links[1:]:  # ts=2 again: a retransmission, to all
            assert _recv_frames(link, 1) == [lines[1]]
        m = stub.gw.metrics()
        assert m["overload_rejections"] == 2 and m["gateway_forwarded"] == 4


def test_gateway_prints_its_counters_when_told_to_stop(tmp_path):
    """SIGTERM: the gateway's last line is its ``metrics()`` document, with
    the counters messages a write is made of. That line is how a benchmark
    run, which scrapes no gateway, leaves them in its ``gateway.log``."""
    config, _seeds = make_local_cluster(4, base_port=1)  # ports 1-4: dead
    cfg_path = tmp_path / "network.json"
    cfg_path.write_text(config.to_json())
    proc = subprocess.Popen(
        [sys.executable, "-m", "pbft_tpu.net.gateway", "--config",
         str(cfg_path), "--port", "0", "--host", "127.0.0.1"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ, PYTHONPATH=str(REPO)),
    )
    try:
        assert re.match(r"gateway listening on \d+", proc.stdout.readline())
        proc.terminate()
        out, _ = proc.communicate(timeout=15)
    finally:
        proc.kill()
    doc = json.loads(out.strip().splitlines()[-1])
    assert {"gateway_forwarded", "replies_routed", "reads", "writes"} <= set(doc)
    assert doc["writes"] == 0 and doc["backpressure_events"] == 0
    assert proc.returncode == 128 + 15


_PARSE_CASES = [
    b'{"client":"gw/a","timestamp":1}',
    b' \t{"client":"gw/a"}\r\n ',  # whitespace round the value
    b'\xef\xbb\xbf{"client":"gw/a"}',  # a byte order mark
    '{"client":"gw/é中"}'.encode(),  # UTF-8 beyond ASCII
    '{"client":"gw/a"}'.encode("utf-16"),  # what json.loads detects
    b'{"client":"gw/\\ud800"}',  # a lone surrogate, escaped
    b'{"client":"gw/\xed\xa0\x80"}',  # a lone surrogate, raw
    b'{"a":NaN,"b":-Infinity}',
    b'{"a":1}{"b":2}',  # extra data: refused
    b'{"a":1},{"b":2}',
    b'{"a":1',
    b'',
    b' ',
    b'nul',
    b'\xff\xfe\x00',
    b'[1,2]',
    b'"gw/a"',
    b'1]',
]


@pytest.mark.parametrize("payload", _PARSE_CASES, ids=range(len(_PARSE_CASES)))
def test_gateway_parse_accepts_what_json_loads_accepts(payload):
    """The gateway's one-decoder parse is ``json.loads`` of the same bytes:
    the same value where that gives one, None where that raises."""
    try:
        expected = json.loads(payload)
    except ValueError:
        expected = None
    got = _parse(payload)
    # As text: NaN is unequal to itself.
    assert json.dumps(got) == json.dumps(expected)
    assert type(got) is type(expected)

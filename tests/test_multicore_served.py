"""The multi-core replica on the served path (ISSUE 40): ``pbftd --net-threads
N`` behind one gateway process and a verify service, with a fsynced WAL and
batches of 32, under 32 connections that keep 32 requests outstanding each.
Up to PR 39 a sharded cluster stopped after one or two sequence numbers of
such a burst in four runs of six (``core/net_shard.cc`` ``WakeFd::drain``
cleared its flag before it emptied the fd, its read() swallowed the write of
a producer that had seen the flag cleared, and the consensus thread was never
woken again), so every sharded case serves FIVE fresh clusters here.

Every acknowledged request is held to the benchmark's plain reference
(``chipbench/reference``): the result the reference executes, by its quorum
rule, each signature checked (all of them by the host's verifier, a sample by
the RFC 8032 reference); the four replicas end on one executed count and one
chain digest in view 0 with every vote in a WAL and nothing dropped at a
thread boundary; and the set of (client, timestamp, result) acknowledged is
the same at every thread count, because it is the reference's.

The second test is the WAL guarantee ACROSS the thread boundary: the test sits
in a replica's place, and whatever vote reaches its socket is already in the
sender's log on disk (``flush_wal`` runs on the consensus thread before
``emit`` hands anything to a pipeline; the ``send()`` is a shard's).
"""

from __future__ import annotations

import functools
import json
import selectors
import socket
import struct
import sys
import threading
import time
from pathlib import Path

import pytest

from pbft_tpu import native
from pbft_tpu.consensus import wal as wal_format
from pbft_tpu.net import PbftClient, VerifyServiceDaemon
from pbft_tpu.net.launcher import LocalCluster
from pbft_tpu.net.secure import wire_hello_version

from test_f5_served import _fetch, _ShapedEngine
from test_gateway import _start_gateway, _stop

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO / "chipbench"))

from reference import ed25519_ref, state_machine  # noqa: E402

N, F = 4, 1
CONNS, OUTSTANDING, EACH = 32, 32, 48  # 1,536 requests a cluster, 1,024 of them at once
TIME_LIMIT_S = 300  # the test's own: a cluster that stops fails here, not at the run's limit
CASES = [(1, "sig", False), (2, "sig", False), (4, "sig", False), (2, "mac", True)]


def _operation(conn: int, ts: int) -> str:
    return f"mc-{conn:02d}-{ts:04d}"


def _burst(addr: str, tag: str, deadline: float) -> dict:
    """32 connections to the gateway, each keeping 32 requests outstanding
    until it has sent 48; a request is acknowledged once 2f+1 distinct
    replicas have answered it (enough for either quorum rule). Returns
    {(client, timestamp): (operation, replies)}."""
    host, port = addr.rsplit(":", 1)
    sel = selectors.DefaultSelector()
    tokens = [f"gw/{tag}-{i}" for i in range(CONNS)]
    index = {t: i for i, t in enumerate(tokens)}
    socks, bufs, next_ts = [], [b""] * CONNS, [1] * CONNS
    open_: dict = {}
    acked: dict = {}

    def send(i: int) -> None:
        ts = next_ts[i]
        next_ts[i] += 1
        open_[(i, ts)] = {}
        socks[i].sendall(
            ('{"client":"%s","operation":"%s","timestamp":%d,"type":"client-request"}\n'
             % (tokens[i], _operation(i, ts), ts)).encode())

    try:
        for i in range(CONNS):
            s = socket.create_connection((host, int(port)), timeout=10)
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sel.register(s, selectors.EVENT_READ, i)
            socks.append(s)
        for i in range(CONNS):
            for _ in range(OUTSTANDING):
                send(i)
        while len(acked) < CONNS * EACH:
            assert time.monotonic() < deadline, (
                f"{len(acked)} of {CONNS * EACH} acknowledged, then nothing: the cluster stopped")
            for key, _ in sel.select(0.25):
                i = key.data
                chunk = key.fileobj.recv(1 << 18)
                assert chunk, f"the gateway closed connection {i}"
                lines = (bufs[i] + chunk).split(b"\n")
                bufs[i] = lines.pop()
                for raw in lines:
                    if not raw:
                        continue
                    reply = json.loads(raw)
                    at = (index.get(reply.get("client")), reply.get("timestamp"))
                    votes = open_.get(at)
                    if votes is None:
                        continue  # acknowledged already
                    assert reply.get("type") == "client-reply", reply
                    votes[reply["replica"]] = reply
                    if len(votes) >= 2 * F + 1:
                        del open_[at]
                        acked[(tokens[at[0]], at[1])] = (_operation(*at), list(votes.values()))
                        if next_ts[at[0]] <= EACH:
                            send(at[0])
    finally:
        for s in socks:
            s.close()
        sel.close()
    return acked


def _settled(cluster, deadline: float) -> list:
    while True:  # trailing commits and checkpoints land
        final = [json.loads(_fetch(port, "/status")) for port in cluster.metrics_ports]
        if len({d["chain_digest"] for d in final}) == 1 and all(
            d["inbox_depth"] == 0 and d["executed_upto"] == d["committed_upto"] for d in final
        ):
            return final
        assert time.monotonic() < deadline, [(d["executed_upto"], d["committed_upto"]) for d in final]
        time.sleep(0.2)


@pytest.mark.parametrize("net_threads, fastpath, tentative", CASES)
def test_a_served_cluster_acks_a_burst_of_1024_by_the_reference_at_every_thread_count(
    net_threads, fastpath, tentative
):
    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    deadline = time.monotonic() + TIME_LIMIT_S
    rounds = 5 if net_threads > 1 else 1
    expected = {
        (f"gw/mc{net_threads}{fastpath}-{i}", ts, state_machine.execute(_operation(i, ts)))
        for i in range(CONNS) for ts in range(1, EACH + 1)
    }
    checked_by_reference = 0
    daemon = VerifyServiceDaemon(backend="auto", engine=_ShapedEngine()).start(wait_ready=True)
    try:
        for _ in range(rounds):
            with LocalCluster(
                n=N, verifier=daemon.address, net_threads=net_threads, fastpath=fastpath,
                tentative=tentative, wal=True, wal_fsync=True, batch_max_items=32,
                batch_flush_us=2000, vc_timeout_ms=10000, metrics_ports=True,
                extra_env=[{"PBFT_VERIFY_CONNECT_MS": "5000"} for _ in range(N)],
            ) as cluster:
                assert cluster.config.f == F
                proc, addr = _start_gateway(cluster)
                try:
                    acked = _burst(addr, f"mc{net_threads}{fastpath}", deadline)
                    final = _settled(cluster, deadline)
                finally:
                    _stop(proc)
                pubkeys = [bytes.fromhex(r.pubkey) for r in cluster.config.replicas]
            # Every request acknowledged, each by the reference's rule.
            quorum = functools.partial(state_machine.quorum_result, f=F, n=N, pubkeys=pubkeys)
            results = set()
            for k, ((client, ts), (operation, replies)) in enumerate(sorted(acked.items())):
                assert all(r["client"] == client and r["timestamp"] == ts for r in replies)
                want = state_machine.execute(operation)
                assert quorum(replies, verify=native.verify) == want, (client, ts, replies)
                if k % 257 == 0:  # and a sample by the RFC 8032 reference itself
                    assert quorum(replies, verify=ed25519_ref.verify) == want
                    checked_by_reference += 1
                results.add((client, ts, want))
            assert results == expected
            # Four replicas, one history, in view 0, every vote in a WAL.
            assert len(final) == N and {d["view"] for d in final} == {0}
            assert len({d["executed_upto"] for d in final}) == 1
            assert len({d["chain_digest"] for d in final}) == 1
            assert all(d["executed"] >= len(expected) for d in final)
            assert {d["net_threads"] for d in final} == {net_threads}
            assert {d["mode"] for d in final} == {fastpath}
            votes_missing = sum(max(0, 2 * d["executed_upto"] - d["wal_appends"]) for d in final)
            assert votes_missing == 0 and all(d["wal_fsyncs"] > 0 for d in final)
            assert sum(d["verify_service_fallbacks"] + d["verify_deadline_fired"] for d in final) == 0
            if net_threads > 1:
                # Nothing was lost at a thread boundary, on any replica.
                for d in final:
                    assert d["shard_dropped"] == {"pipeline": 0, "inbox": 0, "replies": 0}
                    assert len(d["shard_us"]) == len(d["pipe_us"]) == net_threads
            else:
                assert all("shard_dropped" not in d and d["cross_thread_wakes"] == 0 for d in final)
    finally:
        daemon.stop()
    assert checked_by_reference >= rounds


# -- the frames a send() carries, whichever thread sends -----------------------------


@pytest.mark.parametrize("net_threads", [1, 2])
def test_a_scrape_carries_the_frames_and_the_send_calls_of_whichever_threads_sent(net_threads):
    """ISSUE 41: a connection is flushed once for what one emit() (in a
    shard: one drained stretch of commands) queued on it. The two counters
    that show it are the net loop's at one thread and the shards' at two,
    where the consensus thread owns no socket and its own two integers stay
    0: the shards' counts are added into the same two names."""
    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    import stats  # chipbench's reader of the Prometheus text

    deadline = time.monotonic() + TIME_LIMIT_S
    with LocalCluster(
        n=N, net_threads=net_threads, wal=True, batch_max_items=32, batch_flush_us=2000,
        vc_timeout_ms=10000, metrics_ports=True,
    ) as cluster:
        proc, addr = _start_gateway(cluster)
        try:
            acked = _burst(addr, f"fps{net_threads}", deadline)
            final = _settled(cluster, deadline)
            scrapes = [stats.parse_prometheus(_fetch(port, "/metrics")) for port in cluster.metrics_ports]
        finally:
            _stop(proc)
    assert len(acked) == CONNS * EACH and {d["net_threads"] for d in final} == {net_threads}
    for rid, m in enumerate(scrapes):
        frames, calls = m[("pbft_frames_out_total", "")], m[("pbft_send_calls_total", "")]
        # Votes to three peers and a reply a request, at the least.
        assert frames >= CONNS * EACH and 0 < calls <= frames, (rid, frames, calls)
        # A batch of 32 executes inside one emit: replies to the one gateway
        # link share a send(). By how many is the machine's to say at two
        # threads (a shard that is awake drains a command or two a stretch:
        # 2.0 on a loaded host, 6.5 in the benchmark's cell), so: some did.
        assert frames > calls, (rid, frames, calls)


# -- the WAL guarantee across the thread boundary ----------------------------------

_KIND = {"pre-prepare": wal_format.WAL_VOTE_PRE_PREPARE, "prepare": wal_format.WAL_VOTE_PREPARE,
         "commit": wal_format.WAL_VOTE_COMMIT}


class _ReplicaTap:
    """A listener in a (stopped) replica's place: answers each peer's hello
    with a plain one that offers no codec (so the link stays canonical
    JSON), and for every vote that arrives reads the SENDER's log from the
    disk, there and then."""

    def __init__(self, port: int, wal_dir: Path):
        self.wal_dir = wal_dir
        self.seen: list = []     # (type, sender, view, seq)
        self.missing: list = []  # votes that reached the socket before the log
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", port))
        self.sock.listen(16)
        self.sock.settimeout(0.2)
        self.stop = threading.Event()
        self.threads = [threading.Thread(target=self._accept, daemon=True)]
        self.threads[0].start()

    def _accept(self) -> None:
        while not self.stop.is_set():
            try:
                conn, _ = self.sock.accept()
            except (TimeoutError, OSError):
                continue
            t = threading.Thread(target=self._serve, args=(conn,), daemon=True)
            self.threads.append(t)
            t.start()

    def _serve(self, conn: socket.socket) -> None:
        conn.settimeout(0.2)
        buf = b""
        greeted = False
        while not self.stop.is_set():
            try:
                chunk = conn.recv(1 << 16)
            except TimeoutError:
                continue
            except OSError:
                return
            if not chunk:
                return
            buf += chunk
            while len(buf) >= 4 and len(buf) >= 4 + struct.unpack(">I", buf[:4])[0]:
                size = struct.unpack(">I", buf[:4])[0]
                payload, buf = buf[4:4 + size], buf[4 + size:]
                msg = json.loads(payload)
                if not greeted:
                    assert msg["type"] == "hello", msg
                    greeted = True
                    ack = json.dumps({"type": "hello", "ver": wire_hello_version(), "node": 3}).encode()
                    conn.sendall(struct.pack(">I", len(ack)) + ack)
                    continue
                if msg.get("type") in _KIND:
                    self._check(msg)

    def _check(self, msg: dict) -> None:
        sender, view, seq = msg["replica"], msg["view"], msg["seq"]
        self.seen.append((msg["type"], sender, view, seq))
        state = wal_format.decode_bytes((self.wal_dir / f"replica-{sender}.wal").read_bytes())
        logged = state.votes.get((_KIND[msg["type"]], view, seq))
        pruned = state.checkpoint is not None and state.checkpoint[0] >= seq
        if logged != msg["digest"] and not pruned:
            self.missing.append((msg["type"], sender, view, seq, logged))

    def close(self) -> None:
        self.stop.set()
        self.sock.close()
        for t in self.threads:
            t.join(2)


@pytest.mark.parametrize("net_threads", [1, 2])
def test_a_vote_on_the_socket_is_already_in_its_senders_log_on_disk(net_threads):
    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    requests = 24
    with LocalCluster(n=N, net_threads=net_threads, wal=True, wal_fsync=True,
                      metrics_ports=True) as cluster:
        cluster.kill(3)  # the three left are a quorum of 2f+1; the test hears what 3 would
        tap = _ReplicaTap(cluster.config.replicas[3].port, Path(cluster.tmpdir.name) / "wal")
        try:
            client = PbftClient(cluster.config)
            try:
                for k in range(requests):
                    req = client.request(f"tap-{net_threads}-{k}")
                    assert client.wait_result(req.timestamp, timeout=60) == "awesome!"
            finally:
                client.close()
            time.sleep(0.5)  # the last commits reach the tap
            final = [json.loads(_fetch(port, "/status")) for port in cluster.metrics_ports[:3]]
        finally:
            tap.close()
    assert {d["net_threads"] for d in final} == {net_threads}
    assert all(d["executed_upto"] >= requests and d["wal_fsyncs"] > 0 for d in final)
    # The tap heard the primary's pre-prepares and every replica's votes, a
    # sequence number at a time, and found each in its sender's log.
    kinds = {kind: [s for s in tap.seen if s[0] == kind] for kind in _KIND}
    assert len({s[3] for s in kinds["pre-prepare"]}) >= requests
    assert {s[1] for s in kinds["pre-prepare"]} == {0}
    assert {s[1] for s in kinds["prepare"]} == {1, 2} and {s[1] for s in kinds["commit"]} == {0, 1, 2}
    assert len(tap.seen) >= 6 * requests
    assert tap.missing == []

"""Client-gateway tier (ISSUE 10): multiplex thousands of client
connections onto a few persistent replica links.

The reference's client contract (raw JSON request in, reply *dialed back*
to the client's advertised host:port) costs the cluster ~n sockets per
concurrent client — at the ROADMAP's "millions of users" scale that is FD
exhaustion long before it is a throughput problem. The gateway keeps the
telnet-able downstream contract (raw JSON lines in, raw JSON reply lines
out, all on ONE connection) and swaps the upstream shape: one framed,
persistent link per replica, announced by a ``role=gateway`` hello, over
which client requests flow up and replies fan BACK (pbftd trusts the
link instead of dialing the client; core/net.cc).
10k concurrent clients then cost the cluster ~n·gateways sockets.

Identity: a gateway-routed client addresses itself with a ROUTING TOKEN,
never a dialable address — the ``gw/``-prefixed ``client`` field
(GATEWAY_CLIENT_PREFIX, mirrored by core/net.h kGatewayClientPrefix;
constants lint). Tokens are client-chosen and stable across reconnects,
so per-(client, ts) exactly-once and the cached-reply retransmission path
(PBFT §4.1) survive a gateway restart exactly as they survive a client
redial. The gateway forwards request bytes UNCHANGED (canonicality is
end-to-end); replies are routed downstream by the token each reply
carries, and every replica's copy is forwarded — the f+1 reply-quorum
count stays where the paper puts it, in the client.

Forwarding policy: a fresh (token, ts) goes to the current primary
(tracked from the view field of routed replies); a retransmission (ts
not above the token's high-water mark) broadcasts to ALL replicas —
the paper's client liveness rule, which forces forwarding and
eventually a view change on a faulty primary.

Run one gateway:  python -m pbft_tpu.net.gateway --config network.json \
                      [--port P] [--metrics-port M]
Secure clusters are refused upstream: a gateway holds no replica
identity, so the signed-DH handshake cannot admit it.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import signal
import socket
import struct
import threading
import time
from typing import Dict, List, Optional

from ..consensus.config import ClusterConfig
from ..consensus.messages import ClientRequest
from ..utils import (
    MetricsRegistry,
    count_open_fds,
    read_rss_bytes,
    start_metrics_server,
)
from ..utils.trace_schema import HEALTH_DOC_VERSION
from . import secure
from .client import PbftClient

# Gateway-routed client identities carry this prefix (mirrored by
# core/net.h kGatewayClientPrefix; constants lint): such a "client
# address" is a routing token, never a dialable host:port.
GATEWAY_CLIENT_PREFIX = "gw/"

# Bounded outbound per downstream/upstream connection (mirrors
# core/net.cc kMaxConnOutbound; constants lint).
_MAX_WRITE_BUFFER = 8 << 20
# Token bookkeeping bound: on overflow the maps clear — a cleared route
# re-registers on the client's next request, a cleared high-water mark
# turns one fresh request into a broadcast (extra frames, never loss).
_MAX_TOKENS = 1 << 17

# A raw-JSON client line may not exceed this (same bound as the replica
# gateways): longer input is a protocol violation on an unauthenticated
# socket and drops the connection instead of buffering without bound.
MAX_CLIENT_LINE = 1 << 20

# A frame above this drops the link it came on (corrupt length prefix).
_MAX_FRAME = 1 << 24
# The most one read takes (asyncio's own read size for a stream socket).
_READ_SIZE = 256 << 10

_U32 = struct.Struct(">I")
_DECODER = json.JSONDecoder()


def _frame_bytes(payload: bytes) -> bytes:
    return len(payload).to_bytes(4, "big") + payload


def _parse(payload: bytes):
    """What ``json.loads(payload)`` gives, or None where it raises. The
    common case (UTF-8, exactly one value, nothing round it) is one
    ``decode`` and one call of the one decoder; anything else goes to
    ``json.loads`` itself, so what is accepted is what it accepts."""
    try:
        text = payload.decode()
        obj, end = _DECODER.raw_decode(text)
        if end == len(text):
            return obj
    except ValueError:
        pass
    try:
        return json.loads(payload)
    except ValueError:
        return None


def gateway_hello() -> dict:
    """The version-carrying hello that opens every upstream link. The
    ``role`` field is the trust switch: pbftd marks the link as a
    gateway link (requests arrive on it, replies fan back over it)."""
    return {
        "type": "hello",
        "ver": secure.wire_hello_version(),
        "node": -1,
        "role": "gateway",
    }


class _Peer(asyncio.BufferedProtocol):
    """One connection of the gateway's. As a destination: the bytes bound
    for it wait in ``pending``, in arrival order, for the loop turn's one
    flush (``ClientGateway._flush``), which writes them as one block. As a
    source: every connection reads into the gateway's ONE receive buffer
    (a read and its handling are one uninterrupted step of the loop), and
    ``data_received`` is given the bytes that came, copied out once. A
    plain ``Protocol`` would have the transport allocate its whole
    256 KiB read size anew for every read, ~10 us of a read that brings
    a few hundred bytes."""

    __slots__ = ("gw", "transport", "pending")

    def __init__(self, gw: "ClientGateway"):
        self.gw = gw
        self.transport: Optional[asyncio.Transport] = None
        self.pending: List[bytes] = []

    def get_buffer(self, sizehint: int) -> bytearray:
        return self.gw._read_buffer

    def buffer_updated(self, nbytes: int) -> None:
        self.data_received(self.gw._read_view[:nbytes].tobytes())


class _ClientConn(_Peer):
    """One downstream client connection: raw JSON lines in, reply lines
    out."""

    __slots__ = ("tail", "tokens")

    def __init__(self, gw: "ClientGateway"):
        super().__init__(gw)
        self.tail = b""
        self.tokens: List[str] = []

    def connection_made(self, transport) -> None:
        self.transport = transport
        gw = self.gw
        gw.clients_open += 1
        gw._set_clients_gauge()
        gw._inbound.add(self)

    def data_received(self, data: bytes) -> None:
        """Every complete line of the read in one split; the unfinished
        tail is kept once."""
        gw = self.gw
        gw.reads += 1
        if self.tail:
            data = self.tail + data
        *lines, self.tail = data.split(b"\n")
        before = gw.forwarded
        for line in lines:
            line = line.strip()
            if line:
                gw._handle_line(line, self)
        if gw.metrics_registry.enabled:
            gw.metrics_registry.counter("pbft_gateway_forwarded_total").inc(
                gw.forwarded - before
            )
        if len(self.tail) > MAX_CLIENT_LINE:
            self.transport.close()  # oversized line: drop the connection

    def connection_lost(self, exc) -> None:
        gw = self.gw
        gw.clients_open -= 1
        gw._set_clients_gauge()
        gw._inbound.discard(self)
        for token in self.tokens:
            if gw._routes.get(token) is self:
                del gw._routes[token]


class _UpstreamLink(_Peer):
    """One persistent framed link to a replica. It exists from the moment
    its dial starts: frames bound for it meanwhile are ``held`` (bounded
    like a write buffer) and leave behind the hello."""

    __slots__ = ("rid", "task", "tail", "need", "held")

    def __init__(self, gw: "ClientGateway", rid: int):
        super().__init__(gw)
        self.rid = rid
        self.task: Optional[asyncio.Task] = None
        # An unfinished frame: what the reads have brought of it, and the
        # size at which it is worth looking again.
        self.tail = bytearray()
        self.need = 0
        self.held = bytearray()

    def connection_made(self, transport) -> None:
        self.transport = transport
        hello = json.dumps(gateway_hello(), separators=(",", ":")).encode()
        transport.write(_frame_bytes(hello) + self.held)
        self.held = bytearray()

    def data_received(self, data: bytes) -> None:
        """Walk the read's frames by offset; the remainder is copied once.
        Hello-acks are consumed, rejects are loud, and every reply frame
        routes downstream by its token."""
        gw = self.gw
        gw.reads += 1
        if self.tail:
            self.tail += data
            if len(self.tail) < self.need:
                return
            data = bytes(self.tail)
            self.tail.clear()
        routes = gw._routes
        end = len(data)
        off = 0
        while end - off >= 4:
            (n,) = _U32.unpack_from(data, off)
            if n > _MAX_FRAME:
                self.transport.close()  # corrupt frame: drop the link
                return
            stop = off + 4 + n
            if stop > end:
                break
            payload = data[off + 4 : stop]
            off = stop
            obj = _parse(payload)
            if not isinstance(obj, dict):
                continue
            kind = obj.get("type")
            if kind == "hello":
                continue  # the responder's version/codec ack
            if kind == "reject":
                print(
                    f"gateway: replica {self.rid} rejected link: "
                    f"{obj.get('reason')}",
                    flush=True,
                )
                self.transport.close()
                return
            token = obj.get("client")
            if not isinstance(token, str):
                continue
            view = obj.get("view")
            if isinstance(view, int) and view > gw._view:
                gw._view = view  # a view change re-aims fresh requests
            if gw._admission:
                ts = obj.get("timestamp")
                if isinstance(ts, int):
                    # Completion retires admission bookkeeping whether or
                    # not the downstream client is still there to hear.
                    gw._retire_inflight(token, ts)
            conn = routes.get(token)
            if conn is not None:  # else: not ours (fan-out copy) or gone
                gw.replies_routed += 1
                gw._queue(conn, payload + b"\n")
        if off < end:
            self.tail += data[off:]
            # With four bytes or more left the walk stopped at a frame of
            # n bytes that is not all here yet.
            self.need = 4 + n if end - off >= 4 else 4

    def connection_lost(self, exc) -> None:
        gw = self.gw
        if gw._links.get(self.rid) is self:
            del gw._links[self.rid]
        if not gw._stopping:
            # Upstream replica link died mid-run (ISSUE 12): the keeper
            # re-dials within a second — count the failover so a chaos
            # arm can attribute the blip.
            gw.upstream_failovers += 1
            if gw.metrics_registry.enabled:
                gw.metrics_registry.counter(
                    "pbft_gateway_failovers_total"
                ).inc()
            if gw.flight is not None:
                gw.flight.record(
                    "gateway_failover", view=gw._view, peer=self.rid
                )


class ClientGateway:
    """One gateway process: a raw-JSON line server for clients in front
    of n persistent framed replica links.

    Its unit of work is what one read delivered, not one message: a read
    is split and parsed in one pass, and what it sends is appended to a
    pending list of its destination; ONE flush a loop turn, scheduled
    with ``call_soon`` by the turn's first append, writes each list as
    one block. No timer, no hold, no threshold: a message alone in its
    turn leaves in that turn's flush."""

    def __init__(
        self,
        config: ClusterConfig,
        host: str = "0.0.0.0",
        port: int = 0,
        metrics_port: Optional[int] = None,
        max_inflight: int = 0,
        max_queue_depth: int = 0,
        flight=None,
    ):
        if config.secure:
            raise ValueError(
                "gateway tier requires a plaintext cluster: a gateway has "
                "no replica identity for the signed-DH handshake"
            )
        self.config = config
        self.host = host
        self.port = port
        self.listen_port = 0
        self.metrics_registry = MetricsRegistry(
            labels={"gateway": "0"}, enabled=metrics_port is not None
        )
        if self.metrics_registry.enabled:
            self.metrics_registry.preregister(emitter="gateway.py")
        self.metrics_port = metrics_port
        self._metrics_server = None
        self.metrics_listen_port = 0
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._server: Optional[asyncio.Server] = None
        # Accepted client connections, so stop() can close them: since
        # Python 3.12 Server.wait_closed() waits for every one.
        self._inbound: set = set()
        # token -> downstream connection (the reply route), and the
        # per-token forwarded-timestamp high-water mark (retransmission
        # detection).
        self._routes: Dict[str, _ClientConn] = {}
        self._last_ts: Dict[str, int] = {}
        # rid -> _UpstreamLink, dialing or up: one dial runs per replica.
        self._links: Dict[int, _UpstreamLink] = {}
        # Where every connection's reads land (_Peer.get_buffer).
        self._read_buffer = bytearray(_READ_SIZE)
        self._read_view = memoryview(self._read_buffer)
        # Destinations with pending bytes; non-empty = a flush is scheduled.
        self._dirty: List[_Peer] = []
        # Current view, tracked from routed replies: fresh requests go to
        # view % n, so a view change re-aims the firehose without any
        # client knowing.
        self._view = 0
        self._stopping = False
        self._keeper_task: Optional[asyncio.Task] = None
        self.clients_open = 0
        self.forwarded = 0
        self.replies_routed = 0
        self.backpressure_events = 0
        # How often the mechanism engages: reads handled and flushes that
        # reached a transport. Messages a write = (forwarded +
        # replies_routed) / writes.
        self.reads = 0
        self.writes = 0
        # Admission control (ISSUE 12): per-token in-flight cap +
        # a global queue-depth watermark. A FRESH request past either
        # bound is answered with an explicit {"type": "overloaded"} line
        # downstream and NOT forwarded; retransmissions of an already
        # in-flight (token, ts) always pass — liveness is never
        # admission-gated. In-flight entries prune when a reply routes
        # (per-client execution is timestamp-ordered, so a reply for ts
        # retires every entry at or below it). 0 disables either bound,
        # and with both at 0 nothing is kept in flight at all.
        self.max_inflight = max_inflight
        self.max_queue_depth = max_queue_depth
        self._admission = max_inflight > 0 or max_queue_depth > 0
        self._inflight: Dict[str, set] = {}
        self._inflight_total = 0
        self.overload_rejections = 0
        # Gateway-fabric failovers (ISSUE 12): upstream replica links this
        # gateway had to re-dial after they died mid-run.
        self.upstream_failovers = 0
        # Black-box flight recorder (utils/flight.py, --flight-file):
        # failover/overload events ship with the chaos bench's black
        # boxes the same way replica recorders do. None = one attribute
        # check per event site.
        self.flight = flight
        # Health-document uptime anchor (ISSUE 16).
        self._start_time = time.monotonic()

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> "ClientGateway":
        self._loop = asyncio.get_running_loop()
        self._server = await self._loop.create_server(
            lambda: _ClientConn(self), host=self.host, port=self.port
        )
        self.listen_port = self._server.sockets[0].getsockname()[1]
        if self.metrics_port is not None:
            # /status serves the gateway's health document (ISSUE 16) so
            # pbft_top can watch the tier alongside the replicas.
            self._metrics_server = start_metrics_server(
                self.metrics_registry, self.metrics_port,
                status_fn=self.metrics,
            )
            self.metrics_listen_port = self._metrics_server.server_address[1]
        # EVERY replica needs a live gateway link, not just the ones
        # requests flow to: a backup only ever SENDS on its link (the
        # reply fan-back for requests it saw via pre-prepare), so lazy
        # dial-on-send would leave backup replies with nowhere to go and
        # the client short of its f+1 quorum.
        self._keeper_task = self._loop.create_task(self._link_keeper())
        return self

    async def _link_keeper(self) -> None:
        while not self._stopping:
            for rid in range(self.config.n):
                self._link(rid)
            await asyncio.sleep(1.0)

    async def stop(self) -> None:
        self._stopping = True
        if self._keeper_task is not None:
            self._keeper_task.cancel()
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
            self._metrics_server.server_close()
        if self._server:
            self._server.close()
            for conn in list(self._inbound):
                conn.transport.close()
            await self._server.wait_closed()
        for link in list(self._links.values()):
            link.task.cancel()
            if link.transport is not None:
                link.transport.close()
        self._links.clear()

    def metrics(self) -> dict:
        return {
            # Health document (ISSUE 16): the gateway is a replica-less
            # process, so its document is the resource subset — no
            # progress watermarks or chain digests to report.
            "health_version": HEALTH_DOC_VERSION,
            "uptime_seconds": round(time.monotonic() - self._start_time, 6),
            "rss_bytes": read_rss_bytes(),
            "open_fds": count_open_fds(),
            "gateway_clients_open": self.clients_open,
            "gateway_forwarded": self.forwarded,
            "replies_routed": self.replies_routed,
            "reads": self.reads,
            "writes": self.writes,
            "backpressure_events": self.backpressure_events,
            "upstream_links": sum(
                link.transport is not None for link in self._links.values()
            ),
            "overload_rejections": self.overload_rejections,
            "gateway_failovers": self.upstream_failovers,
            "inflight": self._inflight_total,
            "view": self._view,
        }

    # -- the one write a destination a loop turn -----------------------------

    def _queue(self, peer: _Peer, data: bytes) -> None:
        pending = peer.pending
        if not pending:
            if not self._dirty:
                self._loop.call_soon(self._flush)
            self._dirty.append(peer)
        pending.append(data)

    def _flush(self) -> None:
        """Write what the turn's reads left pending, one block a
        destination. Bounded outbound against a slow reader, judged once
        a destination against the size this write would reach
        (drop-and-count): a dropped reply is re-fetched from the
        replicas' reply caches on retransmission, a dropped request is
        retransmission-covered."""
        dirty, self._dirty = self._dirty, []
        registry = self.metrics_registry
        for peer in dirty:
            chunks, peer.pending = peer.pending, []
            data = b"".join(chunks)
            transport = peer.transport
            if transport is None:  # an upstream link still dialing
                room = _MAX_WRITE_BUFFER - len(peer.held)
            elif transport.is_closing():
                continue  # replica down or client gone
            else:
                room = _MAX_WRITE_BUFFER - transport.get_write_buffer_size()
            # Counters move before the write: a peer may ask at once.
            if len(data) > room:
                self.backpressure_events += len(chunks)
                if registry.enabled:
                    registry.counter(
                        "pbft_write_backpressure_events_total"
                    ).inc(len(chunks))
            elif transport is None:
                peer.held += data
            else:
                self.writes += 1
                if registry.enabled:
                    registry.counter("pbft_gateway_writes_total").inc()
                transport.write(data)

    # -- downstream (clients) ------------------------------------------------

    def _set_clients_gauge(self) -> None:
        if self.metrics_registry.enabled:
            self.metrics_registry.gauge("pbft_gateway_clients_open").set(
                self.clients_open
            )

    def _handle_line(self, line: bytes, conn: _ClientConn) -> None:
        obj = _parse(line)
        if not isinstance(obj, dict):
            return
        token = obj.get("client")
        if not isinstance(token, str) or not token.startswith(
            GATEWAY_CLIENT_PREFIX
        ):
            # A dialable address through the gateway would re-open the
            # per-client-socket cost the tier exists to remove — and an
            # unauthenticated redirect channel. Drop it.
            return
        routes = self._routes
        if routes.get(token) is not conn:
            if token not in routes:
                conn.tokens.append(token)
            if len(routes) >= _MAX_TOKENS:
                routes.clear()
            routes[token] = conn
        ts = obj.get("timestamp")
        fresh = True
        if isinstance(ts, int):
            if self._last_ts.get(token, -1) >= ts:
                fresh = False
            else:
                if self._admission and not self._admit(token, ts, conn):
                    return
                if len(self._last_ts) >= _MAX_TOKENS:
                    self._last_ts.clear()
                self._last_ts[token] = ts
        self.forwarded += 1
        framed = _frame_bytes(line)
        if fresh:
            self._queue(self._link(self._view % self.config.n), framed)
        else:
            # The paper's client liveness rule by proxy: a retransmitted
            # request broadcasts to every replica, forcing forwards and
            # eventually a view change on a faulty primary.
            for rid in range(self.config.n):
                self._queue(self._link(rid), framed)

    def _admit(self, token: str, ts: int, conn: _ClientConn) -> bool:
        """Admission control (ISSUE 12): a fresh request past the
        per-token in-flight cap or the global watermark is rejected with
        an explicit overloaded line instead of queueing into the
        cluster's tail. Retransmissions always pass."""
        pend = self._inflight.setdefault(token, set())
        if ts in pend:
            return True
        if (self.max_inflight > 0 and len(pend) >= self.max_inflight) or (
            self.max_queue_depth > 0
            and self._inflight_total >= self.max_queue_depth
        ):
            self._reject_overloaded(token, ts, conn)
            return False
        pend.add(ts)
        self._inflight_total += 1
        return True

    def _reject_overloaded(self, token: str, ts: int, conn: _ClientConn) -> None:
        """Answer a rejected request with an explicit overloaded line —
        the client backs off with jitter (request_with_retry) instead of
        interpreting silence as a faulty primary."""
        self.overload_rejections += 1
        if self.metrics_registry.enabled:
            self.metrics_registry.counter(
                "pbft_overload_rejections_total"
            ).inc()
        if self.flight is not None:
            self.flight.record("overload_rejected", view=self._view, seq=ts)
        self._queue(
            conn,
            json.dumps(
                {
                    "type": "overloaded",
                    "client": token,
                    "timestamp": ts,
                    "replica": -1,
                },
                separators=(",", ":"),
            ).encode()
            + b"\n",
        )

    def _retire_inflight(self, token: str, ts: int) -> None:
        """A reply for (token, ts) routed downstream: per-client execution
        is timestamp-ordered, so every in-flight entry at or below ts is
        complete (or superseded) — prune them all."""
        pend = self._inflight.get(token)
        if not pend:
            return
        done = {t for t in pend if t <= ts}
        if done:
            pend.difference_update(done)
            self._inflight_total -= len(done)
        if not pend:
            del self._inflight[token]

    # -- upstream (replicas) -------------------------------------------------

    def _link(self, rid: int) -> _UpstreamLink:
        """The link to replica ``rid``: a dict lookup where it is up or
        dialing; only a dial makes a task."""
        link = self._links.get(rid)
        if link is None:
            link = self._links[rid] = _UpstreamLink(self, rid)
            link.task = self._loop.create_task(self._dial(link))
        return link

    async def _dial(self, link: _UpstreamLink) -> None:
        ident = self.config.identity(link.rid)
        try:
            await self._loop.create_connection(
                lambda: link, ident.host, ident.port
            )
        except OSError:
            # Replica down: PBFT tolerates f of these. What was held for
            # it goes; retransmission absorbs the loss.
            if self._links.get(link.rid) is link:
                del self._links[link.rid]


# -- the client side of the tier ---------------------------------------------

_token_seq_lock = threading.Lock()
_token_seq = 0


def next_token(prefix: str = "c") -> str:
    """A process-unique gateway routing token. Stable identity is the
    CALLER's job across reconnects (pass the same token back in); this
    only guarantees two clients in one process never collide."""
    global _token_seq
    with _token_seq_lock:
        _token_seq += 1
        return (
            f"{GATEWAY_CLIENT_PREFIX}{prefix}-"
            f"{threading.get_native_id():x}-{_token_seq:x}"
        )


class GatewayClient(PbftClient):
    """PbftClient surface over a gateway connection: same f+1
    signature-verified reply quorum (wait_result is inherited), but no
    dial-back listener — requests and replies share ONE socket, and the
    identity is a routing token instead of host:port.

    HA (ISSUE 12): pass SEVERAL gateway addresses and the client fails
    over on a dead socket — reconnect to the next gateway, same stable
    ``gw/`` token, and replay of the in-flight request lines. Because the
    token and timestamps are unchanged, the replicas' per-(client, ts)
    exactly-once guard + reply caches make the replay safe: a request the
    dead gateway already forwarded executes once and the replay is
    answered from the cache, one it never forwarded gets ordered now —
    completion stays 100% through a gateway death mid-request."""

    def __init__(
        self,
        config: ClusterConfig,
        gateway_addr,
        token: Optional[str] = None,
    ):
        # Deliberately no super().__init__: the base class would start a
        # dial-back listener, which is exactly what the gateway removes.
        self.config = config
        self.replies = []
        self._lock = threading.Lock()
        self._new_reply = threading.Condition(self._lock)
        self._send_lock = threading.Lock()
        self._timestamp = 0
        self.latency_log = {}
        self.address = token or next_token()
        self._addrs: List[str] = (
            [gateway_addr]
            if isinstance(gateway_addr, str)
            else list(gateway_addr)
        )
        self._addr_idx = 0
        # ts -> raw request line, for the failover replay. Entries retire
        # on the first reply seen for their timestamp (a partially-voted
        # request is re-covered by the normal retransmission path).
        self._inflight_lines: Dict[int, bytes] = {}
        self.failovers = 0
        self._closed = False
        self.sock = self._dial_gateway(first=True)
        self._rx_thread = threading.Thread(
            target=self._read_loop, args=(self.sock,), daemon=True
        )
        self._rx_thread.start()

    def _dial_gateway(self, first: bool = False) -> socket.socket:
        """Dial gateways round-robin starting at the current index;
        raises the last OSError when none answers."""
        last_err: Optional[OSError] = None
        for i in range(len(self._addrs)):
            idx = (self._addr_idx + (0 if first else 1) + i) % len(
                self._addrs
            )
            host, _, port = self._addrs[idx].rpartition(":")
            try:
                s = socket.create_connection((host, int(port)), timeout=10)
            except OSError as e:
                last_err = e
                continue
            s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._addr_idx = idx
            return s
        raise last_err or OSError("no gateway reachable")

    def _failover_locked(self, dead: socket.socket) -> None:
        """Replace a dead gateway socket (caller holds _send_lock): dial
        the next gateway, replay every in-flight request line under the
        SAME token, restart the reader. Raises OSError when no gateway
        answers (callers surface it or retry on their own timer)."""
        if self._closed or self.sock is not dead:
            return  # another thread already failed over
        try:
            dead.close()
        except OSError:
            pass
        s = self._dial_gateway()
        self.sock = s
        self.failovers += 1
        for ts in sorted(self._inflight_lines):
            try:
                s.sendall(self._inflight_lines[ts])
            except OSError:
                break  # the next _send_line attempt fails over again
        self._rx_thread = threading.Thread(
            target=self._read_loop, args=(s,), daemon=True
        )
        self._rx_thread.start()

    def _read_loop(self, sock: socket.socket) -> None:
        try:
            fh = sock.makefile("rb")
            for line in fh:
                rx = time.monotonic()
                line = line.strip()
                if not line:
                    continue
                try:
                    reply = json.loads(line)
                except (ValueError, UnicodeDecodeError):
                    continue
                if isinstance(reply, dict):
                    reply["_rx"] = rx
                    ts = reply.get("timestamp")
                    if (
                        isinstance(ts, int)
                        and reply.get("type") != "overloaded"
                    ):
                        self._inflight_lines.pop(ts, None)
                    with self._new_reply:
                        self.replies.append(reply)
                        self._new_reply.notify_all()
        except (OSError, ValueError):
            pass  # socket closed
        # EOF/error on the CURRENT socket = the gateway died under us:
        # fail over proactively so queued replies keep flowing even
        # before the next send notices.
        if not self._closed:
            with self._send_lock:
                try:
                    self._failover_locked(sock)
                except OSError:
                    pass  # no gateway up right now; sends will retry

    def close(self) -> None:
        self._closed = True
        try:
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.sock.close()

    def _send_line(self, payload: bytes) -> None:
        with self._send_lock:  # not _lock: sendall must never block the
            for _ in range(1 + len(self._addrs)):  # reply reader's notify
                sock = self.sock
                try:
                    sock.sendall(payload)
                    return
                except OSError:
                    self._failover_locked(sock)  # raises when none answer

    def request(self, operation, to_replica=0, timestamp=None):
        """One raw-JSON request through the gateway (the gateway picks
        the replica; ``to_replica`` is accepted for drop-in compat and
        ignored)."""
        del to_replica
        if timestamp is None:
            self._timestamp += 1
            timestamp = self._timestamp
        req = ClientRequest(
            operation=operation, timestamp=timestamp, client=self.address
        )
        self._stamp_send(timestamp)
        line = req.canonical() + b"\n"
        self._inflight_lines[timestamp] = line
        self._send_line(line)
        return req

    def request_many(self, operations, to_replica=0, window=32, timeout=30.0):
        """Pipelined submission over the single gateway connection —
        mirrors PbftClient.request_many, with retransmission resending
        the SAME line (the gateway broadcasts a retransmitted (token, ts)
        to all replicas, the paper's liveness rule by proxy)."""
        del to_replica
        results: Dict[int, str] = {}
        timestamps: List[int] = []
        inflight: List[tuple] = []  # (timestamp, operation)
        next_op = 0
        while len(results) < len(operations):
            while next_op < len(operations) and len(inflight) < window:
                self._timestamp += 1
                ts = self._timestamp
                req = ClientRequest(
                    operation=operations[next_op],
                    timestamp=ts,
                    client=self.address,
                )
                self._stamp_send(ts)
                line = req.canonical() + b"\n"
                self._inflight_lines[ts] = line
                self._send_line(line)
                timestamps.append(ts)
                inflight.append((ts, operations[next_op]))
                next_op += 1
            ts, op = inflight.pop(0)
            try:
                results[ts] = self.wait_result(ts, timeout=timeout)
                self._drop_replies_upto(ts)
            except TimeoutError:
                retry = ClientRequest(
                    operation=op, timestamp=ts, client=self.address
                )
                line = retry.canonical() + b"\n"
                self._inflight_lines[ts] = line
                self._send_line(line)
                results[ts] = self.wait_result(ts, timeout=timeout)
                self._drop_replies_upto(ts)
            self._inflight_lines.pop(ts, None)
        return [results[ts] for ts in timestamps]


# -- daemon entry -------------------------------------------------------------


async def _amain(args, config_text: str, flight=None) -> None:
    config = ClusterConfig.from_json(config_text)
    gw = ClientGateway(
        config,
        host=args.host,
        port=args.port,
        metrics_port=args.metrics_port,
        max_inflight=args.max_inflight,
        max_queue_depth=args.max_queue_depth,
        flight=flight,
    )
    await gw.start()

    def last_words(signum, frame):
        """Told to stop, the gateway leaves its counters as one last
        line (nothing scrapes a benchmark run's gateway; its log is
        kept), then does what SIGTERM did before: the flight recorder's
        dump-and-exit where there is one, else dies at once."""
        try:
            os.write(1, json.dumps(gw.metrics()).encode() + b"\n")
            if callable(previous):
                previous(signum, frame)
        finally:
            os._exit(128 + signum)

    previous = signal.signal(signal.SIGTERM, last_words)
    print(f"gateway listening on {gw.listen_port}", flush=True)
    if gw.metrics_listen_port:
        # pbft_top / endurance_soak parse this to find /status (ISSUE 16).
        print(f"gateway metrics on {gw.metrics_listen_port}", flush=True)
    while True:
        await asyncio.sleep(args.metrics_every or 3600)
        if args.metrics_every:
            print(json.dumps(gw.metrics()), flush=True)


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--config", required=True)
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--metrics-every", type=int, default=0)
    parser.add_argument(
        "--metrics-port",
        type=int,
        default=None,
        help="serve Prometheus text format on this port (0 = ephemeral)",
    )
    parser.add_argument(
        "--max-inflight",
        type=int,
        default=0,
        help="admission control (ISSUE 12): per-client-token in-flight "
        "request cap — a fresh request past it is answered with an "
        "explicit overloaded line instead of forwarded (0 = off)",
    )
    parser.add_argument(
        "--max-queue-depth",
        type=int,
        default=0,
        help="admission control: global in-flight watermark across every "
        "token this gateway forwards for (0 = off)",
    )
    parser.add_argument(
        "--flight-file",
        default=None,
        help="black-box flight recorder dump target (failover/overload "
        "events), written on SIGTERM/SIGINT — decode with "
        "scripts/flight_dump.py",
    )
    args = parser.parse_args()
    flight = None
    if args.flight_file:
        from ..utils.flight import FlightRecorder, install_signal_dump

        flight = FlightRecorder(capacity=8192)
        install_signal_dump(flight, args.flight_file)
    with open(args.config) as fh:
        config_text = fh.read()
    try:
        asyncio.run(_amain(args, config_text, flight=flight))
    except BaseException:
        if flight is not None:
            flight.dump(args.flight_file)
        raise


if __name__ == "__main__":
    main()

"""The verify service's wire protocol and its dispatcher: the FFI boundary
between the replica runtimes and whoever verifies (SURVEY.md §5 "Distributed
communication backend": consensus-critical small messages stay on the host
network; only signature *batches* cross into the verifier's process).

Protocol (mirrors core/verifier.h RemoteVerifier):
    request:  u32be count N, then N * 128 bytes (pub 32 | msg 32 | sig 64)
    response: N bytes, each 0/1
    N = 0 / 0xFFFFFFFF: the two status probes (below)

Cross-connection coalescing: when several colocated daemons (one per
replica on a TPU host) submit batches concurrently, the dispatcher merges
everything queued into ONE backend call — one launch for the whole host's
quorum traffic instead of one per daemon. A window is "whatever queued
while the previous launch ran", held open for company only as long as
``hold_s`` grants (a rule the owner of the backend sets from what a launch
costs it; a bare service has none and cuts at once).

What a pending request IS: the ``(n, 128)`` uint8 block of rows it came off
the wire as (the handler reads the socket into a buffer of its own and never
cuts it up), and what it gets back is ONE ``bytes`` of its 0/1 verdicts. A
window is a :class:`Window`: its requests' blocks in order, with ``len()``
its items, which yields ``(pub, msg, sig)`` triples to whoever iterates,
indexes or slices it (the host verifiers, a test's callable) and hands its
rows as they are to whoever stages them (the engine). No Python object is
made for an item on the way in or out. The protocol is unchanged. Every
launch line says ``block_items``: the items of its window whose rows reached
an executable as they came off the wire (the engine says so in the span; 0
where a backend took triples), summed in the status beside ``listed_items``.

This module knows no shape, no device and no kernel: ``backend`` is a
callable on a sequence of items. The accelerator, its window shapes, their
costs and the daemon (``verifyd``) that joins them to this dispatcher
live in ``verify_service.py``, and only that daemon ever answers ``ready``.
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import struct
import sys
import threading
import time
import traceback
from collections.abc import Sequence
from itertools import chain
from typing import Callable, List, Optional, Tuple

import numpy as np

from ..utils.trace import Tracer, open_span

Item = Tuple[bytes, bytes, bytes]
ROW = 128  # bytes an item on the wire: pub 32 | msg 32 | sig 64

# -- readiness handshake wire format (ISSUE 7) -------------------------------
#
# Request header (u32be item count) values that are NOT batches:
#   STATUS_PROBE (0)               -> 8-byte binary status reply
#   STATUS_JSON_PROBE (0xFFFFFFFF) -> u32be length + JSON status reply
# Real batches are capped far below (MAX_WINDOW / the C++ async write
# budget), so neither value can collide with traffic; pre-handshake
# clients never sent count 0 (an empty batch was short-circuited before
# the socket).

STATUS_PROBE = 0
STATUS_JSON_PROBE = 0xFFFFFFFF
STATUS_MAGIC = b"VS"
STATUS_VERSION = 1
STATUS_LEN = 8

STATE_WARMING = 0
STATE_READY = 1
STATE_CPU_ONLY = 2
STATE_NAMES = {
    STATE_WARMING: "warming",
    STATE_READY: "ready",
    STATE_CPU_ONLY: "cpu-only",
}


# Connections the kernel holds between SYN and accept(): a whole cluster
# dials at its first batch (16 replicas at f=5, 31 at f=10), socketserver's
# default of 5 overflows, and a dropped SYN is retried only after a second,
# longer than a replica's connect deadline (PBFT_VERIFY_CONNECT_MS, 250).
LISTEN_QUEUE = 128

# A launch in flight this long is a STALL, and the watcher writes down what
# the process is doing (``launch_stalled``): every launch above 1 s in the
# 176 launch logs kept over PRs 28-33 was one (1.96 to 11.46 s, four runs),
# and the longest healthy launch in the benchmark's ledger waited 132 ms for
# its device (``device_wait_ms_max``). The watcher wakes twice a second (and
# at the mark of a launch it has seen in flight).
STALL_S = 1.0
STALL_POLL_S = 0.5
# The innermost frames kept of each Python thread's stack in that record.
STALL_FRAMES = 12

# Launch slots the daemon gives its dispatcher (``inflight``): with two,
# window N+1 is staged and dispatched from a second launch thread while
# window N computes, which hides the host's share of a launch behind the
# device's. Every cell of the benchmark runs at 2 and none has measured
# another value; a bare service defaults to 1 (one launch at a time: what
# tests that count windows need).
DAEMON_INFLIGHT = 2


def _python_stacks() -> dict:
    """{thread id: {"name", "frames": innermost-last "file:line function"}}
    of every Python thread, from ``sys._current_frames``."""
    names = {t.ident: t.name for t in threading.enumerate()}
    return {
        str(ident): {
            "name": names.get(ident, "?"),
            "frames": [
                f"{os.path.basename(fs.filename)}:{fs.lineno} {fs.name}"
                for fs in traceback.extract_stack(frame)[-STALL_FRAMES:]
            ],
        }
        for ident, frame in sys._current_frames().items()
    }


def _os_threads() -> list:
    """[tid, name, state, wchan] of every thread of this process, the
    runtime's own among them (``pjrt-tpu-tasks``, ``EventFDAsyncWor``,
    ``py_xla_execute``), from ``/proc/self/task``; [] where there is none."""
    out = []
    try:
        tids = sorted(os.listdir("/proc/self/task"), key=int)
    except OSError:
        return out
    for tid in tids:
        try:
            with open(f"/proc/self/task/{tid}/stat") as fh:
                stat = fh.read()
            # "tid (comm) S ...": comm may hold spaces and parentheses.
            name = stat[stat.index("(") + 1 : stat.rindex(")")]
            state = stat[stat.rindex(")") + 2 :].split(" ", 1)[0]
            with open(f"/proc/self/task/{tid}/wchan") as fh:
                wchan = fh.read().strip()
        except (OSError, ValueError):
            continue  # the thread ended between the listing and the read
        out.append([int(tid), name, state, wchan])
    return out


def pack_status(state: int, devices: int, warmed: int) -> bytes:
    """8 bytes: 'V' 'S' version state u16be devices u16be warmed-shapes."""
    return STATUS_MAGIC + struct.pack(
        ">BBHH", STATUS_VERSION, state, min(devices, 0xFFFF), min(warmed, 0xFFFF)
    )


def unpack_status(blob: bytes) -> Optional[Tuple[int, int, int]]:
    """(state, devices, warmed_shapes), or None if not a status record."""
    if len(blob) != STATUS_LEN or blob[:2] != STATUS_MAGIC:
        return None
    version, state, devices, warmed = struct.unpack(">BBHH", blob[2:])
    if version != STATUS_VERSION or state not in STATE_NAMES:
        return None
    return state, devices, warmed


def _recv_buffer(sock: socket.socket, n: int) -> bytearray:
    # Preallocated buffer + recv_into: the n*128-byte blob read is on the
    # coalesced-window hot path, and the old `bytes += chunk` accumulation
    # re-copied the whole prefix per chunk (quadratic across a large
    # window split into MTU-sized reads). The buffer is the caller's own.
    buf = bytearray(n)
    view = memoryview(buf)
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            raise ConnectionError("peer closed mid-message")
        got += r
    return buf


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    return bytes(_recv_buffer(sock, n))


def as_rows(items: Sequence) -> np.ndarray:
    """A list of ``(pub, msg, sig)`` triples -> their ``(n, 128)`` uint8
    rows, joined once: what an in-process caller's list becomes at the door."""
    rows = np.frombuffer(b"".join(chain.from_iterable(items)), np.uint8)
    if rows.size != len(items) * ROW:
        raise ValueError(
            f"{len(items)} items of {rows.size} bytes: not 128-byte triples"
        )
    return rows.reshape(len(items), ROW)


class Window(Sequence):
    """The items of ONE backend call, held as the ``(n, 128)`` uint8 blocks
    their requests came off the wire as, in order. A sequence of
    ``(pub, msg, sig)`` triples to a backend that iterates, indexes or
    slices it (each triple made on demand); :meth:`rows` to one that stages
    rows (the engine), which then makes nothing per item."""

    __slots__ = ("blocks", "_n")

    def __init__(self, blocks: List[np.ndarray]):
        self.blocks = blocks
        self._n = sum(map(len, blocks))

    def __len__(self) -> int:
        return self._n

    def __iter__(self):
        for block in self.blocks:
            blob = block.tobytes()
            for at in range(0, len(blob), ROW):
                yield blob[at : at + 32], blob[at + 32 : at + 64], blob[at + 64 : at + ROW]

    def __getitem__(self, i):
        at = range(self._n)[i]  # range's own rules for a slice or a negative index
        if isinstance(i, slice):
            return [self[j] for j in at]
        for block in self.blocks:
            if at < len(block):
                row = block[at].tobytes()
                return row[:32], row[32:64], row[64:]
            at -= len(block)

    def rows(self, start: int, stop: int) -> List[np.ndarray]:
        """The rows of items ``start`` to ``stop``: views of the blocks, in
        order, a request that straddles either edge cut there."""
        out = []
        for block in self.blocks:
            if stop <= 0:
                break
            if start < len(block):
                out.append(block[max(start, 0) : stop])
            start -= len(block)
            stop -= len(block)
        return out


def cpu_backend(items: Sequence) -> List[bool]:
    """The host oracle, one item at a time (the simulator's control arm)."""
    from ..consensus.simulation import cpu_verifier

    return cpu_verifier(items)


def native_backend(items: Sequence) -> List[bool]:
    """The C++ batch verifier (core/ed25519.cc via ctypes): one fast host
    verifier process serving every colocated daemon — the chip-less
    deployment, and the realistic control arm for measuring coalesced
    window occupancy on a box without a chip."""
    from .. import native

    return native.verify_batch(list(items))  # the triples made once, not a pass


class _Pending:
    __slots__ = ("rows", "n", "conn", "arrived", "event", "verdicts", "error")

    def __init__(self, rows: np.ndarray, conn=None):
        self.rows = rows  # the request's (n, 128) uint8 block
        self.n = len(rows)
        self.conn = conn  # the connection it came over (None: not known)
        self.arrived = time.monotonic()
        self.event = threading.Event()
        self.verdicts: Optional[bytes] = None  # a 0/1 byte an item
        self.error: Optional[Exception] = None


class VerifierService:
    """Threaded TCP (or unix-domain) batch-verification server."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        unix_path: Optional[str] = None,
        backend: Callable[[Sequence], Sequence] | str = "native",
        trace_path: Optional[str] = None,
        inflight: int = 1,
        metrics_port: Optional[int] = None,
        status_provider: Optional[Callable[[], Tuple[int, int, int]]] = None,
        status_json_provider: Optional[Callable[[], dict]] = None,
    ):
        backend_name = backend if isinstance(backend, str) else None
        if isinstance(backend, str):
            backend = {"cpu": cpu_backend, "native": native_backend}[backend]
        self.backend = backend
        # Readiness handshake (verify_service.py): a bare VerifierService
        # owns no accelerator and has no warm-up, so whatever its backend
        # is, it says "cpu-only" and never "ready". The daemon overrides
        # both providers with its live state machine.
        self._status_provider = status_provider or (lambda: (STATE_CPU_ONLY, 0, 0))
        self._status_json_provider = status_json_provider or (
            lambda: {
                "state": STATE_NAMES[self._status_provider()[0]],
                "devices": self._status_provider()[1],
                "backend": backend_name or "custom",
                "requests": self.requests,
                "launches": self.batches,
                "items": self.items,
            }
        )
        # The window policy, hold for company: ``hold_s(items)`` says how
        # long a window of that many items may stay open for more, counted
        # from its oldest request's arrival; 0 cuts at once. None of a bare
        # service's backends has an opinion (every window is cut at once);
        # the daemon sets its engine's (one launch time of the shape the
        # window would run at, while that shape has room:
        # ``ShardedVerifyEngine.hold_s``). The hold ends early once nobody
        # in step is still out: no launch is in flight and every connection
        # the last one answered has its next request in the window (replicas
        # keep one batch in flight each and come back together; a caller
        # alone never waits for anybody).
        self.hold_s: Optional[Callable[[int], float]] = None
        self._flying = 0  # windows cut and not yet answered
        self._answered: set = set()  # connections the last launch answered
        # Overlapped launches: with inflight > 1 the dispatcher ships
        # window N+1 while N is still executing, hiding host-side launch
        # overhead behind device compute (XLA serializes execution per
        # device; the dispatch/transfer cost is what overlaps). Default 1
        # preserves the "window = what queued during the previous launch"
        # dynamic; raising it trades window size for launch concurrency.
        self._inflight = max(1, inflight)
        self._inflight_sem = threading.Semaphore(self._inflight)
        self._launch_threads: List[threading.Thread] = []
        # Per-dispatch JSONL trace ({"ev":"verify_batch","size":merged,..}):
        # the honest occupancy measurement for the launch-cost model — the
        # merged window IS the launch, where per-replica traces only see
        # each daemon's share.
        self._tracer = Tracer(open(trace_path, "a") if trace_path else None)
        # Metrics (utils/metrics.py; the verify subset of the cross-runtime
        # contract in utils/trace_schema.py). Disabled unless a scrape
        # surface was asked for — the dispatcher is the single writer.
        from ..utils import MetricsRegistry, start_metrics_server

        self.metrics_registry = MetricsRegistry(
            labels={"replica": "service"}, enabled=metrics_port is not None
        )
        if self.metrics_registry.enabled:
            self.metrics_registry.preregister("service.py")
        self._metrics_server = None
        self.metrics_listen_port = 0
        if metrics_port is not None:
            self._metrics_server = start_metrics_server(self.metrics_registry, metrics_port)
            self.metrics_listen_port = self._metrics_server.server_address[1]
        self.batches = 0  # backend calls (XLA launches)
        self.requests = 0  # wire requests (>= batches when coalescing)
        self.items = 0
        # What an operator without --trace needs to see a stall: running
        # totals of each stage of a launch — the dispatcher's two waits and
        # every duration (``*_s``) the backend writes into its span, the
        # sharded engine's five steps — and the slowest launch so far with
        # the step that held it (written under _cond by the launch threads).
        # promoted_launches: launches the engine ran on a larger shape than
        # the smallest that fits (its span's ``promoted``); split_launches:
        # windows it ran as several executables (its span's ``split``);
        # fused_launches: windows with slots on executables that run the
        # multiply chains out of VMEM (its span's ``fused`` above 0).
        # block_items / listed_items: items whose rows reached an executable
        # as the blocks they came off the wire as (the engine's span says
        # ``block_items``), and items a backend took as a list of triples
        # instead (the host verifiers, the daemon's fallback before ready).
        # held_out_launches / in_step_launches: windows whose hold ran out,
        # and windows cut early because nobody in step was still out;
        # windows_cut_full: windows cut at MAX_WINDOW with requests left
        # queued behind them, and overflow_items_max the most items any cut
        # left queued (the backlog the service carried at its deepest);
        # launches_by_rung: launches by the padded slots the engine ran;
        # launches_by_rows_per_chip: by the rows a chip of the window's
        # thinnest chunk (its span's ``rows_per_chip``).
        self.stage_seconds = {"queue_s": 0.0, "slot_s": 0.0}
        self.promoted_launches = 0
        self.split_launches = 0
        self.fused_launches = 0
        self.block_items = 0
        self.listed_items = 0
        self.held_out_launches = 0
        self.in_step_launches = 0
        self.windows_cut_full = 0
        self.overflow_items_max = 0
        self.launches_by_rung: dict = {}
        self.launches_by_rows_per_chip: dict = {}
        self._slowest: Optional[dict] = None
        # Backend calls in flight ({t0, size, span, thread, stalled}), read by
        # the stall watcher; stalls / longest_stall_s go into the status JSON.
        # ``stall_probe`` is the owner of the backend's to set: what it can
        # say about its device in a stall (the daemon: every local device's
        # ``memory_stats()``); a bare service knows no device.
        self._flights: List[dict] = []
        self._flight_lock = threading.Lock()
        self.stall_probe: Optional[Callable[[], object]] = None
        self.stalls = 0
        self.longest_stall_s = 0.0
        self._cond = threading.Condition()
        self._pending: List[_Pending] = []
        self._queued = 0  # items in _pending (under _cond)
        self._running = True
        service = self

        class Handler(socketserver.BaseRequestHandler):
            def setup(self):
                # TCP_NODELAY on accepted verify streams (ISSUE 10 socket
                # discipline): the 1-byte-per-item verdict reply must not
                # sit in a Nagle stall. Unix sockets have no Nagle.
                if self.request.family == socket.AF_INET:
                    self.request.setsockopt(
                        socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                    )

            def handle(self):  # one connection, many batches
                sock = self.request
                try:
                    while True:
                        header = _recv_exact(sock, 4)
                        n = int.from_bytes(header, "big")
                        if n == STATUS_PROBE:
                            # Readiness handshake: replicas/bench decide
                            # whether to route here before shipping work.
                            sock.sendall(pack_status(*service._status_provider()))
                            continue
                        if n == STATUS_JSON_PROBE:
                            blob = json.dumps(
                                service._status_json_provider()
                            ).encode()
                            sock.sendall(len(blob).to_bytes(4, "big") + blob)
                            continue
                        rows = np.frombuffer(_recv_buffer(sock, n * ROW), np.uint8)
                        sock.sendall(service._submit(rows.reshape(n, ROW), conn=self))
                except (ConnectionError, OSError):
                    return
                finally:
                    service._gone(self)

        if unix_path is not None:

            class UnixServer(socketserver.ThreadingUnixStreamServer):
                daemon_threads = True
                request_queue_size = LISTEN_QUEUE

            self.server = UnixServer(unix_path, Handler)
            self.address = unix_path
        else:

            class TcpServer(socketserver.ThreadingTCPServer):
                daemon_threads = True
                allow_reuse_address = True
                request_queue_size = LISTEN_QUEUE

            self.server = TcpServer((host, port), Handler)
            self.address = "%s:%d" % self.server.server_address
        self._thread: Optional[threading.Thread] = None
        self._dispatcher = threading.Thread(target=self._dispatch_loop, daemon=True)
        self._dispatcher.start()
        threading.Thread(target=self._stall_watch, daemon=True).start()

    # Largest merged window, in items; overflow stays queued for the next
    # window. A bound on one launch's latency and staging memory, set to the
    # top of the pad ladder (crypto/batch.py _PAD_LADDER; the lint in
    # analysis/constants.py holds the two equal) so that a full window is
    # one launch of the largest shape. A constant: no option sets it.
    MAX_WINDOW = 4096

    def _gone(self, conn) -> None:
        """A connection closed: no held window waits for it any more."""
        with self._cond:
            self._answered.discard(conn)
            self._cond.notify_all()

    def _in_step_are_back(self) -> bool:
        """No launch in flight, and every connection the last one answered
        has a request pending again (under ``_cond``)."""
        return self._flying == 0 and self._answered <= {
            p.conn for p in self._pending
        }

    def _submit(self, items, conn=None):
        """Handler-thread entry: verify `items`, possibly merged with other
        connections' concurrent submissions into one backend call. A
        handler's ``(n, 128)`` block is answered with its verdict bytes; an
        in-process caller's list of triples is packed here, once, and
        answered with a list of bools."""
        listed = not isinstance(items, np.ndarray)
        p = _Pending(as_rows(items) if listed else items, conn)
        with self._cond:
            self.requests += 1
            if not self._running:  # dispatcher gone: fail this connection
                raise ConnectionError("verifier service stopping")
            self._pending.append(p)
            self._queued += p.n
            self._cond.notify()
        # No fixed deadline (the backend's time is its own), but a dead
        # dispatcher must not strand the connection.
        while not p.event.wait(timeout=1.0):
            if not self._dispatcher.is_alive():
                raise ConnectionError("verifier dispatcher died")
        if p.error is not None:
            raise ConnectionError(f"verification failed: {p.error!r}")
        assert p.verdicts is not None
        return list(map(bool, p.verdicts)) if listed else p.verdicts

    def _dispatch_loop(self) -> None:
        while True:
            with self._cond:
                while self._running and not self._pending:
                    self._cond.wait(0.5)
                if not self._running and not self._pending:
                    return
                # The hold the window is granted at its cut, and which of
                # its exits cut it (neither: it filled its shape, or no
                # hold applies).
                hold, held_out, in_step = 0.0, 0, 0
                if self.hold_s is not None:
                    while self._running:
                        # Asked about what this window can carry: a backlog
                        # beyond MAX_WINDOW is cut there whatever arrives, so
                        # the room on the backlog's LAST shape is not this
                        # window's to wait for (the engine says 0 for a
                        # window that fills the largest shape).
                        hold = self.hold_s(min(self._queued, self.MAX_WINDOW))
                        remaining = (
                            self._pending[0].arrived + hold - time.monotonic()
                        )
                        if hold <= 0:  # the window fills its shape
                            break
                        if remaining <= 0:
                            held_out = 1
                            break
                        if self._in_step_are_back():
                            in_step = 1
                            break
                        self._cond.wait(remaining)
                # Take whole requests up to MAX_WINDOW items (a single
                # oversized request still goes through, alone).
                window: List[_Pending] = []
                size = 0
                while self._pending:
                    nxt = self._pending[0].n
                    if window and size + nxt > self.MAX_WINDOW:
                        break
                    size += nxt
                    window.append(self._pending.pop(0))
                self._queued -= size
                self._flying += 1
                cut_at = time.monotonic()
                left = self._queued  # queued past MAX_WINDOW
                if self.metrics_registry.enabled:
                    self.metrics_registry.gauge("pbft_verify_queue_depth").set(left)
            # The window is cut BEFORE a launch slot is free and cannot grow
            # while it waits for one: slot_s is that wait, pending_at_launch
            # what a cut made only now would have merged into it.
            self._inflight_sem.acquire()
            got_slot = time.monotonic()
            with self._cond:
                arrived_since = self._queued
            waits = {
                "queue_s": round(cut_at - min(p.arrived for p in window), 6),
                "slot_s": round(got_slot - cut_at, 6),
                "pending_at_cut": left,
                "pending_at_launch": arrived_since,
                # The cut loop stops short of an empty queue only at
                # MAX_WINDOW: whatever is left stayed behind a full window.
                "cut_full": int(left > 0),
                "hold_s": round(hold, 6),
                "held_out": held_out,
                "in_step": in_step,
            }
            if self._inflight == 1:
                self._dispatch_guarded(window, waits)
            else:
                # Overlapped mode: the launch runs on its own thread while
                # the dispatcher loops back to accumulate the next window.
                t = threading.Thread(
                    target=self._dispatch_guarded, args=(window, waits), daemon=True
                )
                with self._cond:  # stop() reads this list concurrently
                    self._launch_threads = [
                        x for x in self._launch_threads if x.is_alive()
                    ]
                    self._launch_threads.append(t)
                t.start()

    def _dispatch_guarded(self, window: List[_Pending], waits: dict) -> None:
        try:
            self._dispatch_window(window, waits)
        except Exception as e:  # noqa: BLE001 - never strand a handler
            # Any dispatcher bug outside the backend guard must still
            # wake every waiting connection with an error rather than
            # leaving clients hung mid-read.
            for p in window:
                if not p.event.is_set():
                    p.error = e
                    p.event.set()
        finally:
            with self._cond:
                self._flying -= 1
                self._answered = {p.conn for p in window} - {None}
                self._cond.notify_all()  # a held window may go now
            self._inflight_sem.release()

    @staticmethod
    def _checked(backend, items: Window) -> np.ndarray:
        """Run the backend and validate the verdict count — a wrong-length
        result would otherwise mis-slice silently across connections. The
        verdicts leave as ONE uint8 array of 0/1 (the engine's bool array
        seen as bytes; a host verifier's list converted here, once)."""
        verdicts = backend(items)
        if verdicts is None or len(verdicts) != len(items):
            got = "None" if verdicts is None else str(len(verdicts))
            raise ValueError(
                f"backend returned {got} verdicts for {len(items)} items"
            )
        return np.asarray(verdicts, dtype=bool).view(np.uint8)

    def _spanned(self, items: Window) -> Tuple[np.ndarray, dict]:
        """One backend call -> (verdicts, what the backend wrote into the
        span opened round it: the engine's steps, or nothing; every span
        says ``block_items``, 0 unless the backend says otherwise). While
        it runs the call is on the stall watcher's list."""
        with open_span() as span:
            span["block_items"] = 0
            flight = {
                "t0": time.monotonic(), "size": len(items), "span": span,
                "thread": threading.get_ident(), "stalled": False,
            }
            with self._flight_lock:
                self._flights.append(flight)
            try:
                return self._checked(self.backend, items), span
            finally:
                with self._flight_lock:
                    self._flights.remove(flight)
                if flight["stalled"]:
                    self._stall_ended(flight)

    # -- what the process does in a stall (ISSUE 38) --------------------------

    def _stall_watch(self) -> None:
        """Twice a second: a launch in flight longer than STALL_S gets ONE
        ``launch_stalled`` record (the tracer's line, and stderr)."""
        nap = STALL_POLL_S
        while self._running:
            time.sleep(nap)
            now = time.monotonic()
            with self._flight_lock:
                late = [
                    f for f in self._flights
                    if not f["stalled"] and now - f["t0"] > STALL_S
                ]
                for f in late:
                    f["stalled"] = True
                # Twice a second, and sooner where a launch seen in flight
                # is about to pass the mark.
                due = [f["t0"] + STALL_S - now for f in self._flights if not f["stalled"]]
            nap = min([STALL_POLL_S] + [max(d, 0.0) + 0.01 for d in due])
            for f in late:
                with self._cond:
                    self.stalls += 1
                record = dict(
                    replica="service",
                    size=f["size"],
                    rung=f["span"].get("rung"),  # None until the engine says
                    age_s=round(now - f["t0"], 3),
                    thread=f["thread"],
                    stacks=_python_stacks(),
                    tasks=_os_threads(),
                    memory=self._probe_device(),
                )
                self._tracer.event("launch_stalled", **record)
                print("[verify-service] launch_stalled "
                      + json.dumps(record, separators=(",", ":"), default=str),
                      file=sys.stderr, flush=True)

    def _probe_device(self):
        """``stall_probe()`` with half a second to answer: the runtime that
        hangs a launch may hang this call too, and the record must get out."""
        if self.stall_probe is None:
            return None
        box: list = []

        def ask() -> None:
            try:
                box.append(self.stall_probe())
            except Exception as e:  # noqa: BLE001 - goes into the record
                box.append(f"failed: {e!r}")

        t = threading.Thread(target=ask, daemon=True)
        t.start()
        t.join(STALL_POLL_S)
        return box[0] if box else f"no answer in {STALL_POLL_S} s"

    def _stall_ended(self, flight: dict) -> None:
        secs = round(time.monotonic() - flight["t0"], 3)
        with self._cond:
            self.longest_stall_s = max(self.longest_stall_s, secs)
        self._tracer.event(
            "launch_stall_ended", replica="service", size=flight["size"], secs=secs
        )
        print(f"[verify-service] launch_stall_ended size={flight['size']} secs={secs}",
              file=sys.stderr, flush=True)

    def _account(self, secs: float, size: int, waits: dict, span: dict) -> None:
        """Fold one finished launch into the status totals (under _cond)."""
        steps = {k: v for k, v in span.items() if k.endswith("_s")}
        for name in ("queue_s", "slot_s"):
            self.stage_seconds[name] += waits[name]
        for name, took in steps.items():
            self.stage_seconds[name] = self.stage_seconds.get(name, 0.0) + took
        self.promoted_launches += bool(span.get("promoted"))
        self.split_launches += bool(span.get("split"))
        self.fused_launches += bool(span.get("fused"))
        self.block_items += span["block_items"]
        self.listed_items += size - span["block_items"]
        self.held_out_launches += waits["held_out"]
        self.in_step_launches += waits["in_step"]
        self.windows_cut_full += waits["cut_full"]
        self.overflow_items_max = max(self.overflow_items_max, waits["pending_at_cut"])
        for field, counts in (
            ("rung", self.launches_by_rung),
            ("rows_per_chip", self.launches_by_rows_per_chip),
        ):
            if field in span:
                key = str(span[field])  # JSON has no integer keys
                counts[key] = counts.get(key, 0) + 1
        if self._slowest is None or secs > self._slowest["secs"]:
            self._slowest = {
                "secs": round(secs, 6),
                "size": size,
                "rung": span.get("rung"),
                "stage": max(steps, key=steps.get) if steps else None,
                "at": time.monotonic(),  # launch_status() turns it into ago_s
            }

    def launch_status(self) -> dict:
        """The stage totals, the counts of launches (promoted, split, fused, by exit
        of the hold, cut at MAX_WINDOW, by shape run, by rows a chip), the
        items that reached an executable as block rows and those a backend
        took as a list, the deepest backlog a cut left queued, the slowest launch, and
        the launches that stalled (above STALL_S in flight) with the longest
        of them that has ended, for the status JSON."""
        with self._cond:
            slowest = dict(self._slowest) if self._slowest else None
            totals = {k: round(v, 6) for k, v in self.stage_seconds.items()}
            counts = {
                "promoted_launches": self.promoted_launches,
                "split_launches": self.split_launches,
                "fused_launches": self.fused_launches,
                "block_items": self.block_items,
                "listed_items": self.listed_items,
                "held_out_launches": self.held_out_launches,
                "in_step_launches": self.in_step_launches,
                "windows_cut_full": self.windows_cut_full,
                "overflow_items_max": self.overflow_items_max,
                "launches_by_rung": dict(self.launches_by_rung),
                "launches_by_rows_per_chip": dict(self.launches_by_rows_per_chip),
                "stalls": self.stalls,
                "longest_stall_s": self.longest_stall_s,
            }
        if slowest:
            slowest["ago_s"] = round(time.monotonic() - slowest.pop("at"), 3)
        return {"stage_seconds": totals, **counts, "slowest_launch": slowest}

    def _dispatch_window(self, window: List[_Pending], waits: dict) -> None:
        merged = Window([p.rows for p in window])
        size = len(merged)
        rejected = -1  # of a window that failed
        t0 = time.monotonic()
        span: dict = {}
        try:
            verdicts, span = self._spanned(merged)
            rejected = size - int(np.count_nonzero(verdicts))
        except Exception:
            # One launch failing must not reject every client's honest
            # signatures ("never a false reject"): retry each request
            # alone so only the actually-poisoned one errors out.
            verdicts = None
        secs = time.monotonic() - t0
        if self._tracer.enabled:
            # A failed merged launch is NOT a verify_batch event: the
            # launch-cost model reads verify_batch sizes as items-per-
            # launch, and counting the failed merge (plus not counting
            # its per-request retries below) would overstate occupancy.
            self._tracer.event(
                "verify_batch" if verdicts is not None else "verify_window_failed",
                replica="service",
                size=size,
                requests=len(window),
                rejected=rejected,
                secs=round(secs, 6),
                **({**waits, **span} if verdicts is not None else {}),
            )
        with self._cond:
            # Under the lock: with inflight > 1 several launch threads
            # finish concurrently (the replica runtimes' single-writer
            # discipline doesn't hold here).
            self.batches += 1
            self.items += size
            if verdicts is not None:
                self._account(secs, size, waits, span)
            if self.metrics_registry.enabled:
                self.metrics_registry.counter("pbft_verify_batches_total").inc()
                self.metrics_registry.counter("pbft_verify_items_total").inc(size)
                self.metrics_registry.histogram("pbft_verify_batch_size").observe(size)
                self.metrics_registry.histogram("pbft_verify_seconds").observe(secs)
                # Service launch surface (ISSUE 7): items per XLA launch
                # and how many connections each merged window carried —
                # the coalescing win the launch-cost model prices.
                self.metrics_registry.counter(
                    "pbft_verify_service_launches_total"
                ).inc()
                self.metrics_registry.histogram(
                    "pbft_verify_service_window_size"
                ).observe(size)
                self.metrics_registry.histogram(
                    "pbft_verify_service_coalesced_clients"
                ).observe(len(window))
                if verdicts is not None:
                    self.metrics_registry.counter("pbft_verify_rejected_total").inc(
                        rejected
                    )
        if verdicts is None:
            for p in window:
                t1 = time.monotonic()
                try:
                    alone, span = self._spanned(Window([p.rows]))
                    p.verdicts = alone.tobytes()
                except Exception as e:  # noqa: BLE001 - handed to submitter
                    p.error = e
                if self._tracer.enabled:
                    if p.verdicts is not None:
                        self._tracer.event(
                            "verify_batch",
                            replica="service",
                            size=p.n,
                            requests=1,
                            rejected=p.n - int(np.count_nonzero(alone)),
                            secs=round(time.monotonic() - t1, 6),
                            **span,
                        )
                    else:
                        # NOT a verify_batch event: trace_report sums the
                        # rejected field over verify_batch events, and an
                        # errored retry has no verdicts to count.
                        self._tracer.event(
                            "verify_batch_error",
                            replica="service",
                            size=p.n,
                            secs=round(time.monotonic() - t1, 6),
                        )
                p.event.set()
            return
        off = 0
        for p in window:
            p.verdicts = verdicts[off : off + p.n].tobytes()
            off += p.n
            p.event.set()

    def start(self) -> "VerifierService":
        self._thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def stop(self) -> None:
        # Flip _running BEFORE joining anything: handlers enqueueing after
        # this point get a ConnectionError instead of waiting on an event
        # nobody will set; the dispatcher drains what's already queued.
        with self._cond:
            self._running = False
            self._cond.notify_all()
        if self._metrics_server is not None:
            self._metrics_server.shutdown()
            self._metrics_server.server_close()
        self.server.shutdown()
        self.server.server_close()
        if self._thread:
            self._thread.join(timeout=5)
        self._dispatcher.join(timeout=5)
        with self._cond:
            launch_threads = list(self._launch_threads)
        for t in launch_threads:
            t.join(timeout=5)
        if self._tracer.sink is not None and not (
            self._dispatcher.is_alive() or any(t.is_alive() for t in launch_threads)
        ):
            # Only close once the dispatcher is provably done with it: a
            # join timeout (a launch still in flight) must leak the fd
            # rather than turn that window's
            # successful verifications into I/O errors mid-write.
            self._tracer.sink.close()
            self._tracer = type(self._tracer)()  # disabled from here on

"""Cross-connection coalescing in the VerifierService: concurrent batch
submissions from separate connections must merge into fewer backend calls
(one XLA launch per window on TPU) with per-request verdict slices intact.

Plus the persistent-service lifecycle (ISSUE 7): readiness handshake,
warming -> ready transitions, pbftd's native-pool fallback when the
service is killed mid-stream, the counted fallbacks on both ends, the chip deployment's refusals (``--backend jax``
never settles for a CPU), and the warm restart through the persistent
compile cache."""

import socket
import threading
import time

import pytest

from pbft_tpu.net import (
    ShardedVerifyEngine,
    VerifierService,
    VerifyServiceDaemon,
    probe_status,
    probe_status_json,
)
from pbft_tpu.net.service import (
    STATE_CPU_ONLY,
    STATE_READY,
    STATE_WARMING,
)


def _send_batch(addr: str, items):
    host, port = addr.rsplit(":", 1)
    payload = b"".join(p + m + s for p, m, s in items)
    with socket.create_connection((host, int(port)), timeout=10) as sock:
        sock.sendall(len(items).to_bytes(4, "big") + payload)
        out = b""
        while len(out) < len(items):
            chunk = sock.recv(len(items) - len(out))
            assert chunk
            out += chunk
    return [bool(b) for b in out]


def _item(tag: int, valid: bool):
    # The fake backend below deems an item valid iff sig[0] == pub[0];
    # tag makes every item distinguishable so slicing bugs can't hide.
    pub = bytes([tag]) * 32
    msg = bytes([tag ^ 0xFF]) * 32
    sig = (bytes([tag]) if valid else bytes([tag ^ 1])) + bytes(63)
    return pub, msg, sig


def test_concurrent_requests_coalesce_into_fewer_launches():
    calls = []
    gate = threading.Event()

    def slow_backend(items):
        calls.append(len(items))
        if len(calls) == 1:
            gate.wait(10)  # hold the first launch so others queue behind it
        return [p[0] == s[0] for p, m, s in items]

    svc = VerifierService(backend=slow_backend).start()
    try:
        results = {}

        def client(cid: int):
            items = [_item(cid, True), _item(cid, cid % 2 == 0)]
            results[cid] = _send_batch(svc.address, items)

        threads = [threading.Thread(target=client, args=(c,)) for c in range(1, 5)]
        threads[0].start()
        while not calls:  # first request is inside the backend now
            time.sleep(0.01)
        for t in threads[1:]:
            t.start()
        # Give the three remaining requests time to queue, then release.
        deadline = time.monotonic() + 5
        while svc.requests < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join(timeout=10)

        assert svc.requests == 4
        # 1 held launch + 1 merged launch for the 3 queued requests.
        assert svc.batches < 4, f"no coalescing happened: {calls}"
        assert sum(calls) == 8 and svc.items == 8
        for cid in range(1, 5):
            assert results[cid] == [True, cid % 2 == 0], (cid, results[cid])
    finally:
        gate.set()
        svc.stop()


@pytest.mark.parametrize("path", ["list", "block"])
def test_poison_batch_only_fails_its_own_connection(tmp_path, path):
    """A backend failure on a merged launch must not false-reject other
    clients' honest signatures: the window is retried per-request and only
    the poisoned connection errors out. The trace must stay honest too:
    the failed merge is verify_window_failed (NOT verify_batch, whose
    sizes the launch-cost model reads as items-per-launch) and the
    retries are traced as singleton launches. ``list``: a callable that
    iterates the window's triples; ``block``: the engine, staging the
    requests' rows (ISSUE 45), the window and every retry alike."""
    import json

    gate = threading.Event()
    first = threading.Event()
    engine = ShardedVerifyEngine(shapes=(8,), kernel=_fake_kernel)
    if path == "block":
        engine.warm()

    def backend(items):
        if not first.is_set():
            first.set()
            gate.wait(10)
            # fall through: the held first request itself verifies fine
        if path == "block":
            if any((rows[:, 0] == 66).any() for rows in items.blocks):
                raise RuntimeError("poison")
            return engine.verify(items)
        if any(p[0] == 66 for p, m, s in items):
            raise RuntimeError("poison")
        return [p[0] == s[0] for p, m, s in items]

    trace = tmp_path / "service.jsonl"
    svc = VerifierService(backend=backend, trace_path=str(trace)).start()
    try:
        results = {}

        def client(cid: int):
            try:
                results[cid] = _send_batch(svc.address, [_item(cid, True)])
            except (AssertionError, ConnectionError, OSError):
                results[cid] = "error"

        t1 = threading.Thread(target=client, args=(1,))
        t1.start()
        while not first.is_set():
            time.sleep(0.01)
        others = [threading.Thread(target=client, args=(c,)) for c in (65, 66, 67)]
        for t in others:
            t.start()
        deadline = time.monotonic() + 5
        while svc.requests < 4 and time.monotonic() < deadline:
            time.sleep(0.01)
        gate.set()
        t1.join(timeout=10)
        for t in others:
            t.join(timeout=10)
        assert results[1] == [True]
        assert results[65] == [True]
        assert results[66] == "error"  # the poisoned one, and only it
        assert results[67] == [True]
    finally:
        gate.set()
        svc.stop()
    events = [json.loads(line) for line in trace.read_text().splitlines()]
    vb = [e for e in events if e["ev"] == "verify_batch"]
    failed = [e for e in events if e["ev"] == "verify_window_failed"]
    errored = [e for e in events if e["ev"] == "verify_batch_error"]
    assert len(failed) == 1 and failed[0]["size"] == 3, failed
    # 1 clean launch (the held first request) + 2 clean singleton
    # retries; the poisoned retry is verify_batch_error (it produced no
    # verdicts, so it must not enter the items-per-launch or rejected
    # sums trace_report computes over verify_batch events).
    assert sum(e["size"] for e in vb) == 3, vb
    assert sum(e["rejected"] for e in vb) == 0, vb
    assert len(errored) == 1 and errored[0]["size"] == 1, errored
    assert all(e["requests"] == 1 for e in vb if e["size"] == 1), vb
    assert all(e["block_items"] == (e["size"] if path == "block" else 0) for e in vb), vb


def test_wrong_length_verdicts_fail_loudly():
    """A backend returning the wrong number of verdicts must error the
    affected connections, never mis-slice across a merged window or
    desync the wire protocol (each response is exactly N bytes)."""

    def backend(items):
        return [True] * (len(items) - 1)  # one verdict short

    svc = VerifierService(backend=backend).start()
    try:
        try:
            out = _send_batch(svc.address, [_item(1, True), _item(2, True)])
            raised = False
        except (ConnectionError, OSError, AssertionError):
            raised = True
        assert raised, f"short verdicts accepted: {out}"
    finally:
        svc.stop()


def test_window_respects_pad_ladder_cap():
    """Merged windows never exceed MAX_WINDOW items (the top of the XLA
    pad ladder) — oversized merges would compile new shapes at runtime."""
    calls = []
    gate = threading.Event()

    def backend(items):
        calls.append(len(items))
        if len(calls) == 1:
            gate.wait(10)
        return [p[0] == s[0] for p, m, s in items]

    svc = VerifierService(backend=backend).start()
    svc.MAX_WINDOW = 4  # instance override for the test
    try:
        threads = [
            threading.Thread(
                target=lambda c=c: _send_batch(
                    svc.address, [_item(c, True), _item(c, True)]
                )
            )
            for c in range(1, 8)
        ]
        threads[0].start()
        while not calls:
            time.sleep(0.01)
        for t in threads[1:]:
            t.start()
        deadline = time.monotonic() + 5
        while svc.requests < 7 and time.monotonic() < deadline:
            time.sleep(0.01)
        gate.set()
        for t in threads:
            t.join(timeout=10)
        assert all(size <= 4 for size in calls), calls
        assert sum(calls) == 14
    finally:
        gate.set()
        svc.stop()


class _Conn:
    """One connection kept open over many batches, as a replica keeps its."""

    def __init__(self, addr: str):
        host, port = addr.rsplit(":", 1)
        self.sock = socket.create_connection((host, int(port)), timeout=30)

    def send(self, items):
        payload = b"".join(p + m + s for p, m, s in items)
        self.sock.sendall(len(items).to_bytes(4, "big") + payload)
        out = b""
        while len(out) < len(items):
            chunk = self.sock.recv(len(items) - len(out))
            assert chunk
            out += chunk
        return [bool(b) for b in out]

    def send_later(self, items, results, key):
        t = threading.Thread(target=lambda: results.__setitem__(key, self.send(items)))
        t.start()
        return t

    def close(self):
        self.sock.close()


def _sizes_backend(calls, gate=None):
    def backend(items):
        calls.append(len(items))
        if gate is not None and len(calls) == 1:
            gate.wait(20)
        return [p[0] == s[0] for p, m, s in items]

    return backend


def test_a_window_with_room_is_held_for_whoever_is_in_step():
    """``hold_s`` (the daemon sets its engine's) keeps a window open while
    the shape it would run at has room AND somebody in step is still out: a
    connection the last launch answered whose next request has not come.
    The window goes the moment the last of them is back (one launch for
    both), or has hung up. The hold here is 30 s: only that explains it."""
    calls, results = [], {}
    svc = VerifierService(backend=_sizes_backend(calls)).start()
    svc.hold_s = lambda n: 30.0
    a, b = _Conn(svc.address), _Conn(svc.address)
    try:
        assert a.send([_item(1, True)]) == [True]  # nobody to wait for: at once
        t = b.send_later([_item(2, False)], results, "b")
        t.join(0.4)
        assert t.is_alive() and calls == [1], "b's window went without a"
        assert a.send([_item(3, True)]) == [True]  # a is back: both go, together
        t.join(10)
        assert results == {"b": [False]} and calls == [1, 2]
        t = a.send_later([_item(4, True)], results, "a")  # now b is the one out
        t.join(0.4)
        assert t.is_alive() and calls == [1, 2]
        b.close()  # ... and hangs up: nothing left to wait for
        t.join(10)
        assert results["a"] == [True] and calls == [1, 2, 1]
    finally:
        a.close()
        b.close()
        svc.stop()


def test_a_window_is_held_while_a_launch_is_in_flight():
    """Whoever rides the launch in flight comes back after it: a window cut
    now would miss them, so it stays open (until its hold runs out)."""
    calls, results = [], {}
    gate = threading.Event()
    svc = VerifierService(backend=_sizes_backend(calls, gate), inflight=2).start()
    svc.hold_s = lambda n: 30.0
    a, b = _Conn(svc.address), _Conn(svc.address)
    try:
        ta = a.send_later([_item(1, True)], results, "a1")
        while not calls:
            time.sleep(0.01)  # a's launch is on the (gated) device
        tb = b.send_later([_item(2, True)], results, "b")
        tb.join(0.4)
        assert tb.is_alive() and calls == [1], "a second slot was free, and was taken"
        gate.set()
        ta.join(10)
        tb.join(0.4)
        assert tb.is_alive() and calls == [1]  # a was answered and is still out
        assert a.send([_item(3, False)]) == [False]
        tb.join(10)
        assert results == {"a1": [True], "b": [True]} and calls == [1, 2]
    finally:
        gate.set()
        a.close()
        b.close()
        svc.stop()


def test_a_caller_alone_never_pays_the_hold():
    calls = []
    svc = VerifierService(backend=_sizes_backend(calls)).start()
    svc.hold_s = lambda n: 30.0
    a = _Conn(svc.address)
    try:
        t0 = time.monotonic()
        for k in range(5):
            assert a.send([_item(k + 1, True), _item(k + 9, False)]) == [True, False]
        assert time.monotonic() - t0 < 10 and calls == [2] * 5
    finally:
        a.close()
        svc.stop()


def test_a_held_window_goes_when_it_fills_or_its_hold_runs_out(tmp_path):
    """The hold is a bound, counted from the oldest request's ARRIVAL (it
    shows as ``queue_s``); a window that fills the shape it would run at
    (``hold_s`` says 0) goes at once; and with no ``hold_s`` (a bare
    service) every window is cut at once, whoever is still out."""
    import json

    calls = []
    trace = tmp_path / "service.jsonl"
    svc = VerifierService(backend=_sizes_backend(calls), trace_path=str(trace)).start()
    a, b = _Conn(svc.address), _Conn(svc.address)
    try:
        svc.hold_s = lambda n: 0.3 if n < 2 else 0.0
        assert a.send([_item(1, True)]) == [True]  # a is answered, and stays out
        t0 = time.monotonic()
        assert b.send([_item(2, True)]) == [True]
        held = time.monotonic() - t0
        t0 = time.monotonic()
        assert b.send([_item(3, True), _item(4, False)]) == [True, False]
        full = time.monotonic() - t0
        svc.hold_s = None
        t0 = time.monotonic()
        assert a.send([_item(5, True)]) == [True]  # b is out: nobody asks
        bare = time.monotonic() - t0
    finally:
        a.close()
        b.close()
        svc.stop()
    assert 0.3 <= held < 5.0 and full < 0.25 and bare < 0.25, (held, full, bare)
    lines = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert [e["size"] for e in lines] == [1, 1, 2, 1]
    assert lines[1]["queue_s"] >= 0.29 and lines[2]["queue_s"] < 0.25
    assert (lines[3]["hold_s"], lines[3]["held_out"], lines[3]["in_step"]) == (0.0, 0, 0)


@pytest.mark.parametrize(
    "hold, b_sends, c_sends, a_comes_back, want",
    [
        # b's window has room and a, whom the last launch answered, is out:
        # it is cut when a is back, long before its hold is over.
        (30.0, 1, 0, True, {"size": 2, "hold_s": 30.0, "held_out": 0, "in_step": 1}),
        # a stays out: the hold runs out.
        (0.3, 1, 0, False, {"size": 1, "hold_s": 0.3, "held_out": 1, "in_step": 0}),
        # the window fills the shape it would run at: no hold is granted.
        (30.0, 4, 0, False, {"size": 4, "hold_s": 0.0, "held_out": 0, "in_step": 0}),
        # a trickle: b, then c, then a, each on its own connection and a few
        # ms apart, share ONE launch (alone, each would have been one).
        (30.0, 1, 1, True, {"size": 3, "requests": 3, "hold_s": 30.0, "held_out": 0, "in_step": 1}),
        # c fills the window b holds open: it goes at once, although a is
        # still out and the hold it was granted is a minute.
        (60.0, 1, 3, False, {"size": 4, "requests": 2, "hold_s": 0.0, "held_out": 0, "in_step": 0}),
    ],
    ids=["in_step", "held_out", "full", "trickle", "fills_while_held"],
)
def test_the_line_says_which_exit_of_the_hold_cut_the_window(
    tmp_path, hold, b_sends, c_sends, a_comes_back, want
):
    """Every ``verify_batch`` line carries the hold its window was granted at
    the cut (``hold_s``) and which exit cut it: ``in_step`` (nobody in step
    was still out), ``held_out`` (the hold ran out) or neither (it filled
    its shape); the status JSON counts both."""
    import json

    trace = tmp_path / "service.jsonl"
    svc = VerifierService(backend=_sizes_backend([]), trace_path=str(trace)).start()
    svc.hold_s = lambda n: hold if n < 4 else 0.0
    a, b, c = _Conn(svc.address), _Conn(svc.address), _Conn(svc.address)
    results = {}
    try:
        assert a.send([_item(1, True)]) == [True]  # a caller alone: in step with nobody
        t0 = time.monotonic()
        t = b.send_later([_item(2 + k, True) for k in range(b_sends)], results, "b")
        if c_sends:
            t.join(0.05)
            assert t.is_alive()
            tc = c.send_later([_item(6 + k, k == 0) for k in range(c_sends)], results, "c")
        if a_comes_back:
            t.join(0.2)
            assert t.is_alive()
            assert a.send([_item(9, False)]) == [False]
        t.join(10)
        assert results["b"] == [True] * b_sends
        if c_sends:
            tc.join(10)
            assert results["c"] == [k == 0 for k in range(c_sends)]
        assert time.monotonic() - t0 < 20  # no case waits its hold out but held_out's 0.3 s
        status = svc.launch_status()
    finally:
        a.close()
        b.close()
        c.close()
        svc.stop()
    first, second = [json.loads(ln) for ln in trace.read_text().splitlines()]
    assert {k: first[k] for k in ("hold_s", "held_out", "in_step")} == {
        "hold_s": hold, "held_out": 0, "in_step": 1,
    }
    assert {k: second[k] for k in want} == want
    assert second["queue_s"] >= want["hold_s"] * want["held_out"]
    assert status["in_step_launches"] == 1 + want["in_step"]
    assert status["held_out_launches"] == want["held_out"]
    assert status["launches_by_rung"] == {}  # this backend runs no shape


def test_a_backlog_beyond_the_largest_window_is_cut_there_and_not_held(tmp_path):
    """ISSUE 42: the hold is asked about the window that will be cut, not
    about everything queued. With more queued than the largest window holds
    the engine's rule finds room on the backlog's LAST shape and grants its
    hold (up to PR 41 the chip-paced n=31 cell read ``hold_s`` 29.6 ms and
    ``held_out`` 1 on 998 launches in 1,000, every one of them a window that
    was full): a window that is cut at ``MAX_WINDOW`` cannot grow, so it goes
    at once, with ``cut_full`` 1 and what is left behind in
    ``pending_at_cut``."""
    import json

    trace = tmp_path / "service.jsonl"
    gate = threading.Event()
    svc = VerifierService(
        backend=_sizes_backend([], gate), trace_path=str(trace), inflight=2
    ).start()
    svc.MAX_WINDOW = 4  # the largest window, for this service alone
    svc.hold_s = lambda n: 0.0 if n % 4 == 0 else 30.0  # room on the last shape: its hold
    a, b, c = _Conn(svc.address), _Conn(svc.address), _Conn(svc.address)
    results = {}
    try:
        ta = a.send_later([_item(1, True)], results, "a")  # in flight until the gate opens
        deadline = time.monotonic() + 10
        while svc._flying == 0:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        # b's window has room and a is out: it is held (for 30 s, were it
        # alone) until c's request takes what is queued past the largest window.
        tb = b.send_later([_item(2 + k, True) for k in range(3)], results, "b")
        deadline = time.monotonic() + 10
        while not svc._pending:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        tb.join(0.05)
        assert tb.is_alive()
        tc = c.send_later([_item(6 + k, k == 0) for k in range(4)], results, "c")
        tb.join(10)
        tc.join(10)
        # Both came back while a's launch, with whom nobody is in step, was
        # still out: neither window waited for it, nor for a hold.
        assert not gate.is_set() and ta.is_alive()
        assert results["b"] == [True] * 3 and results["c"] == [True, False, False, False]
        status = svc.launch_status()
    finally:
        gate.set()
        ta.join(10)
        for conn in (a, b, c):
            conn.close()
        svc.stop()
    lines = {e["size"]: e for e in map(json.loads, trace.read_text().splitlines())}
    want = {"hold_s": 0.0, "held_out": 0, "in_step": 0}
    assert {k: lines[3][k] for k in (*want, "cut_full", "pending_at_cut")} == {
        **want, "cut_full": 1, "pending_at_cut": 4}
    assert {k: lines[4][k] for k in (*want, "cut_full", "pending_at_cut")} == {
        **want, "cut_full": 0, "pending_at_cut": 0}
    assert lines[3]["queue_s"] < 5 and lines[4]["queue_s"] < 5
    assert (status["windows_cut_full"], status["overflow_items_max"]) == (1, 4)
    assert (status["held_out_launches"], status["in_step_launches"]) == (0, 0)


@pytest.mark.parametrize("family", ["tcp", "unix"])
def test_a_whole_cluster_dialing_at_once_is_accepted_without_a_retry(tmp_path, family):
    """32 replicas dial in the same instant, before the server has accepted
    anybody (it is not even serving yet): the listen queue holds them all.
    With socketserver's queue of 5 the seventh SYN is dropped and retried a
    second later, past a replica's connect deadline of 250 ms."""
    if family == "unix":
        svc = VerifierService(unix_path=str(tmp_path / "v.sock"), backend=_sizes_backend([]))
    else:
        svc = VerifierService(backend=_sizes_backend([]))
    socks = []
    try:
        for _ in range(32):
            if family == "unix":
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.settimeout(0.25)
                sock.connect(svc.address)
            else:
                host, port = svc.address.rsplit(":", 1)
                sock = socket.create_connection((host, int(port)), timeout=0.25)
            socks.append(sock)
        svc.start()
        for k, sock in enumerate(socks):  # and every one of them is served
            sock.settimeout(30)
            pub, msg, sig = _item(k + 1, k % 2 == 0)
            sock.sendall((1).to_bytes(4, "big") + pub + msg + sig)
            assert sock.recv(1) == bytes([k % 2 == 0])
    finally:
        for sock in socks:
            sock.close()
        if svc._thread is None:  # never served: stop() would wait for a loop that never ran
            svc.server.server_close()
        else:
            svc.stop()


def test_service_trace_records_merged_windows(tmp_path):
    """The per-dispatch trace is the honest items-per-LAUNCH record for
    the launch-cost model (per-replica traces only see each daemon's
    share of a merged window)."""
    import json

    trace = tmp_path / "service.jsonl"
    svc = VerifierService(backend=_sizes_backend([]), trace_path=str(trace)).start()
    svc.hold_s = lambda n: 30.0  # b's window stays open until a is back
    a, b = _Conn(svc.address), _Conn(svc.address)
    results = {}
    try:
        assert a.send([_item(1, True)]) == [True]
        t = b.send_later([_item(2, True), _item(2, True)], results, "b")
        t.join(0.2)
        assert t.is_alive()
        assert a.send([_item(3, True), _item(3, False)]) == [True, False]
        t.join(10)
        assert results["b"] == [True, True]
    finally:
        a.close()
        b.close()
        svc.stop()
    events = [json.loads(line) for line in trace.read_text().splitlines()]
    batches = [e for e in events if e["ev"] == "verify_batch"]
    assert [(e["size"], e["requests"], e["rejected"]) for e in batches] == [
        (1, 1, 0),
        (4, 2, 1),  # one line for the merged launch, not one a connection
    ]
    assert all(e["secs"] >= 0 and e["replica"] == "service" for e in batches)


def test_overlapped_launches_hide_launch_latency():
    """inflight=2: window N+1 ships while N executes, so two slow
    launches overlap in wall time; the serial default cannot. Verdict
    slicing stays per-request in both modes."""

    def run(inflight: int):
        first_launch_started = threading.Event()
        spans = []  # (start, end) per backend call, appended at the end

        def slow_backend(items):
            start = time.monotonic()
            first_launch_started.set()
            time.sleep(0.35)  # stands in for launch RTT; releases the GIL
            spans.append((start, time.monotonic()))
            return [p[0] == s[0] for p, m, s in items]

        svc = VerifierService(backend=slow_backend, inflight=inflight).start()
        try:
            results = {}

            def client(cid: int):
                if cid == 2:
                    # Only submit once launch 1 is provably in flight, so
                    # the requests deterministically form TWO windows (a
                    # sleep-based stagger could coalesce on a loaded box).
                    assert first_launch_started.wait(10)
                results[cid] = _send_batch(svc.address, [_item(cid, True)])

            threads = [
                threading.Thread(target=client, args=(c,)) for c in (1, 2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=15)
            assert results[1] == [True] and results[2] == [True]
            assert svc.batches == 2, svc.batches
            assert len(spans) == 2, spans
            return sorted(spans)
        finally:
            svc.stop()

    # Load-immune assertion: compare launch SPANS, not wall-clock totals
    # (the box's shared core can stall either run arbitrarily). Serial
    # mode must not start launch 2 before launch 1 returned; overlapped
    # mode must.
    serial = run(1)
    assert serial[1][0] >= serial[0][1], f"serial launches overlapped: {serial}"
    overlapped = run(2)
    assert overlapped[1][0] < overlapped[0][1], (
        f"overlapped launches serialized: {overlapped}"
    )


# -- persistent-service lifecycle (ISSUE 7) ----------------------------------


def _fake_kernel(pubs, msgs, sigs):
    """Cheap jit-able stand-in for the Ed25519 kernel (compiles in ms):
    valid iff sig[0] == pub[0] — same rule as the fake socket backends."""
    return pubs[:, 0] == sigs[:, 0]


def test_status_probe_reports_state_and_traffic_continues():
    """The readiness handshake: count-0 returns the 8-byte status, the
    JSON probe returns the rich status, and a batch on the SAME connection
    after a probe still verifies (probes must not desync the stream)."""

    def backend(items):
        return [p[0] == s[0] for p, m, s in items]

    svc = VerifierService(backend=backend).start()
    try:
        assert probe_status(svc.address) == (STATE_CPU_ONLY, 0, 0)
        js = probe_status_json(svc.address)
        assert js["state"] == "cpu-only" and js["backend"] == "custom"
        host, port = svc.address.rsplit(":", 1)
        with socket.create_connection((host, int(port)), timeout=10) as sock:
            sock.sendall((0).to_bytes(4, "big"))  # binary probe
            status = b""
            while len(status) < 8:
                status += sock.recv(8 - len(status))
            assert status[:2] == b"VS"
            p, m, s = _item(9, True)
            sock.sendall((1).to_bytes(4, "big") + p + m + s)
            assert sock.recv(1) == b"\x01"
    finally:
        svc.stop()


@pytest.mark.parametrize(
    "kwargs, named",
    [
        ({}, "native"),
        ({"backend": "native"}, "native"),
        ({"backend": "cpu"}, "cpu"),
        ({"backend": lambda items: [False] * len(items)}, "custom"),
    ],
    ids=["default", "native", "cpu", "callable"],
)
def test_a_bare_service_never_says_ready(kwargs, named):
    """``ready`` means a warmed engine, and only the daemon has one: a bare
    ``VerifierService`` answers ``cpu-only`` on both probes whatever serves
    it, before and after traffic, and "jax" is no backend it knows by name
    (that one compiled at the first window, behind a ``ready``)."""
    with pytest.raises(KeyError):
        VerifierService(backend="jax")
    svc = VerifierService(**kwargs).start()
    try:
        for _ in range(2):
            assert probe_status(svc.address) == (STATE_CPU_ONLY, 0, 0)
            js = probe_status_json(svc.address)
            assert js["state"] == "cpu-only" and js["devices"] == 0
            assert js["backend"] == named
            assert _send_batch(svc.address, [(bytes(32), bytes(32), bytes(64))]) == [False]
    finally:
        svc.stop()


class _StubEngine:
    """Engine double with a gated warmup and a distinguishable verdict."""

    def __init__(self, gate, platform="tpu", warm_error=None):
        self.gate = gate
        self._platform = platform
        self._warm_error = warm_error
        self.platform = None
        self.device_kind = None
        self.devices_seen = 0
        self.device_count = 0
        self.stats = {}
        self._warmed = ()

    @property
    def warmed_sizes(self):
        return self._warmed

    def init_backend(self):
        self.platform = self._platform
        self.device_kind = f"stub {self._platform}"
        self.devices_seen = self.device_count = 5

    def warm(self):
        assert self.gate.wait(10)
        if self._warm_error:
            raise RuntimeError(self._warm_error)
        self._warmed = (16, 64)
        self.stats = {"cold_compile_s": 0.5, "warm_load_s": 0.0}
        return self.stats

    def verify(self, items):
        return [True] * len(items)  # accept-all: provably not the fallback

    def memory_peak_bytes(self):
        return None


def test_daemon_warming_serves_fallback_then_flips_ready():
    """While the accelerator warms, traffic is served by the fallback
    (never queued behind the warmup); once warm, the readiness handshake
    flips and the engine takes over."""
    gate = threading.Event()
    engine = _StubEngine(gate)
    daemon = VerifyServiceDaemon(
        backend="auto",
        engine=engine,
        fallback=lambda items: [False] * len(items),  # reject-all fallback
    )
    daemon.start()
    try:
        st = probe_status(daemon.address)
        assert st is not None and st[0] == STATE_WARMING
        # Warming: the reject-all fallback answers, the engine does not
        # (a client shipping without the handshake gets the daemon's).
        assert _send_batch(daemon.address, [_item(2, True)]) == [False]
        gate.set()
        deadline = time.monotonic() + 10
        while daemon.state != STATE_READY and time.monotonic() < deadline:
            time.sleep(0.02)
        assert probe_status(daemon.address) == (STATE_READY, 5, 2)
        # Ready: the accept-all engine answers.
        assert _send_batch(daemon.address, [_item(3, False)]) == [True]
        js = probe_status_json(daemon.address)
        assert js["state"] == "ready" and js["devices"] == 5
        assert js["warm_stats"]["cold_compile_s"] == 0.5
        # What the device runs on, and engine dispatches counted apart
        # from the fallback's: one pre-handshake batch hit the fallback
        # while warming; everything since `ready` went to the engine.
        assert js["platform"] == "tpu" and js["device_kind"] == "stub tpu"
        assert js["devices_seen"] == 5
        assert (js["fallback_launches"], js["fallback_items"]) == (1, 1)
        assert js["engine_launches"] >= 1
        assert js["engine_items"] == js["engine_launches"]  # 1-item batches
    finally:
        gate.set()
        daemon.stop()


def _run_verifyd_main(engine, backend="jax"):
    """verifyd's CLI in-process with a stub engine; returns its exit code
    (None = it kept serving past the deadline)."""
    from pbft_tpu.net import verify_service

    box = {}

    def run():
        try:
            verify_service.main(
                ["--backend", backend, "--port", "0"], engine=engine
            )
        except SystemExit as e:
            box["code"] = e.code

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(10)
    return box.get("code")


def test_verifyd_backend_jax_exits_nonzero_when_warm_raises(capfd):
    """The chip deployment never degrades to a CPU service: a warm-up
    exception ends the process non-zero with the error."""
    gate = threading.Event()
    gate.set()
    code = _run_verifyd_main(_StubEngine(gate, warm_error="mosaic said no"))
    assert code == 1
    assert "mosaic said no" in capfd.readouterr().err


def test_verifyd_backend_jax_exits_nonzero_off_tpu(monkeypatch, capfd):
    """JAX that silently fell back to XLA:CPU (JAX_PLATFORMS does not name
    cpu) must not be warmed and served as if it were the chip."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    gate = threading.Event()  # never set: warm() must not even be reached
    code = _run_verifyd_main(_StubEngine(gate, platform="cpu"))
    assert code == 1
    err = capfd.readouterr().err
    assert "needs a TPU" in err and "'cpu'" in err


@pytest.mark.parametrize(
    "flag", ["--flush-us", "--flush-items", "--window", "--warm-shapes", "--inflight"]
)
def test_verifyd_refuses_a_flag_that_is_gone(flag, capfd):
    """A stale unit file must fail loudly, not run another policy: the
    flags of the flush window and the knobs nobody turned are a usage
    error (exit 2), and nothing is left listening."""
    from pbft_tpu.net import verify_service
    from pbft_tpu.net.launcher import free_ports

    port = free_ports(1)[0]
    with pytest.raises(SystemExit) as exit_:
        verify_service.main(["--backend", "native", "--port", str(port), flag, "16"])
    assert exit_.value.code == 2
    assert f"unrecognized arguments: {flag}" in capfd.readouterr().err
    assert probe_status(f"127.0.0.1:{port}", timeout=0.2) is None


def test_backend_jax_on_cpu_is_allowed_when_platforms_names_cpu(monkeypatch):
    """JAX_PLATFORMS=cpu is the test arm: --backend jax may warm there
    (and says platform cpu, which chip_smoke.py / bench.py then refuse)."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    gate = threading.Event()
    gate.set()
    daemon = VerifyServiceDaemon(
        backend="jax", engine=_StubEngine(gate, platform="cpu")
    ).start(wait_ready=True)
    try:
        js = probe_status_json(daemon.address)
        assert daemon.fatal_error is None
        assert js["state"] == "ready" and js["platform"] == "cpu"
    finally:
        daemon.stop()


def test_backend_auto_still_degrades_to_cpu_only():
    """auto keeps its meaning for chip-less deployments: a failed warm-up
    is cpu-only service, not an exit."""
    gate = threading.Event()
    gate.set()
    daemon = VerifyServiceDaemon(
        backend="auto",
        engine=_StubEngine(gate, warm_error="no backend"),
        fallback=lambda items: [False] * len(items),
    ).start(wait_ready=True)
    try:
        assert daemon.fatal_error is None
        assert daemon.state == STATE_CPU_ONLY
        js = probe_status_json(daemon.address)
        assert "no backend" in js["warm_error"]
    finally:
        daemon.stop()


def test_wait_for_tpu_service_refuses_everything_but_a_ready_tpu():
    """bench.py's and chip_smoke.py's gate: only `ready` on platform
    `tpu` passes; a CPU platform, a cpu-only state and a dead daemon
    fail at once, not at the end of the budget."""
    import pytest

    from pbft_tpu.net.verify_service import (
        VerifydNotReady,
        wait_for_tpu_service,
    )

    gate = threading.Event()
    gate.set()
    ok = VerifyServiceDaemon(backend="auto", engine=_StubEngine(gate)).start(
        wait_ready=True
    )
    try:
        st = wait_for_tpu_service(ok.address, budget_s=5)
        assert st["platform"] == "tpu" and st["state"] == "ready"
        assert ok.service.hold_s is None  # an engine with no rule: no window is held
    finally:
        ok.stop()

    hold = threading.Event()  # keeps the CPU stub in `warming`
    on_cpu = VerifyServiceDaemon(
        backend="auto", engine=_StubEngine(hold, platform="cpu")
    ).start()
    try:
        t0 = time.monotonic()
        with pytest.raises(VerifydNotReady, match="no TPU.*'cpu'"):
            wait_for_tpu_service(on_cpu.address, budget_s=60)
        assert time.monotonic() - t0 < 10  # while still warming, not after
    finally:
        hold.set()
        on_cpu.stop()

    native_svc = VerifyServiceDaemon(backend="native").start()
    try:
        with pytest.raises(VerifydNotReady, match="cpu-only"):
            wait_for_tpu_service(native_svc.address, budget_s=60)
    finally:
        native_svc.stop()

    class _Dead:
        returncode = 1

        def poll(self):
            return 1

    with pytest.raises(VerifydNotReady, match="exited with code 1"):
        wait_for_tpu_service("127.0.0.1:1", proc=_Dead(), budget_s=60)


@pytest.mark.parametrize("net_threads", [1, 2])
def test_cluster_falls_back_when_service_killed_mid_stream(tmp_path, net_threads):
    """The satellite contract end to end: a pbftd cluster
    dials a real verifyd subprocess; SIGKILL it mid-run; replicas must
    keep committing via their native pools with no liveness stall."""
    import os
    import signal
    import subprocess
    import sys

    import pytest

    from pbft_tpu import native

    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    from pbft_tpu.net import LocalCluster, PbftClient
    from pbft_tpu.net.launcher import free_ports

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = free_ports(1)[0]
    proc = subprocess.Popen(
        [
            sys.executable,
            os.path.join(repo, "scripts", "verifyd.py"),
            "--backend",
            "native",
            "--port",
            str(port),
        ],
        stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=repo),
    )
    target = f"127.0.0.1:{port}"
    try:
        deadline = time.monotonic() + 30
        while probe_status(target) is None:
            assert time.monotonic() < deadline, "verifyd never listened"
            assert proc.poll() is None, "verifyd died at startup"
            time.sleep(0.1)
        with LocalCluster(
            n=4,
            verifier=target,
            net_threads=net_threads,
            metrics_every=1,
        ) as cluster:

            def fallbacks():
                """Each replica's latest verify_service_fallbacks, from
                the metrics line pbftd prints (None = no line yet)."""
                import re
                from pathlib import Path

                out = []
                for i in range(4):
                    log = Path(cluster.tmpdir.name) / f"replica-{i}.log"
                    seen = re.findall(
                        r'"verify_service_fallbacks":\s*(\d+)',
                        log.read_text(errors="replace"),
                    )
                    out.append(int(seen[-1]) if seen else None)
                return out

            client = PbftClient(cluster.config)
            try:
                req = client.request("with-service")
                assert client.wait_result(req.timestamp, timeout=20) == "awesome!"
                # The service answered from the first dial: a cluster that
                # reached it reports ZERO host fallbacks.
                deadline = time.monotonic() + 10
                while None in fallbacks():
                    assert time.monotonic() < deadline, cluster.logs()
                    time.sleep(0.2)
                assert fallbacks() == [0, 0, 0, 0], cluster.logs()
                proc.send_signal(signal.SIGKILL)
                proc.wait(timeout=10)
                # No stall: every post-kill request commits on the
                # native-pool fallback well inside the timeout.
                for i in range(3):
                    req = client.request(f"after-kill-{i}")
                    assert (
                        client.wait_result(req.timestamp, timeout=20)
                        == "awesome!"
                    ), cluster.logs()
                # ...and is COUNTED: the fallback is the liveness
                # guarantee, the count is what keeps it from hiding a dead
                # service.
                deadline = time.monotonic() + 10
                while not all(n and n > 0 for n in fallbacks()):
                    assert time.monotonic() < deadline, (
                        fallbacks(),
                        cluster.logs(),
                    )
                    time.sleep(0.2)
            finally:
                client.close()
    finally:
        if proc.poll() is None:
            proc.kill()


def test_engine_parity_pad_slots_and_window_boundaries():
    """Sharded-engine verdicts must be bit-identical to the plain
    evaluation of the same rule across pad slots, shape boundaries, and
    the multi-window chunking path (the real-kernel equivalence against
    the oracle/native arms is pinned in test_parallel.py's slow tier)."""
    eng = ShardedVerifyEngine(shapes=(8, 16), kernel=_fake_kernel)
    eng.warm()
    assert eng.device_count >= 1
    # 11 items -> padded to 16: pad slots must be sliced off, invalid
    # items at the boundary must stay invalid.
    items = [_item(i + 1, i % 3 != 0) for i in range(11)]
    want = [i % 3 != 0 for i in range(11)]
    assert eng.verify(items) == want
    # The empty window, and a few items of mixed validity on the small shape.
    assert eng.verify([]) == []
    assert eng.verify(items[:5]) == want[:5] == [False, True, True, False, True]
    # Exactly one shape (8) and one item past it (9 -> 16).
    assert eng.verify(items[:8]) == want[:8]
    assert eng.verify(items[:9]) == want[:9]
    # Oversized: chunks into top-of-ladder windows, order preserved.
    big = [_item((i % 23) + 1, i % 5 != 0) for i in range(40)]
    assert eng.verify(big) == [i % 5 != 0 for i in range(40)]


def test_engine_parity_when_a_window_runs_on_a_larger_shape():
    """The serving table may send a window to a larger warmed shape than the
    smallest that fits (ISSUE 27): more pad slots, the same verdicts, and
    every shape asked for still compiled."""
    from pbft_tpu.utils.trace import open_span

    eng = ShardedVerifyEngine(shapes=(8, 16), kernel=_fake_kernel)
    stats = eng.warm()
    assert eng.warmed_sizes == (8, 16)
    assert [p["size"] for p in stats["per_shape"]] == [8, 16]
    assert all(p["launch_s"] > 0 for p in stats["per_shape"])
    assert set(stats["serving_table"]) == {"8", "16"}
    sizes = (1, 8, 9, 16, 17)
    for costs, rungs, promoted in (
        ({8: 0.001, 16: 0.001}, (8, 8, 16, 16, 16 + 8), (0, 0, 0, 0, 0)),
        ({8: 0.001, 16: 0.0001}, (16, 16, 16, 16, 16 + 16), (1, 1, 0, 0, 1)),
    ):
        eng._route(costs)
        for n, rung, chunks in zip(sizes, rungs, promoted):
            items = [_item(i + 1, i % 3 != 0) for i in range(n)]
            with open_span() as span:
                assert eng.verify(items) == [i % 3 != 0 for i in range(n)]
            assert (span["rung"], span["promoted"]) == (rung, chunks)
            assert (span["chunks"], span["split"]) == ((2, 1) if n == 17 else (1, 0))


@pytest.mark.parametrize(
    "costs, n, plan",
    [
        # Injected costs (what a CPU reads decides nothing here): a 32-slot
        # program that costs four times the 8- and 16-slot ones.
        ({8: 0.001, 16: 0.001, 32: 0.004}, 17, (16, 8)),
        ({8: 0.001, 16: 0.001, 32: 0.004}, 24, (16, 8)),
        ({8: 0.001, 16: 0.001, 32: 0.004}, 25, (16, 16)),
        ({8: 0.001, 16: 0.001, 32: 0.004}, 32, (16, 16)),
        # ... and ten times: three chunks are still under it.
        ({8: 0.001, 32: 0.003, 128: 0.020}, 41, (32, 8, 8)),
        ({8: 0.001, 32: 0.003, 128: 0.020}, 48, (32, 8, 8)),
        ({8: 0.001, 16: 0.004, 32: 0.010}, 29, (8, 8, 8, 8)),
        # Beyond the top: a chunk of it, and the rest by its plan.
        ({8: 0.001, 16: 0.001, 32: 0.004}, 32 + 20, (32, 16, 8)),
    ],
)
def test_engine_parity_when_a_window_runs_as_chunks(costs, n, plan):
    """The chunk plan may run a window as several launches of smaller shapes
    (ISSUE 29): the same verdicts, in item order, as one launch of the
    smallest shape that fits and as the rule evaluated on the host, with
    rejects planted on both sides of every chunk boundary."""
    import itertools

    from pbft_tpu.utils.trace import open_span

    shapes = tuple(costs)
    eng = ShardedVerifyEngine(shapes=shapes, kernel=_fake_kernel)
    eng.warm()
    whole = ShardedVerifyEngine(shapes=shapes, kernel=_fake_kernel)
    whole.warm()
    whole._route(dict.fromkeys(shapes, 0.001))  # one launch a window up to the top
    eng._route(costs)
    edges = set(itertools.accumulate(plan))
    for planted in (
        {e - 1 for e in edges} | set(edges),  # the last of a chunk and the first of the next
        {e - 1 for e in edges},
        set(edges),
        set(),
        set(range(n)),
    ):
        want = [i not in planted for i in range(n)]
        items = [_item((i % 200) + 1, ok) for i, ok in enumerate(want)]
        with open_span() as span:
            got = eng.verify(items)
        assert got == want == whole.verify(items)
        assert got == [p[0] == s[0] for p, m, s in items]  # the rule, on the host
        assert (span["chunks"], span["rung"], span["split"]) == (len(plan), sum(plan), 1)
    assert eng._plan(n) == plan


# -- a request is the block it came off the wire as (ISSUE 45) ------------------


def _wire_blocks(items, sizes):
    """``items`` cut into requests of ``sizes`` as the handler holds them: one
    ``(n, 128)`` uint8 block each, over a buffer of its own."""
    import numpy as np

    blocks, off = [], 0
    for n in sizes:
        wire = bytearray(b"".join(p + m + s for p, m, s in items[off : off + n]))
        blocks.append(np.frombuffer(wire, np.uint8).reshape(n, 128))
        off += n
    return blocks


def _blocks(sizes, planted=()):
    """Requests of ``sizes`` items as wire blocks, with the items (rejects at
    the window's positions ``planted``) and the fake kernel's verdicts."""
    want = [i not in planted for i in range(sum(sizes))]
    items = [_item((i % 200) + 1, ok) for i, ok in enumerate(want)]
    return _wire_blocks(items, sizes), items, want


def test_a_window_is_a_sequence_of_triples_over_its_requests_blocks():
    import numpy as np

    from pbft_tpu.net.service import Window, as_rows

    blocks, items, _want = _blocks([5, 14, 4])
    window = Window(blocks)
    assert len(window) == 23 and list(window) == items and list(window) == items  # twice
    assert [window[i] for i in range(23)] == items and window[-1] == items[-1]
    assert window[3:7] == items[3:7] and window[::-5] == items[::-5] and window[21:99] == items[21:]
    assert window[:0] == [] and window.index(items[6]) == 6 and items[19] in window
    for wild in (23, -24):
        with pytest.raises(IndexError):
            window[wild]
    # rows(): views of the requests' own buffers, cut where asked and nowhere else
    for (a, b), cuts in {(0, 16): [5, 11], (16, 24): [3, 4], (5, 19): [14], (4, 20): [1, 14, 1],
                         (0, 23): [5, 14, 4], (19, 19): [], (23, 40): []}.items():
        got = window.rows(a, b)
        assert [len(r) for r in got] == cuts, (a, b)
        assert b"".join(r.tobytes() for r in got) == b"".join(p + m + s for p, m, s in items[a:b])
        assert all(any(np.shares_memory(r, blk) for blk in blocks) for r in got)
    assert len(Window([])) == 0 and list(Window([])) == [] and Window([]).rows(0, 8) == []
    # a list of triples becomes ONE block at the door, or is refused there
    assert np.array_equal(as_rows(items), np.concatenate(blocks)) and as_rows([]).shape == (0, 128)
    with pytest.raises(ValueError, match="not 128-byte triples"):
        as_rows([(items[0][0], items[0][1], items[0][2][:63])])


@pytest.mark.parametrize(
    "costs, sizes, plan, writes",
    [
        # (a) a request straddles a chunk's edge: it is staged in two pieces
        ({8: 0.001, 16: 0.001, 32: 0.004}, [5, 14, 4], (16, 8), [2, 2]),
        ({8: 0.001, 32: 0.003, 128: 0.020}, [30, 1, 1, 9, 7], (32, 8, 8), [3, 1, 2]),
        # (b) a request fills a shape exactly, alone and as the first of two chunks
        ({8: 0.001, 16: 0.001}, [16], (16,), [1]),
        ({8: 0.001, 16: 0.001, 32: 0.004}, [16, 8], (16, 8), [1, 1]),
        ({8: 0.001, 16: 0.001}, [3, 5], (8,), [2]),
        # one request beyond the largest shape: a piece a chunk
        ({8: 0.001, 16: 0.001}, [40], (16, 16, 8), [1, 1, 1]),
    ],
    ids=["straddle", "straddle-twice", "fills-16", "fills-16-then-8", "two-fill-8", "oversized"],
)
def test_a_window_of_blocks_is_staged_a_request_a_slice(monkeypatch, costs, sizes, plan, writes):
    """The engine on the dispatcher's ``Window``: one assignment a request
    segment into each chunk's block (``writes`` a chunk), nothing made an
    item, the verdicts ONE bool array equal to the rule on the host and to
    what the same items give as a list, with rejects planted first, last,
    at every request's edge and on both sides of every chunk's."""
    import itertools

    import numpy as np

    from pbft_tpu.crypto import batch
    from pbft_tpu.net.service import Window
    from pbft_tpu.utils.trace import open_span

    eng = ShardedVerifyEngine(shapes=tuple(costs), kernel=_fake_kernel)
    eng.warm()
    eng._route(costs)
    n = sum(sizes)
    assert eng._plan(n) == plan
    staged, real_pad = [], batch.pad_rows
    monkeypatch.setattr(
        batch, "pad_rows", lambda segs, size: staged.append([len(r) for r in segs]) or real_pad(segs, size)
    )
    monkeypatch.setattr(Window, "__iter__", lambda self: pytest.fail("an item was made"))
    monkeypatch.setattr(Window, "__getitem__", lambda self, i: pytest.fail("an item was made"))
    edges = set(itertools.accumulate(sizes)) | set(itertools.accumulate(plan))
    for planted in (
        {0, n - 1} | {e - 1 for e in edges} | {e for e in edges if e < n},
        set(),
        set(range(n)),
    ):
        blocks, items, want = _blocks(sizes, planted)
        staged.clear()
        with open_span() as span:
            got = eng.verify(Window(blocks))
        assert isinstance(got, np.ndarray) and got.dtype == bool and got.tolist() == want
        assert [len(segs) for segs in staged] == writes and sum(map(sum, staged)) == n
        assert (span["block_items"], span["chunks"], span["rung"]) == (n, len(plan), sum(plan))
        with open_span() as span:
            assert eng.verify(items) == want  # a list in, a list out, packed at the engine's door
        assert span["block_items"] == 0
        assert want == [p[0] == s[0] for p, m, s in items]  # the rule, on the host


def _real_items(n):
    """``n`` really signed items, the first one's signature broken."""
    from pbft_tpu.crypto import ref

    items = []
    for i in range(n):
        seed, msg = bytes([i + 1]) * 32, bytes([0xA0 ^ i]) * 32
        items.append((ref.public_key(seed), msg, ref.sign(seed, msg)))
    items[0] = (*items[0][:2], bytes([items[0][2][0] ^ 1]) + items[0][2][1:])
    return items


@pytest.mark.parametrize("backend", ["callable", "native", "cpu", "fallback", "engine"])
def test_every_backend_is_served_by_the_one_dispatcher_and_the_line_says_how(tmp_path, backend):
    """A merged window reaches a list-returning callable, the ``native`` and
    ``cpu`` backends and the daemon's fallback before ``ready`` as a sequence
    of triples (``block_items`` 0 on the line, ``listed_items`` in the
    status) and the engine as its requests' blocks (``block_items`` = size),
    through the same handler, queue and cut; the verdicts are the same
    bytes on the wire either way."""
    import json

    gate, calls = threading.Event(), []
    real = backend in ("native", "cpu", "fallback")
    items = _real_items(5) if real else [_item(i + 1, i != 0) for i in range(5)]
    want = [False, True, True, True, True]
    engine = ShardedVerifyEngine(shapes=(8,), kernel=_fake_kernel)

    def held(verify):
        def run(window):
            calls.append(len(window))
            if len(calls) == 1:
                gate.wait(20)
            return verify(window)

        return run

    trace = tmp_path / "service.jsonl"
    if backend == "fallback":  # a daemon whose engine never gets warm
        never = threading.Event()
        from pbft_tpu.consensus.replica import host_batch_verify

        daemon = VerifyServiceDaemon(
            backend="auto", engine=_StubEngine(never), trace_path=str(trace),
            fallback=held(host_batch_verify),
        ).start()
        svc, stop = daemon.service, lambda: (never.set(), daemon.stop())
    else:
        if backend == "engine":
            engine.warm()
        verify = {
            "callable": lambda window: [p[0] == s[0] for p, m, s in window],
            "engine": engine.verify,
        }.get(backend)
        if verify is None:
            from pbft_tpu.net import service

            verify = {"native": service.native_backend, "cpu": service.cpu_backend}[backend]
        svc = VerifierService(backend=held(verify), trace_path=str(trace)).start()
        stop = svc.stop
    try:
        results, conns = {}, [_Conn(svc.address) for _ in range(3)]
        threads = [conns[0].send_later(items[:1], results, 0)]
        while not calls:
            time.sleep(0.005)
        for k, part in ((1, items[1:3]), (2, items[3:])):  # queued behind it, in this order
            threads.append(conns[k].send_later(part, results, k))
            while svc.requests < k + 1:
                time.sleep(0.005)
        gate.set()
        for t in threads:
            t.join(timeout=20)
            assert not t.is_alive()
        assert results == {0: want[:1], 1: want[1:3], 2: want[3:]}
        status = svc.launch_status()
    finally:
        gate.set()
        for c in conns:
            c.close()
        stop()
    lines = [json.loads(ln) for ln in trace.read_text().splitlines()]
    if backend != "fallback":  # (the daemon's second launch slot takes the second request at once)
        assert [(e["size"], e["requests"], e["rejected"]) for e in lines] == [(1, 1, 1), (4, 2, 0)]
    assert (sum(e["size"] for e in lines), sum(e["rejected"] for e in lines)) == (5, 1)
    assert all(e["block_items"] == (e["size"] if backend == "engine" else 0) for e in lines)
    assert (status["block_items"], status["listed_items"]) == ((5, 0) if backend == "engine" else (0, 5))


def test_an_in_process_caller_s_list_is_packed_once_at_the_door():
    """``_submit`` with a list of triples (no socket): the same queue and
    window, rows to a backend that stages rows, and a list of bools back."""
    seen = []

    def backend(window):
        seen.append([rows.shape for rows in window.blocks])
        return [p[0] == s[0] for p, m, s in window]

    svc = VerifierService(backend=backend).start()
    try:
        items = [_item(i + 1, i % 2 == 0) for i in range(3)]
        assert svc._submit(items) == [True, False, True]
        assert svc._submit([]) == []
        with pytest.raises(ValueError, match="not 128-byte triples"):
            svc._submit([(b"short", b"", b"")])
    finally:
        svc.stop()
    assert seen == [[(3, 128)], [(0, 128)]]


_WARM_TWICE = """
import json, sys
from pbft_tpu.net import ShardedVerifyEngine

def kernel(pubs, msgs, sigs):
    return pubs[:, 0] == sigs[:, 0] + {delta}

eng = ShardedVerifyEngine(shapes=(8, 16), kernel=kernel)
stats = eng.warm()
item = (bytes([7]) * 32, bytes(32), bytes([7 - {delta}]) + bytes(63))
print(json.dumps({{"stats": stats, "verdict": eng.verify([item])}}))
"""


def _warm_in_fresh_process(cache_dir, delta=0):
    """One verifyd-like start in its own process, the compile cache placed
    from OUTSIDE by JAX_COMPILATION_CACHE_DIR (the thresholds too, so the
    millisecond stand-in kernel qualifies for the cache at all)."""
    import json
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(
        os.environ,
        PYTHONPATH=repo,
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(cache_dir),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
    )
    out = subprocess.run(
        [sys.executable, "-c", _WARM_TWICE.format(delta=delta)],
        capture_output=True,
        text=True,
        env=env,
        timeout=300,
    )
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_warm_over_the_same_cache_dir_is_a_cache_hit(tmp_path):
    """Warm-restart contract, through the ONE cache there is: the first
    start compiles every shape, a second start over the same
    JAX_COMPILATION_CACHE_DIR gets every shape from the cache (zero
    cold-compile seconds), in the directory as given — nothing appended —
    and verdicts survive the reload bit-for-bit."""
    import os

    cache = tmp_path / "cc"
    first = _warm_in_fresh_process(cache)
    s1 = first["stats"]
    assert s1["cache_dir"] == str(cache)
    assert s1["compiled"] == 2 and s1["cache_hits"] == 0, s1
    assert [p["cache_hit"] for p in s1["per_shape"]] == [False, False]
    # As given: the entries sit directly in the directory named.
    entries = os.listdir(cache)
    assert entries and all(
        os.path.isfile(cache / e) for e in entries
    ), entries

    second = _warm_in_fresh_process(cache)
    s2 = second["stats"]
    assert s2["cache_hits"] == 2 and s2["compiled"] == 0, s2
    assert s2["cold_compile_s"] == 0.0
    assert second["verdict"] == first["verdict"] == [True]


def test_changed_kernel_is_never_served_from_the_old_cache(tmp_path):
    """A cache warmed by one kernel must MISS for a changed kernel: the
    entry is keyed by the lowered module, so an edit to the crypto can
    not be measured as "unchanged" through an old artifact."""
    cache = tmp_path / "cc"
    _warm_in_fresh_process(cache)
    changed = _warm_in_fresh_process(cache, delta=1)
    s = changed["stats"]
    assert s["compiled"] == 2 and s["cache_hits"] == 0, s
    assert changed["verdict"] == [True]  # the NEW rule answered

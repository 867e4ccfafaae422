"""Four chips SERVED (ISSUE 45), beside ``test_four_chips.py``: the real
``ShardedVerifyEngine`` over four virtual devices behind the program's own
daemon, handler, queue and cut, reached over sockets. Several connections'
requests are merged into ONE window, which the engine stages from the blocks
they came off the wire as (one slice assignment a request, two for one that
straddles a chunk's edge) and shards over the mesh; every verdict is held to
the RFC 8032 reference, item by item.

The arithmetic inside the executables is the native host verifier's
(``_f5_x4_rehearse.host_arithmetic``), so real signatures get their real
verdicts without the minutes the kernel takes to compile for a CPU; the real
kernel on the same windows is ``test_parallel.py``'s (slow tier).
"""

from __future__ import annotations

import functools
import json
import random
import sys
import time
from pathlib import Path

import pytest

from pbft_tpu.crypto import ref
from pbft_tpu.net import ShardedVerifyEngine, VerifyServiceDaemon

from _f5_x4_rehearse import host_arithmetic
from test_service_coalesce import _Conn

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))
try:
    import chip_smoke
finally:
    sys.path.pop(0)

# Injected costs (a CPU's own readings decide nothing here): a 64-slot
# program at four times the 16- and 32-slot ones, so 33-48 items run as
# 32 + 16 slots and 49-64 as 32 + 32, on 4 and 8 rows a chip.
COSTS = {16: 0.001, 32: 0.001, 64: 0.004}
CLASSES = tuple(chip_smoke.planted(random.Random(0), chip_smoke.signed_pool(random.Random(0), 1)[0]))
FLIPPED, BIG_S, WRONG, CONTROL = "flipped signature byte", "S >= L", "wrong message", CLASSES[-1]
oracle = functools.lru_cache(maxsize=None)(ref.verify)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    trace = tmp_path_factory.mktemp("x4") / "verifyd.jsonl"
    engine = ShardedVerifyEngine(shapes=tuple(COSTS), devices=4, kernel=host_arithmetic())
    daemon = VerifyServiceDaemon(
        backend="auto", engine=engine, trace_path=str(trace),
        fallback=lambda items: pytest.fail("the fallback ran"),
    ).start(wait_ready=True, timeout=300)
    try:
        assert daemon.state_name == "ready" and engine.device_count == 4
        assert engine._route(COSTS)["chunk_plan"] == {"33-48": "32+16", "49-64": "32+32"}
        yield daemon, trace
    finally:
        daemon.stop()


def _one_window(served, requests):
    """Send ``requests`` (lists of items), each over a connection of its own,
    so that they are cut as ONE window, in this order: both launch slots are
    held while they queue behind an opener (a window is cut before it has a
    slot and cannot grow while it waits for one). Returns each request's
    verdicts and the merged window's launch line."""
    daemon, trace = served
    svc = daemon.service
    before = len(trace.read_text().splitlines())
    conns = [_Conn(svc.address) for _ in range(len(requests) + 1)]
    opener = chip_smoke.signed_pool(random.Random(45), 1)
    results, threads, slots = {}, [], 0
    deadline = time.monotonic() + 60
    try:
        for _ in range(2):
            assert svc._inflight_sem.acquire(timeout=30)
            slots += 1
        for k, items in enumerate([opener, *requests]):
            asked = svc.requests
            threads.append(conns[k].send_later(items, results, k))
            # queued; and the opener CUT (alone: its hold may still be running)
            while svc.requests == asked or svc._flying == 0:
                assert time.monotonic() < deadline
                time.sleep(0.002)
    finally:
        for _ in range(slots):
            svc._inflight_sem.release()
    try:
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
    finally:
        for c in conns:
            c.close()
    assert results[0] == [True]
    # the two launches run side by side: whichever ends first writes first
    lines = sorted(map(json.loads, trace.read_text().splitlines()[before:]), key=lambda e: e["size"])
    assert [(e["size"], e["requests"]) for e in lines] == [
        (1, 1), (sum(map(len, requests)), len(requests))
    ]
    return [results[k + 1] for k in range(len(requests))], lines[1]


def _requests(seed, sizes, planted):
    """Valid signed items cut into requests of ``sizes``, with ``planted``
    ({position in the window: class name}) in their places."""
    rng = random.Random(seed)
    pool = chip_smoke.signed_pool(rng, sum(sizes))
    plants = chip_smoke.planted(rng, pool[0])
    items = [plants[planted[i]][0] if i in planted else item for i, item in enumerate(pool)]
    cuts = [sum(sizes[:k]) for k in range(len(sizes) + 1)]
    return [items[a:b] for a, b in zip(cuts, cuts[1:])]


# first, last, and both sides of both inner edges of requests of 6, 20 and 7
# items: 33 items, so the second request also straddles the 32-slot chunk's edge
EDGES = (0, 5, 6, 25, 26, 32)

CASES = [
    # (a) the second request straddles the first chunk's edge, a reject on each side of both edges
    ("straddle", [20, 20, 5], (32, 16), dict.fromkeys((19, 20, 31, 32), FLIPPED)),
    # (b) a request fills a shape exactly: alone, as two halves, and as the first of two chunks
    ("fills-32", [32], (32,), {0: BIG_S, 31: WRONG}),
    ("two-fill-32", [16, 16], (32,), {15: BIG_S, 16: WRONG}),
    ("fills-32-then-16", [32, 16], (32, 16), {31: BIG_S, 32: WRONG, 47: FLIPPED}),
    ("three-fill-32-32", [32, 17, 15], (32, 32), {31: BIG_S, 32: WRONG, 48: BIG_S, 63: WRONG}),
    # (c) every class the probe plants, first, last and at every request's edge
    *[(f"class-{k}", [6, 20, 7], (32, 16), dict.fromkeys(EDGES, name)) for k, name in enumerate(CLASSES)],
]


def test_the_probe_s_classes_are_the_seven_rejects_and_the_control():
    assert len(CLASSES) == chip_smoke.N_CLASSES == 8
    assert {FLIPPED, BIG_S, WRONG} <= set(CLASSES[:7]) and "control" in CONTROL


@pytest.mark.parametrize("sizes, plan, planted", [c[1:] for c in CASES], ids=[c[0] for c in CASES])
def test_a_merged_window_of_blocks_gets_the_reference_s_verdicts_on_four_chips(served, sizes, plan, planted):
    requests = _requests(4500000000 + sum(sizes) + len(planted), sizes, planted)
    want = [[oracle(*item) for item in items] for items in requests]
    flat = [v for part in want for v in part]
    control = [i for i, name in planted.items() if name == CONTROL]
    assert [flat[i] for i in sorted(planted)] == [i in control for i in sorted(planted)]
    assert sum(flat) == len(flat) - len(planted) + len(control)  # everything else is sound
    got, line = _one_window(served, requests)
    assert got == want
    n = sum(sizes)
    assert (line["size"], line["block_items"], line["rejected"]) == (n, n, n - sum(flat))
    assert (line["chunks"], line["rung"], line["split"]) == (len(plan), sum(plan), int(len(plan) > 1))
    assert (line["devices"], line["rows_per_chip"]) == (4, min(plan) // 4)


def test_nothing_served_went_to_a_list(served):
    """After the cases above (this file's order): every item the daemon served
    reached its executables as block rows, none through the fallback."""
    daemon, _trace = served
    status = daemon.status_json()
    assert status["fallback_items"] == 0 and status["listed_items"] == 0
    assert status["block_items"] == status["engine_items"] > 0

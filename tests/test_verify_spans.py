"""One verify trip, stage by stage (ISSUE 26): the spans the verify service
writes on every ``verify_batch`` line (dispatcher: ``queue_s`` / ``slot_s`` /
``pending_at_*``; engine: the five steps, ``rung``, ``t_dev``), the status
fields that show a stall without ``--trace``, the replica's two histograms
on the async (RemoteVerifier) branch, and the benchmark's readers of all of
them (``chipbench/reducers/launch_field_stat.py``,
``launch_union_idle_pct.py`` and the sixteen ``chipbench/metrics`` files)."""

import hashlib
import importlib.util
import json
import statistics
import sys
import threading
import time
import urllib.request
from collections import Counter
from pathlib import Path

import pytest

from pbft_tpu.net import ShardedVerifyEngine, VerifierService, VerifyServiceDaemon
from pbft_tpu.net.verify_service import (
    MAX_CHUNKS,
    PROMOTE_MARGIN,
    SPLIT_MARGIN,
    chunk_plan,
    chunk_plan_words,
    plan_table,
    serving_table,
)
from pbft_tpu.utils import trace_schema
from pbft_tpu.utils.trace import current_span, open_span

from tests.test_service_coalesce import _item, _send_batch

ROOT = Path(__file__).resolve().parent.parent
CHIPBENCH = ROOT / "chipbench"
STEPS = ShardedVerifyEngine.STEPS


def _lines(path):
    events = [json.loads(line) for line in Path(path).read_text().splitlines()]
    return [e for e in events if e["ev"] == "verify_batch"]


# -- (a) the dispatcher's waits ------------------------------------------------


def test_dispatcher_lines_carry_queue_and_slot_waits(tmp_path):
    """Three back-to-back windows on two launch slots: the third is cut at
    once and then waits for a slot (``slot_s``), and what arrives meanwhile
    (``pending_at_launch``) becomes the fourth, whose ``queue_s`` is its
    OLDEST request's wait."""
    gate = threading.Event()

    def backend(items):
        assert gate.wait(20)
        return [p[0] == s[0] for p, m, s in items]

    trace = tmp_path / "service.jsonl"
    svc = VerifierService(backend=backend, inflight=2, trace_path=str(trace)).start()
    threads, sent_at = [], {}

    def send(tag, after_requests):
        deadline = time.monotonic() + 10
        while svc.requests < after_requests:  # the one before it has queued
            assert time.monotonic() < deadline
            time.sleep(0.005)
        sent_at[tag] = time.monotonic()
        t = threading.Thread(target=_send_batch, args=(svc.address, [_item(tag, True)] * tag))
        t.start()
        threads.append(t)

    try:
        for n, tag in enumerate((1, 2, 3)):  # windows 1 and 2 hold the slots, 3 waits
            send(tag, n)
            time.sleep(0.05)
        send(4, 3)
        time.sleep(0.15)
        send(5, 4)  # 4 and 5 queue behind the window that waits for a slot
        deadline = time.monotonic() + 10
        while svc.requests < 5:
            assert time.monotonic() < deadline
            time.sleep(0.005)
        released = time.monotonic()
        gate.set()
        for t in threads:
            t.join(timeout=20)
            assert not t.is_alive()
    finally:
        gate.set()
        svc.stop()
    lines = sorted(_lines(trace), key=lambda e: e["size"])
    assert [e["size"] for e in lines] == [1, 2, 3, 9]
    for e in lines:
        assert {"queue_s", "slot_s", "pending_at_cut", "pending_at_launch"} <= set(e)
        assert e["queue_s"] >= 0 and e["slot_s"] >= 0 and e["pending_at_cut"] == 0
    first, second, third, fourth = lines
    assert first["slot_s"] < 0.05 and second["slot_s"] < 0.05  # a slot was free
    # The third window was cut when its request arrived and got a slot only
    # when the gate opened; by then requests 4 and 5 (9 items) had queued.
    assert third["slot_s"] >= released - sent_at[3] - 0.05 > 0.1
    assert third["queue_s"] < 0.05
    assert third["pending_at_launch"] == 9
    # The fourth window holds requests 4 and 5, cut once the dispatcher was
    # back: its wait is request 4's (the oldest), not request 5's.
    assert fourth["requests"] == 2
    cut_by = fourth["ts"] - fourth["secs"]
    assert sent_at[5] - sent_at[4] <= fourth["queue_s"] <= cut_by - sent_at[4] + 0.05
    status = svc.launch_status()
    assert status["stage_seconds"]["slot_s"] >= third["slot_s"]
    assert status["stage_seconds"]["queue_s"] >= fourth["queue_s"]


# -- (b) the engine's five steps, two launches in flight ----------------------


def test_span_records_are_per_thread():
    barrier = threading.Barrier(2, timeout=10)
    seen = {}

    def work(name):
        assert current_span() is None
        with open_span() as span:
            barrier.wait()  # both spans are open now
            current_span()["who"] = name
            barrier.wait()  # both have written
            seen[name] = dict(span)
        assert current_span() is None

    threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    assert seen == {"a": {"who": "a"}, "b": {"who": "b"}}


def _slow_kernel(pubs, msgs, sigs):
    """valid iff sig[0] == pub[0], after some milliseconds of device work,
    so that two launches are really in the engine at once."""
    import jax
    import jax.numpy as jnp

    spin = jax.lax.fori_loop(
        0, 400_000, lambda i, acc: acc + (i & 1), pubs[0, 0].astype(jnp.int32)
    )
    return (pubs[:, 0] == sigs[:, 0]) & (spin >= 0)


class _Seen:
    """An executable of the engine, noting what it is called on."""

    def __init__(self, compiled):
        self.compiled, self.input_shardings, self.args = compiled, compiled.input_shardings, []
        self.as_text = compiled.as_text

    def __call__(self, *args):
        self.args.append(args)
        return self.compiled(*args)


def _rung_of(size, shapes):
    top = max(shapes)
    chunks = [min(top, size - off) for off in range(0, size, top)]
    return sum(min(s for s in shapes if s >= n) for n in chunks)


def test_engine_steps_sum_to_secs_with_two_launches_in_flight(tmp_path, monkeypatch):
    import jax

    shapes = (8, 16)
    engine = ShardedVerifyEngine(shapes=shapes, kernel=_slow_kernel)
    # One transfer a chunk, the executable's own: it is handed the host
    # block, and nothing in the process calls ``device_put``.
    monkeypatch.setattr(jax, "device_put", lambda *a, **kw: pytest.fail("a device_put"))
    trace = tmp_path / "verifyd.jsonl"
    daemon = VerifyServiceDaemon(
        backend="auto", engine=engine, trace_path=str(trace),
        fallback=lambda items: pytest.fail("the fallback ran"),
    ).start(wait_ready=True, timeout=300)
    assert daemon.state_name == "ready"
    # Equal costs, as the kernel's are, whatever this host's clock read.
    assert engine._route(dict.fromkeys(shapes, 0.001))["chunk_plan"] == {}
    engine._compiled = {size: _Seen(c) for size, c in engine._compiled.items()}  # from here on: served chunks
    errors = []

    def client(n_items, rounds):
        items = [_item(n_items, i % 2 == 0) for i in range(n_items)]
        try:
            for _ in range(rounds):
                assert _send_batch(daemon.address, items) == [i % 2 == 0 for i in range(n_items)]
        except Exception as e:  # noqa: BLE001 - reported below
            errors.append(e)

    try:
        # Sizes whose rungs differ (5 -> 8, 12 -> 16, merged 17 -> 16 + 8):
        # a record read from the other thread would carry the wrong rung.
        threads = [threading.Thread(target=client, args=(n, 8)) for n in (5, 12, 5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        crowded = len(_lines(trace))
        client(7, 8)  # and one caller alone: nothing contends for the interpreter
        whole = len(_lines(trace))
        # The same again on a device whose 16-slot program costs four times
        # the 8-slot one (injected: a CPU's own readings decide nothing
        # here): 12 items run as 8 + 8 slots, both chunks dispatched before
        # the first is read back, with two launches in flight.
        assert engine._route({8: 0.001, 16: 0.004})["chunk_plan"] == {"9-16": "8+8"}
        threads = [threading.Thread(target=client, args=(n, 8)) for n in (5, 12, 5)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
            assert not t.is_alive()
        status = daemon.status_json()
    finally:
        daemon.stop()
    assert not errors, errors
    lines, chunked = _lines(trace)[:whole], _lines(trace)[whole:]
    calls = [args for size in shapes for args in engine._compiled[size].args]
    assert len(calls) == sum(e["chunks"] for e in lines + chunked)
    assert {(type(b).__name__, b.shape, str(b.dtype)) for (b,) in calls} == {
        ("ndarray", (8, 128), "uint8"), ("ndarray", (16, 128), "uint8")
    }
    assert all(e["put_s"] == 0 for e in lines + chunked)  # nothing to time: the transfer is the call's
    keys = trace_schema.EVENT_SCHEMAS["verify_batch"]
    assert all(keys["required"] <= set(e) <= keys["required"] | keys["optional"] for e in lines + chunked)
    assert sum(e["size"] for e in lines) == 8 * (5 + 12 + 5 + 7)
    assert sum(e["size"] for e in chunked) == 8 * (5 + 12 + 5)
    for e in chunked:
        # 1-8 items: 8 slots; 9-16: 8 + 8; 17-22 (merged): 16 + 8 as before.
        assert (e["chunks"], e["rung"]) == ((1, 8) if e["size"] <= 8 else (2, 16) if e["size"] <= 16 else (2, 24))
        assert e["split"] == (e["size"] > 8) and e["promoted"] == 0
        # every plan here has an 8-slot chunk, an eighth of it a virtual device
        assert (e["devices"], e["rows_per_chip"]) == (engine.device_count, 8 // engine.device_count)
        assert sum(e[k] for k in STEPS) <= e["secs"] + 1e-5
        assert e["ts"] - e["secs"] - 1e-3 <= e["t_dev"] <= e["ts"]
    split = [e for e in chunked if e["split"]]
    assert len(split) >= 8  # every 12-item request, alone or merged
    gaps = [e["secs"] - sum(e[k] for k in STEPS) for e in split]
    assert statistics.median(gaps) < max(0.0005, 0.05 * statistics.median(e["secs"] for e in split))
    spans = sorted((e["ts"] - e["secs"], e["ts"]) for e in lines)
    assert any(b_start < a_end for (_, a_end), (b_start, _) in zip(spans, spans[1:])), (
        "no two launches overlapped: the test did not exercise the daemon's two launch slots"
    )
    for e in lines:
        assert set(STEPS) | {"rung", "promoted", "chunks", "split", "t_dev", "queue_s", "slot_s"} <= set(e)
        assert e["rung"] == _rung_of(e["size"], shapes)
        assert e["promoted"] == 0  # a flat-cost kernel: the tie keeps smallest-fit
        assert (e["chunks"], e["split"]) == ((2, 1) if e["size"] > 16 else (1, 0))
        # the thinnest executable the window ran: 16 slots alone, else 8
        thinnest = 16 if 8 < e["size"] <= 16 else 8
        assert (e["devices"], e["rows_per_chip"]) == (engine.device_count, thinnest // engine.device_count)
        assert e["ts"] - e["secs"] - 1e-3 <= e["t_dev"] <= e["ts"]
        # The steps lie inside the interval `secs` times, one after another,
        # so they never add up to more than it.
        assert sum(e[k] for k in STEPS) <= e["secs"] + 1e-5
    # What they leave out is a few bytecodes: within 2% or 0.5 ms of `secs`
    # (read where one caller was alone: among many threads the interpreter
    # lock changes hands just there, between many short calls; the median,
    # so that one descheduled thread cannot fail it).
    alone = lines[crowded:]
    assert len(alone) == 8
    gaps = [e["secs"] - sum(e[k] for k in STEPS) for e in alone]
    assert statistics.median(gaps) < max(0.0005, 0.02 * statistics.median(e["secs"] for e in alone))
    # The status JSON: totals of the seven stages, the slowest launch with
    # the step that held it, the device's peak memory (None on a backend
    # that reports no memory statistics, as the CPU's).
    totals = status["stage_seconds"]
    assert set(totals) == {"queue_s", "slot_s", *STEPS}
    for k in STEPS:
        assert totals[k] == pytest.approx(sum(e[k] for e in lines + chunked), abs=1e-4)
    slowest = status["slowest_launch"]
    assert set(slowest) == {"secs", "size", "rung", "stage", "ago_s"}
    assert slowest["secs"] == pytest.approx(max(e["secs"] for e in lines + chunked), abs=1e-3)
    assert slowest["stage"] in STEPS and slowest["ago_s"] >= 0
    assert "memory_peak_bytes" in status
    assert status["memory_peak_bytes"] is None or status["memory_peak_bytes"] >= 0
    assert status["promoted_launches"] == 0
    assert status["split_launches"] == sum(e["split"] for e in lines + chunked)
    by_rows = Counter(str(e["rows_per_chip"]) for e in lines + chunked)
    assert status["launches_by_rows_per_chip"] == by_rows and set(by_rows) <= {"1", "2"}
    assert sum(by_rows.values()) == status["engine_launches"]
    assert status["warm_stats"]["serving_table"] == {"8": 8, "16": 16}


def test_a_one_device_engine_says_so_on_every_launch_line(tmp_path, capsys):
    """``devices`` is what the executables' input sharding spans, kept at
    warm-up, and ``rows_per_chip`` the slots of the window's smallest chunk
    over it: on one device 1, and the chunk's own slots. The status JSON
    counts launches by it, and the two scripts print both."""
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import trace_report
        import verify_status
    finally:
        sys.path.pop(0)
    shapes = (8, 16)
    engine = ShardedVerifyEngine(shapes=shapes, devices=1, kernel=lambda p, m, s: p[:, 0] == s[:, 0])
    trace = tmp_path / "verifyd.jsonl"
    daemon = VerifyServiceDaemon(
        backend="auto", engine=engine, trace_path=str(trace),
        fallback=lambda items: pytest.fail("the fallback ran"),
    ).start(wait_ready=True, timeout=300)
    try:
        assert daemon.state_name == "ready" and engine.device_count == 1
        assert [p["devices"] for p in engine.stats["per_shape"]] == [[0], [0]]
        engine._route({8: 0.001, 16: 0.004})  # 9-16 items run as 8 + 8 slots
        for n in (3, 8, 12, 16 + 2):
            assert _send_batch(daemon.address, [_item(n, True)] * n) == [True] * n
        engine._route(dict.fromkeys(shapes, 0.001))  # and as one shape
        assert _send_batch(daemon.address, [_item(12, True)] * 12) == [True] * 12
        status = daemon.status_json()
        capsys.readouterr()
        assert verify_status.main([daemon.address]) == 0
    finally:
        daemon.stop()
    lines = _lines(trace)
    assert [(e["size"], e["rung"], e["devices"], e["rows_per_chip"]) for e in lines] == [
        (3, 8, 1, 8), (8, 8, 1, 8), (12, 16, 1, 8), (18, 24, 1, 8), (12, 16, 1, 16),
    ]
    assert status["launches_by_rows_per_chip"] == {"8": 4, "16": 1}
    assert sum(status["launches_by_rows_per_chip"].values()) == status["engine_launches"]
    assert set(status) <= trace_schema.VERIFYD_STATUS_KEYS
    assert {"devices", "rows_per_chip"} <= trace_schema.EVENT_SCHEMAS["verify_batch"]["optional"]
    out = capsys.readouterr().out
    assert "rows a chip     8: 4  16: 1  (the thinnest chunk of each launch, over 1 chip(s))" in out
    assert "launches_by_rows_per_chip" not in out  # printed once, as that line
    trace_report.report([trace])
    assert "5 launches sharded over 1 chip(s); rows a chip of the thinnest chunk: 8: 4  16: 1" in (
        capsys.readouterr().out
    )


def test_every_launch_line_says_what_share_of_its_slots_ran_the_vmem_chains(tmp_path, capsys):
    """``fused`` is the share of the window's slots (``rung``) that ran on
    executables whose multiply chains live in VMEM: what each executable's
    own HLO says (``parallel.chains_of``: a Mosaic call or none), kept at
    warm-up as ``per_shape[].chains``. A stand-in kernel has no Mosaic call
    (``xla``); on the CPU the real kernel has none either."""
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import verify_status
    finally:
        sys.path.pop(0)
    shapes = (8, 16)
    engine = ShardedVerifyEngine(shapes=shapes, devices=1, kernel=lambda p, m, s: p[:, 0] == s[:, 0])
    trace = tmp_path / "verifyd.jsonl"
    daemon = VerifyServiceDaemon(
        backend="auto", engine=engine, trace_path=str(trace),
        fallback=lambda items: pytest.fail("the fallback ran"),
    ).start(wait_ready=True, timeout=300)
    try:
        assert [p["chains"] for p in engine.stats["per_shape"]] == ["xla", "xla"]
        assert _send_batch(daemon.address, [_item(3, True)] * 3) == [True] * 3
        # As on a TPU whose 16-slot shape fills a tile and whose 8-slot one does not.
        engine._chains[16] = "vmem"
        engine.stats["per_shape"][1]["chains"] = "vmem"
        for n in (3, 12, 16 + 2):  # 8 slots; 16; 16 + 8
            assert _send_batch(daemon.address, [_item(n, True)] * n) == [True] * n
        status = daemon.status_json()
        capsys.readouterr()
        assert verify_status.main([daemon.address]) == 0
    finally:
        daemon.stop()
    lines = _lines(trace)
    assert [(e["rung"], e["fused"]) for e in lines] == [(8, 0.0), (8, 0.0), (16, 1.0), (24, 0.6667)]
    assert status["fused_launches"] == 2 and status["engine_launches"] == 4
    assert set(status) <= trace_schema.VERIFYD_STATUS_KEYS
    assert "fused" in trace_schema.EVENT_SCHEMAS["verify_batch"]["optional"]
    assert "fused_launches" in trace_schema.VERIFYD_STATUS_KEYS
    assert "chains" in trace_schema.VERIFYD_PER_SHAPE_KEYS
    out = capsys.readouterr().out
    assert "multiply chains 8: xla  16: vmem  (2 launches with slots on the VMEM chains)" in out
    assert "fused_launches" not in out  # printed once, as that line


def test_the_real_kernel_s_shapes_say_xla_on_a_cpu():
    from pbft_tpu.crypto.ed25519 import chains_for

    assert [chains_for(rows) for rows in (16, 256, 1024, 4096)] == ["xla"] * 4


def test_chains_are_read_from_the_program_not_from_the_rule(monkeypatch):
    """``chains_of`` looks for the Mosaic call in the executable's text, and
    in the lowered module's where an executable hands none back; the engine
    records THAT, so a rule that says ``vmem`` of a program compiled without
    the kernels does not reach ``per_shape[].chains`` or ``fused``."""
    from pbft_tpu.crypto import ed25519
    from pbft_tpu.parallel import chains_of

    class Text:
        def __init__(self, text):
            self.as_text = lambda: text

    assert chains_of(Text("... custom_call_target=\"tpu_custom_call\" ...")) == "vmem"
    assert chains_of(Text("fusion.1 { convolution }")) == "xla"
    assert chains_of(Text(None), Text("stablehlo.custom_call @tpu_custom_call(")) == "vmem"
    assert chains_of(Text(None), Text("stablehlo.convolution")) == "xla"
    with pytest.raises(RuntimeError, match="gives text"):
        chains_of(Text(None))
    engine = ShardedVerifyEngine(shapes=(8,), devices=1, kernel=lambda p, m, s: p[:, 0] == s[:, 0])
    monkeypatch.setattr(ed25519, "chains_for", lambda rows, backend=None: "vmem")
    assert [p["chains"] for p in engine.warm()["per_shape"]] == ["xla"]


# -- (b') which executable serves a window: the table, and an engine that promotes


# What warm-up read on the TPU v5e (PR 27's status JSON; 7.1 / 15.8 / 53.3 ms at
# 256 / 1,024 / 4,096 in PR 28's): one launch through the host, not the
# device time alone (5.31 / 13.47 / 50.69 ms, PERF.md section 5).
V5E_LAUNCH_S = {16: 0.04460, 64: 0.04425, 256: 0.00727, 1024: 0.01553, 4096: 0.05314}
IDENTITY = {s: s for s in V5E_LAUNCH_S}


@pytest.mark.parametrize(
    "launch_s, want",
    [
        (V5E_LAUNCH_S, {16: 256, 64: 256, 256: 256, 1024: 1024, 4096: 4096}),
        # Cost grows with size (a CPU): smallest-fit, whatever the steps.
        ({16: 0.001, 64: 0.004, 256: 0.016, 1024: 0.064, 4096: 0.256}, IDENTITY),
        ({16: 0.0050, 64: 0.0051, 256: 0.0052, 1024: 0.0053, 4096: 0.0054}, IDENTITY),
        # Equal costs, and a larger shape that is cheaper but inside the
        # margin, keep the smaller shape.
        (dict.fromkeys(V5E_LAUNCH_S, 0.005), IDENTITY),
        ({16: 0.010, 64: 0.010, 256: 0.010 / PROMOTE_MARGIN, 1024: 0.02, 4096: 0.06}, IDENTITY),
        ({16: 0.010, 64: 0.010, 256: 0.006, 1024: 0.02, 4096: 0.06}, IDENTITY),
        # Just past the margin.
        ({16: 0.010, 64: 0.010, 256: 0.010 / PROMOTE_MARGIN - 1e-4, 1024: 0.02, 4096: 0.06},
         {16: 256, 64: 256, 256: 256, 1024: 1024, 4096: 4096}),
        # One cheap shape in the middle serves everything under it, nothing above.
        ({16: 0.03, 64: 0.03, 256: 0.03, 1024: 0.004, 4096: 0.06},
         {16: 1024, 64: 1024, 256: 1024, 1024: 1024, 4096: 4096}),
        # A cheap top shape takes all; a cheap shape above a dear one does not
        # reach past it (each shape looks at what serves the NEXT larger one).
        ({8: 0.02, 16: 0.001}, {8: 16, 16: 16}),
        ({8: 0.02, 16: 0.02, 32: 0.001}, {8: 32, 16: 32, 32: 32}),
        ({8: 0.02, 16: 0.002, 32: 0.001}, {8: 16, 16: 16, 32: 32}),
        ({}, {}),
    ],
)
def test_serving_table(launch_s, want):
    assert serving_table(launch_s) == want
    assert list(serving_table(launch_s)) == sorted(launch_s)


# -- (b'') a window as chunks of the cheaper shapes (ISSUE 29) --------------------

FLAT = dict.fromkeys(V5E_LAUNCH_S, 0.005)
LINEAR = {s: s * 1e-5 for s in V5E_LAUNCH_S}
CHEAP_TOP = {16: 0.03, 64: 0.03, 256: 0.03, 1024: 0.03, 4096: 0.004}
# Two 8-slot launches against one of 16 slots, at the margin's two sides.
JUST_INSIDE = {8: 0.010, 16: 2 * 0.010 * SPLIT_MARGIN}
JUST_PAST = {8: 0.010, 16: 2 * 0.010 * SPLIT_MARGIN + 1e-4}


@pytest.mark.parametrize(
    "launch_s, n, want",
    [
        # The TPU v5e: the 4,096-slot program costs 3.4 times the 1,024-slot
        # one, so a window just above 1,024 items runs on the smaller shapes
        (V5E_LAUNCH_S, 1025, (1024, 256)),
        (V5E_LAUNCH_S, 1100, (1024, 256)),
        (V5E_LAUNCH_S, 1280, (1024, 256)),
        (V5E_LAUNCH_S, 1281, (1024, 256, 256)),  # 30.1 ms, under two 1,024-slot launches' 31.1
        (V5E_LAUNCH_S, 1536, (1024, 256, 256)),
        (V5E_LAUNCH_S, 1537, (1024, 1024)),
        (V5E_LAUNCH_S, 2048, (1024, 1024)),
        (V5E_LAUNCH_S, 2049, (1024, 1024, 256)),
        (V5E_LAUNCH_S, 2304, (1024, 1024, 256)),
        # ... and stays whole where the chunks come to 1.14x (3 x 1,024) and
        # 1.07x (2 x 256 against 1,024), inside the margin.
        (V5E_LAUNCH_S, 2305, (4096,)),
        (V5E_LAUNCH_S, 3072, (4096,)),
        (V5E_LAUNCH_S, 4095, (4096,)),
        (V5E_LAUNCH_S, 257, (1024,)),
        (V5E_LAUNCH_S, 512, (1024,)),
        # n = 0, 1, an exact shape: one shape, by the serving table.
        (V5E_LAUNCH_S, 0, ()),
        (V5E_LAUNCH_S, 1, (256,)),
        (V5E_LAUNCH_S, 16, (256,)),
        (V5E_LAUNCH_S, 256, (256,)),
        (V5E_LAUNCH_S, 1024, (1024,)),
        (V5E_LAUNCH_S, 4096, (4096,)),
        # A chunk goes where the serving table sends it: the 6 items left
        # over run at 256 slots, never at 16 or 64.
        (V5E_LAUNCH_S, 1030, (1024, 256)),
        # Beyond the largest shape: chunks of it, and the rest by the rule.
        (V5E_LAUNCH_S, 4097, (4096, 256)),
        (V5E_LAUNCH_S, 5000, (4096, 1024)),
        (V5E_LAUNCH_S, 4096 + 1100, (4096, 1024, 256)),
        (V5E_LAUNCH_S, 2 * 4096, (4096, 4096)),
        (V5E_LAUNCH_S, 2 * 4096 + 1, (4096, 4096, 256)),
        # The margin's two sides.
        (JUST_INSIDE, 9, (16,)),
        (JUST_PAST, 9, (8, 8)),
        (JUST_PAST, 8, (8,)),
        (JUST_PAST, 17, (16, 8)),
        # Flat cost: the identity, at every size.
        (FLAT, 17, (64,)),
        (FLAT, 300, (1024,)),
        (FLAT, 1100, (4096,)),
        (FLAT, 4097, (4096, 16)),
        # Cost linear in the slots (pad slots are real work, as on a CPU):
        # the least slots that cover, in at most four chunks.
        (LINEAR, 300, (256, 16, 16, 16)),  # 304 slots, not 256 + 64 = 320
        (LINEAR, 320, (256, 64)),
        (LINEAR, 1100, (1024, 64, 16)),
        (LINEAR, 1030, (1024, 16)),
        (LINEAR, 2000, (1024, 1024)),  # 2,048 slots: 1,024 + 3 x 256 = 1,792 do not cover
        (LINEAR, 1000, (1024,)),  # 4 x 256 cover it for the same cost: one launch
        (LINEAR, 900, (1024,)),  # 3 x 256 + 2 x 64 + 16 would be six
        (LINEAR, 50, (64,)),  # 16 x 4 = 64 slots for the same cost: one launch
        # A cheap top shape serves everything and never splits.
        (CHEAP_TOP, 1, (4096,)),
        (CHEAP_TOP, 1100, (4096,)),
        (CHEAP_TOP, 4097, (4096, 4096)),
        ({}, 5, ()),
    ],
)
def test_chunk_plan(launch_s, n, want):
    serves = serving_table(launch_s)
    got = chunk_plan(n, launch_s, serves)
    assert got == want
    assert sum(got) >= (n if launch_s else 0) and list(got) == sorted(got, reverse=True)
    assert all(serves[s] == s for s in got)  # every chunk is where the table sends it
    if launch_s and 0 < n <= max(launch_s):
        assert len(got) <= MAX_CHUNKS
        # The table the engine looks a window up in says the same.
        hi, plan = next(row for row in plan_table(launch_s, serves) if row[0] >= n)
        assert plan == got


def test_chunk_plan_in_words():
    def words(launch_s):
        return chunk_plan_words(plan_table(launch_s, serving_table(launch_s)))

    serves = serving_table(V5E_LAUNCH_S)
    assert words(V5E_LAUNCH_S) == {
        "1025-1280": "1024+256", "1281-1536": "1024+256+256",
        "1537-2048": "1024+1024", "2049-2304": "1024+1024+256",
    }
    assert [hi for hi, _ in plan_table(V5E_LAUNCH_S, serves)] == [256, 1024, 1280, 1536, 2048, 2304, 4096]
    assert words(FLAT) == words(CHEAP_TOP) == words(JUST_INSIDE) == words({}) == {}
    assert words(JUST_PAST) == {"9-16": "8+8"}


@pytest.mark.parametrize(
    "n,want",
    [
        (1, 0.00727),  # fits 16, runs at 256: room for 255 more
        (50, 0.00727),
        (255, 0.00727),
        (256, 0.0),  # fills the shape it runs at: goes at once
        (257, 0.01553),
        (1024, 0.0),
        (1025, 0.00727),  # 1,024 + 256 slots: the 256-slot chunk has the room
        (1280, 0.0),  # fills its plan
        (1300, 0.00727),  # 1,024 + 256 + 256
        (1537, 0.01553),  # 1,024 + 1,024
        (2048, 0.0),
        (2305, 0.05314),  # one 4,096-slot launch again
        (4095, 0.05314),
        (4096, 0.0),
        (5000, 0.01553),  # oversized: 4,096 + 1,024 slots, 120 of them free
        (2 * 4096, 0.0),
    ],
)
def test_hold_is_one_launch_of_the_shape_run_while_it_has_room(n, want):
    engine = ShardedVerifyEngine(shapes=tuple(V5E_LAUNCH_S))
    assert engine.hold_s(n) == 0.0  # before warm-up: nothing timed, nothing held
    engine._route(V5E_LAUNCH_S)
    assert engine.hold_s(n) == want


def _slow_below_32(pubs, msgs, sigs):
    """``_slow_kernel``'s rule on a device whose small programs are the slow
    ones: twenty times the spin where the window has fewer than 32 slots
    (the kernel is traced for one device's rows, under ``shard_map``: the
    window's slots are those times the mesh).
    (The shape is static under jit: each executable is slow or fast outright.
    The fast ones spin too, for a millisecond: the timer tells two launches
    of microseconds apart by whatever else the host was doing.)"""
    import jax
    import jax.numpy as jnp

    spin = jax.lax.fori_loop(
        0,
        2_000_000 if pubs.shape[0] * jax.lax.axis_size("batch") < 32 else 100_000,
        lambda i, acc: acc + (i & 1),
        pubs[0, 0].astype(jnp.int32),
    )
    return (pubs[:, 0] == sigs[:, 0]) & (spin >= 0)


def test_engine_serves_small_windows_on_the_cheaper_larger_shape(tmp_path):
    shapes = (8, 16, 32, 64)
    engine = ShardedVerifyEngine(shapes=shapes, kernel=_slow_below_32)
    plain = ShardedVerifyEngine(shapes=shapes, kernel=lambda p, m, s: p[:, 0] == s[:, 0])
    plain.warm()
    plain._route(dict.fromkeys(shapes, 0.001))  # smallest-fit, whatever its microsecond launches read
    trace = tmp_path / "verifyd.jsonl"
    daemon = VerifyServiceDaemon(
        backend="auto", engine=engine, trace_path=str(trace),
        fallback=lambda items: pytest.fail("the fallback ran"),
    ).start(wait_ready=True, timeout=300)
    sizes = (5, 8, 12, 16, 20, 32, 33, 64, 64 + 5, 64 + 40)
    try:
        assert daemon.state_name == "ready"
        assert engine.warmed_sizes == shapes  # every shape asked for stays compiled
        costs = {p["size"]: p["launch_s"] for p in engine.stats["per_shape"]}
        assert set(costs) == set(shapes) and all(c > 0 for c in costs.values())
        assert min(costs[8], costs[16]) > PROMOTE_MARGIN * max(costs[32], costs[64]), costs
        assert engine.stats["serving_table"] == {"8": 32, "16": 32, "32": 32, "64": 64}
        assert daemon.service.hold_s == engine.hold_s  # set once warm-up has timed the shapes
        assert engine.hold_s(5) == costs[32] and engine.hold_s(32) == 0.0
        for n in sizes:  # accepted and rejected items, alone on the wire
            items = [_item(n + i, i % 3 != 0) for i in range(n)]
            got = _send_batch(daemon.address, items)
            assert got == [i % 3 != 0 for i in range(n)] == plain.verify(items)
        status = daemon.status_json()
    finally:
        daemon.stop()
    lines = _lines(trace)
    assert [e["size"] for e in lines] == list(sizes)
    #          5   8   12  16  20  32  33  64  64+5     64+40
    assert [e["rung"] for e in lines] == [32, 32, 32, 32, 32, 32, 64, 64, 64 + 32, 64 + 64]
    assert [e["promoted"] for e in lines] == [1, 1, 1, 1, 0, 0, 0, 0, 1, 0]
    assert status["promoted_launches"] == 5
    assert status["launches_by_rung"] == {"32": 6, "64": 2, "96": 1, "128": 1}
    # The hold each window was granted at its cut: one launch of the last
    # shape of its plan while that shape has room (the two oversized windows:
    # the 32-slot chunk with 5 items, the second 64-slot one with 40); every
    # caller here is alone, so a granted hold ends at once (in step with
    # nobody) unless the connection before it was slow to hang up (then it
    # runs out).
    assert [e["hold_s"] for e in lines] == (
        [costs[32]] * 5 + [0.0, costs[64], 0.0, costs[32], costs[64]]
    )
    assert [e["in_step"] + e["held_out"] for e in lines] == [1] * 5 + [0, 1, 0, 1, 1]
    assert status["in_step_launches"] + status["held_out_launches"] == 8
    assert [e["chunks"] for e in lines] == [1] * 8 + [2, 2]
    assert status["split_launches"] == 2 and status["warm_stats"]["chunk_plan"] == {}
    assert status["warmed_shapes"] == list(shapes)
    assert set(status) <= trace_schema.VERIFYD_STATUS_KEYS
    assert set(status["warm_stats"]) <= trace_schema.VERIFYD_WARM_STATS_KEYS
    for shape in status["warm_stats"]["per_shape"]:
        assert set(shape) == trace_schema.VERIFYD_PER_SHAPE_KEYS
        assert shape["launch_s"] == costs[shape["size"]]
    assert status["warm_stats"]["serving_table"] == {"8": 32, "16": 32, "32": 32, "64": 64}
    assert {"promoted", "chunks", "split", "hold_s", "held_out", "in_step"} <= (
        trace_schema.EVENT_SCHEMAS["verify_batch"]["optional"]
    )


def test_warm_up_fails_where_an_executable_rejects_the_pad_triple(monkeypatch):
    """The timed launches are a self-test of each executable: the engine's
    own kernel has to accept the known-good triple in every pad slot."""
    import pbft_tpu.parallel as parallel

    real = parallel.lower_sharded

    def broken(mesh, size, kernel=None):
        import jax
        import jax.numpy as jnp

        assert kernel is None  # the engine's own kernel was asked for

        def rejects_the_last_slot(p, m, s):
            # The kernel is traced for one device's rows (shard_map): a slot's
            # place in the window is its row behind the devices before this one.
            rows = p.shape[0]
            return jax.lax.axis_index("batch") * rows + jnp.arange(rows) < size - 1

        # A program that rejects its last slot, in the real kernel's place.
        return real(mesh, size, kernel=rejects_the_last_slot)

    monkeypatch.setattr(parallel, "lower_sharded", broken)
    engine = ShardedVerifyEngine(shapes=(8,))
    with pytest.raises(RuntimeError, match="8-slot executable rejected .* 1 of 8 slots"):
        engine.warm()
    assert engine.warmed_sizes == ()
    daemon = VerifyServiceDaemon(backend="auto", engine=ShardedVerifyEngine(shapes=(8,)))
    daemon.start(wait_ready=True, timeout=300)
    try:
        assert daemon.state_name == "cpu-only"
        assert "self-test" in daemon.status_json()["warm_error"]
    finally:
        daemon.stop()


# -- (b''') a window is staged as one block of bytes (ISSUE 31) -------------------


def _staged_item_by_item(items, size):
    """The staging that ``pad_batch`` replaced, kept as its reference: three
    tiled pad columns and three row stores an item."""
    import numpy as np

    from pbft_tpu.crypto import ref
    from pbft_tpu.crypto.batch import _PAD_MSG, _PAD_SEED

    pubs = np.tile(np.frombuffer(ref.public_key(_PAD_SEED), np.uint8), (size, 1))
    msgs = np.tile(np.frombuffer(_PAD_MSG, np.uint8), (size, 1))
    sigs = np.tile(np.frombuffer(ref.sign(_PAD_SEED, _PAD_MSG), np.uint8), (size, 1))
    for i, (pub, msg, sig) in enumerate(items):
        pubs[i] = np.frombuffer(pub, np.uint8)
        msgs[i] = np.frombuffer(msg, np.uint8)
        sigs[i] = np.frombuffer(sig, np.uint8)
    return pubs, msgs, sigs


def _probe_items(n, seed=31):
    """``n`` items as the benchmark's probe mixes them: valid signatures
    with one of each class the kernel decides (seven rejects and the
    control) planted among the first."""
    import random

    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.pop(0)
    rng = random.Random(seed)
    pool = chip_smoke.signed_pool(rng, max(n, 1))
    plants = [item for item, _want in chip_smoke.planted(rng, pool[0]).values()]
    assert len(plants) == 8
    return (plants + pool)[:n]


@pytest.mark.parametrize("n", [0, 1, 15, 16])
def test_a_staged_block_is_the_items_then_the_pad_triple(n):
    """n = 0, 1, size - 1, size: the block's columns are what staging item
    by item gave, and the kernel is handed exactly them."""
    import numpy as np

    from pbft_tpu.crypto import ref
    from pbft_tpu.crypto.batch import _PAD_ROW, pad_batch, split_block

    items = _probe_items(n)
    block, got_n = pad_batch(items, 16)
    assert got_n == n and block.shape == (16, 128) and block.dtype == np.uint8
    assert block.flags.writeable and block.flags.c_contiguous
    for got, want in zip(split_block(block), _staged_item_by_item(items, 16)):
        assert np.array_equal(got, want)
    assert ref.verify(*map(bytes, split_block(_PAD_ROW[None])))  # the pad row is a valid triple
    assert [tuple(map(bytes, row)) for row in zip(*(c[:n] for c in split_block(block)))] == items
    with pytest.raises(ValueError, match="exceeds padded size"):
        pad_batch([_item(0, True)] * 17, 16)
    if n:
        with pytest.raises(ValueError, match="not 128-byte triples"):
            pad_batch([(items[0][0], items[0][1], items[0][2][:63])] + items[1:], 16)


def test_rows_past_a_chunk_are_the_pad_triple_whatever_was_staged_before():
    import random

    import numpy as np

    from pbft_tpu.crypto.batch import _PAD_ROW, _pad_template, pad_batch

    rng = random.Random(31)
    items = [(rng.randbytes(32), rng.randbytes(32), rng.randbytes(64)) for _ in range(300)]
    fresh = np.tile(_PAD_ROW, (1024, 1))
    big, n_big = pad_batch(items, 1024)
    big[:] = 0xAB  # a block is the caller's own: writing it reaches nobody else
    small, n_small = pad_batch(items[:10], 1024)
    assert (n_big, n_small) == (300, 10)
    assert np.array_equal(small[10:], fresh[10:])
    assert small[:10].tobytes() == b"".join(p + m + s for p, m, s in items[:10])
    template = _pad_template(1024)
    assert np.array_equal(template, fresh) and not template.flags.writeable
    assert template is _pad_template(1024)  # made once a shape
    assert not np.shares_memory(small, template) and not np.shares_memory(small, big)


def test_the_executable_takes_one_block_and_hands_the_kernel_its_columns():
    import jax
    import numpy as np

    from pbft_tpu.crypto.batch import pad_batch
    from pbft_tpu.parallel import compile_sharded, make_mesh

    items = _probe_items(11)
    want = [np.asarray(c) for c in _staged_item_by_item(items, 16)]
    seen = []

    def kernel(pubs, msgs, sigs):
        # Traced for ONE device's rows of the block (shard_map over the batch
        # axis): the rows behind those of the devices before it.
        seen.append([(a.shape, str(a.dtype)) for a in (pubs, msgs, sigs)])
        rows = pubs.shape[0]
        start = jax.lax.axis_index("batch") * rows
        same = [
            (got == jax.lax.dynamic_slice_in_dim(jax.numpy.asarray(col), start, rows)).all(axis=1)
            for got, col in zip((pubs, msgs, sigs), want)
        ]
        return same[0] & same[1] & same[2]

    mesh = make_mesh(devices=jax.local_devices())
    rows = 16 // mesh.devices.size
    compiled = compile_sharded(mesh, 16, kernel=kernel)
    assert seen == [[((rows, 32), "uint8"), ((rows, 32), "uint8"), ((rows, 64), "uint8")]]
    args, kwargs = compiled.in_avals
    assert [(a.shape, str(a.dtype)) for a in args] == [((16, 128), "uint8")] and not kwargs
    assert len(compiled.input_shardings[0]) == 1
    block, _n = pad_batch(items, 16)
    assert np.asarray(compiled(block)).all()
    block[3, 40] ^= 1  # one bit of one message: that row's columns differ, no other's
    assert np.asarray(compiled(block)).tolist() == [i != 3 for i in range(16)]
    with pytest.raises(TypeError):
        compiled(*want)


def test_warm_up_and_serving_stage_through_the_same_function(monkeypatch):
    """``launch_s`` (and with it the serving table, the chunk plan and the
    hold) is timed on the path that serves: a replaced ``pad_rows`` is
    seen by ``_measure`` and by ``verify`` alike, and the executable gets
    what it returned, untouched, from both."""
    import numpy as np

    import pbft_tpu.parallel as parallel
    from pbft_tpu.crypto import batch

    pads, blocks, real_pad, real_lower = [], [], batch.pad_rows, parallel.lower_sharded

    class SeenLowered:  # what lower_sharded gives, its executable wrapped
        def __init__(self, lowered):
            self.as_text, self.compile = lowered.as_text, lambda: _Seen(lowered.compile())

    def pad(segments, size):
        block, n = real_pad(segments, size)
        pads.append(n)
        blocks.append(block)
        return block, n

    monkeypatch.setattr(batch, "pad_rows", pad)
    monkeypatch.setattr(parallel, "lower_sharded", lambda *a, **kw: SeenLowered(real_lower(*a, **kw)))
    engine = ShardedVerifyEngine(shapes=(8,), kernel=lambda p, m, s: p[:, 0] == s[:, 0])
    engine.warm()
    assert pads == [0] * (1 + engine.WARM_LAUNCHES)
    items = [_item(i, i != 1) for i in range(3)]
    assert engine.verify(items) == [True, False, True]
    engine._route({8: 0.001})
    assert engine.verify(items * 4) == [True, False, True] * 4  # 12 items: 8 + 8 slots
    assert pads[1 + engine.WARM_LAUNCHES :] == [3, 8, 4]
    seen = engine._compiled[8].args
    assert len(seen) == len(blocks) and all(len(args) == 1 for args in seen)
    assert all(args[0] is block and isinstance(block, np.ndarray) for args, block in zip(seen, blocks))


def test_verify_status_prints_the_serving_table(capsys):
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import verify_status
    finally:
        sys.path.pop(0)
    engine = ShardedVerifyEngine(shapes=(8, 32), kernel=_slow_below_32)
    daemon = VerifyServiceDaemon(backend="auto", engine=engine).start(wait_ready=True, timeout=300)
    try:
        assert _send_batch(daemon.address, [_item(3, True)]) == [True]
        assert verify_status.main([daemon.address]) == 0
    finally:
        daemon.stop()
    out = capsys.readouterr().out
    assert "serving table   8→32 32→32  (1 launches promoted)" in out
    assert "chunk plan      one shape a window  (0 launches split)" in out
    costs = out.split("launch cost     ")[1].splitlines()[0]
    assert [w for w in costs.split() if w.endswith(":")] == ["8:", "32:"] and costs.endswith(" ms")
    assert "promoted_launches" not in out  # printed once, with the table
    assert "split_launches" not in out and "chunk_plan" not in out


def test_verify_status_prints_the_stall_fields(capsys):
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import verify_status
    finally:
        sys.path.pop(0)
    daemon = VerifyServiceDaemon(backend="cpu").start()
    try:
        _send_batch(daemon.address, [_item(1, True)])  # rejected by the real oracle
        assert verify_status.main([daemon.address]) == 0
    finally:
        daemon.stop()
    out = capsys.readouterr().out
    assert "stage seconds   queue " in out and " slot " in out
    assert "slowest launch" in out and "1 items at rung None" in out
    assert "stalls          0 launch(es) over a second in flight, the longest 0.000s" in out
    assert "longest_stall_s" not in out  # printed once, in words


def _blocks_for(seconds):
    def backend(items):
        time.sleep(seconds)  # the frame the stall record has to show
        return [True] * len(items)

    return backend


@pytest.mark.parametrize("blocked_s, records", [(1.3, 1), (0.2, 0)])
def test_a_launch_over_a_second_in_flight_leaves_one_stall_record(
    tmp_path, capfd, blocked_s, records
):
    """What the chip process does in a stall (ISSUE 38): a watcher thread
    writes ONE ``launch_stalled`` record for a launch in flight longer than
    ``STALL_S``, with the blocked thread's stack in it, every OS thread of
    the process and what the backend's owner says of its device; one
    ``launch_stall_ended`` when it returns; counts in the status JSON. A
    healthy launch leaves nothing."""
    from pbft_tpu.net import service as service_module

    assert service_module.STALL_S == 1.0 and service_module.STALL_POLL_S == 0.5
    trace = tmp_path / "launches.jsonl"
    svc = VerifierService(backend=_blocks_for(blocked_s), trace_path=str(trace)).start()
    svc.stall_probe = lambda: [{"bytes_in_use": 123}]
    try:
        assert _send_batch(svc.address, [_item(1, True), _item(2, True)]) == [True, True]
        status = svc.launch_status()
    finally:
        svc.stop()
    lines = [json.loads(line) for line in trace.read_text().splitlines()]
    stalled = [e for e in lines if e["ev"] == "launch_stalled"]
    ended = [e for e in lines if e["ev"] == "launch_stall_ended"]
    assert len(stalled) == len(ended) == records
    assert [e["ev"] for e in lines].count("verify_batch") == 1
    assert status["stalls"] == records
    err = capfd.readouterr().err
    assert err.count("launch_stalled {") == err.count("launch_stall_ended size=2") == records
    if not records:
        assert status["longest_stall_s"] == 0.0
        return
    (rec,), (end,) = stalled, ended
    assert set(rec) == trace_schema.EVENT_SCHEMAS["launch_stalled"]["required"] | {"rung", "memory"}
    assert rec["size"] == 2 and rec["rung"] is None and 1.0 < rec["age_s"] < blocked_s + 0.3
    # The launch's own thread, blocked where the backend blocks.
    mine = rec["stacks"][str(rec["thread"])]
    assert any(f.endswith(" backend") and f.startswith("test_verify_spans.py:") for f in mine["frames"])
    assert any(" _spanned" in f for f in mine["frames"])
    assert len(rec["stacks"]) >= 3  # the handler's and the watcher's own are there too
    # Every OS thread of the process: [tid, name, state, wchan].
    assert len(rec["tasks"]) >= len(rec["stacks"])
    assert all(len(t) == 4 and t[2] in "RSDZTtXxKWPI" for t in rec["tasks"])
    assert rec["memory"] == [{"bytes_in_use": 123}]
    assert end["size"] == 2 and blocked_s <= end["secs"] < blocked_s + 0.5
    assert status["longest_stall_s"] == end["secs"]


def test_a_device_that_does_not_answer_cannot_hold_the_stall_record_back():
    svc = VerifierService(backend="cpu").start()
    try:
        assert svc._probe_device() is None  # a bare service knows no device
        svc.stall_probe = lambda: time.sleep(5)
        t0 = time.monotonic()
        assert svc._probe_device() == "no answer in 0.5 s"
        assert time.monotonic() - t0 < 1.5
        svc.stall_probe = lambda: 1 / 0
        assert svc._probe_device().startswith("failed: ZeroDivisionError")
    finally:
        svc.stop()


# -- the replica's two histograms, async branch --------------------------------


def _chipbench_stats():
    sys.path.insert(0, str(CHIPBENCH))
    try:
        import stats
    finally:
        sys.path.pop(0)
    return stats


def _fetch(port: int, path: str) -> str:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.read().decode()


def test_replica_histograms_on_the_async_branch():
    """pbftd behind a verify service (RemoteVerifier, the async branch of
    run_verify_batch): one inbox-wait observation per verify batch, and a
    WAL flush histogram, on every replica's /metrics."""
    from pbft_tpu import native

    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    from pbft_tpu.net import LocalCluster, PbftClient

    stats = _chipbench_stats()
    daemon = VerifyServiceDaemon(backend="native").start()
    try:
        with LocalCluster(
            n=4, verifier=daemon.address, metrics_ports=True, wal=True
        ) as cluster:
            client = PbftClient(cluster.config)
            try:
                for i in range(3):
                    req = client.request(f"spans-{i}")
                    assert client.wait_result(req.timestamp, timeout=30) == "awesome!"
            finally:
                client.close()
            time.sleep(0.5)  # trailing commits and checkpoints
            scrapes = [stats.parse_prometheus(_fetch(port, "/metrics")) for port in cluster.metrics_ports]
    finally:
        daemon.stop()
    assert daemon.fallback_items > 0  # the service (native backend) verified them
    for m in scrapes:
        batches = m[("pbft_verify_batches_total", "")]
        waits = m[("pbft_verify_inbox_wait_seconds_count", "")]
        # A batch still in flight at the scrape has been observed already.
        assert batches >= 3 and batches <= waits <= batches + 1
        assert 0 <= m[("pbft_verify_inbox_wait_seconds_sum", "")] < 30
        assert m[("pbft_wal_flush_seconds_count", "")] >= 3
        assert m[("pbft_wal_flush_seconds_sum", "")] > 0
        assert m[("pbft_verify_service_fallbacks_total", "")] == 0


def test_a_served_cluster_launches_ahead_of_kept_verdicts(tmp_path, capsys):
    """The order of a pass on the async branch (ISSUE 37): under concurrent
    requests a replica ships the span of its inbox behind a batch BEFORE it
    works through that batch's verdicts. Every replica counts launches made
    that way, clocks what keeping the verdicts costs once a batch, and the
    cluster still has one history with nothing verified on the host."""
    from pbft_tpu import native

    if not native.available():  # pragma: no cover - unbuilt container
        pytest.skip("native core not built")
    from pbft_tpu.net import LocalCluster, PbftClient

    stats = _chipbench_stats()
    clients, each = 4, 12
    errors: list = []
    daemon = VerifyServiceDaemon(backend="native").start()
    try:
        with LocalCluster(
            n=4, verifier=daemon.address, metrics_ports=True, wal=True,
            trace_dir=str(tmp_path),
        ) as cluster:

            def serve(k: int) -> None:
                client = PbftClient(cluster.config)
                try:
                    sent = [client.request(f"ahead-{k}-{i}") for i in range(each)]
                    for req in sent:
                        assert client.wait_result(req.timestamp, timeout=60) == "awesome!"
                except Exception as e:  # noqa: BLE001 - shown by the main thread
                    errors.append(e)
                finally:
                    client.close()

            threads = [threading.Thread(target=serve, args=(k,)) for k in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(120)
            assert not errors, errors
            deadline = time.monotonic() + 30
            while True:  # trailing commits and checkpoints land
                final = [json.loads(_fetch(port, "/status")) for port in cluster.metrics_ports]
                if len({d["chain_digest"] for d in final}) == 1 and all(
                    d["inbox_depth"] == 0 for d in final
                ):
                    break
                assert time.monotonic() < deadline, [d["executed"] for d in final]
                time.sleep(0.2)
            scrapes = [stats.parse_prometheus(_fetch(port, "/metrics")) for port in cluster.metrics_ports]
    finally:
        daemon.stop()
    assert daemon.fallback_items > 0  # the service (native backend) verified them
    assert {d["executed"] for d in final} == {clients * each} and {d["view"] for d in final} == {0}
    assert sum(d["verify_service_fallbacks"] + d["verify_deadline_fired"] for d in final) == 0
    for m, d in zip(scrapes, final):
        batches = m[("pbft_verify_batches_total", "")]
        ahead = m[("pbft_verify_launched_ahead_total", "")]
        assert 0 < ahead <= batches and d["verify_launched_ahead"] == ahead
        # Once a batch on the async branch, whether or not a launch went ahead.
        assert m[("pbft_verdict_held_seconds_count", "")] == batches
        assert 0 <= m[("pbft_verdict_held_seconds_sum", "")] < 30
        assert m[("pbft_verify_seconds_count", "")] == batches
        assert batches <= m[("pbft_verify_inbox_wait_seconds_count", "")] <= batches + 1
    # Every batch line of the trace says whether a launch went ahead of it
    # (the last batches may be delivered after the scrape), and both scripts
    # print the share where they print the verify batches.
    said = 0
    for i, m in enumerate(scrapes):
        lines = _lines(tmp_path / f"replica-{i}.jsonl")
        assert all(e["ahead"] in (0, 1) for e in lines)
        # ... how long working through its verdicts took, and where the loop's
        # time had gone by then: seven running totals that never fall, the
        # line written when the apply ended (ISSUE 38).
        assert all(0 <= e["apply_s"] < 30 and len(e["loop_us"]) == 7 for e in lines)
        for before, after in zip(lines, lines[1:]):
            assert all(x <= y for x, y in zip(before["loop_us"], after["loop_us"]))
            grew = 1e-6 * (sum(after["loop_us"]) - sum(before["loop_us"]))
            assert abs(grew - (after["ts"] - before["ts"])) < 0.002  # two lines bracket an interval
        assert m[("pbft_verdict_apply_seconds_count", "")] == m[("pbft_verify_batches_total", "")]
        assert sum(m[(f"pbft_loop_{s}_us_total", "")] for s in trace_schema.LOOP_STAGES) == (
            m[("pbft_loop_us_total", "")]
        )
        assert sum(e["ahead"] for e in lines) >= m[("pbft_verify_launched_ahead_total", "")]
        said += sum(e["ahead"] for e in lines)
    assert "ahead" in trace_schema.EVENT_SCHEMAS["verify_batch"]["optional"]
    sys.path.insert(0, str(ROOT / "scripts"))
    try:
        import consensus_timeline
        import trace_report
    finally:
        sys.path.pop(0)
    capsys.readouterr()
    trace_report.report([tmp_path / "replica-0.jsonl"])
    first = _lines(tmp_path / "replica-0.jsonl")
    assert f", launched ahead {sum(e['ahead'] for e in first)}/{len(first)} (" in capsys.readouterr().out
    consensus_timeline.main([str(tmp_path), "--limit", "1"])
    assert f"launched ahead of the verdicts kept: {said} (" in capsys.readouterr().out
    # The benchmark's two readers on those scrapes (an empty scrape before):
    # a share of the batches, and milliseconds; on a program from before the
    # counter and the histogram, as the parent commit is, nothing and no error.
    status = [{"view": 0}] * 4
    run = {"edge_a": {"metrics": [{}] * 4, "status": status},
           "edge_b": {"metrics": scrapes, "status": status}}
    share = _read("launched_ahead_share.closed", run)
    assert 0 < share <= 1 and _read("launched_ahead_share.rate", run) == share
    assert 0 <= _read("verdict_held_ms_mean.closed", run) < 30e3
    old = [{k: v for k, v in m.items() if "launched_ahead" not in k[0] and "verdict_held" not in k[0]}
           for m in scrapes]
    before = {"edge_a": {"metrics": [{}] * 4, "status": status},
              "edge_b": {"metrics": old, "status": status}}
    for name in ("launched_ahead_share.closed", "launched_ahead_share.rate",
                 "verdict_held_ms_mean.closed", "verdict_held_ms_mean.rate"):
        assert _read(name, before) is None


# -- (c) the two reducers, on hand-made runs -----------------------------------


def _reducer(name):
    sys.path.insert(0, str(CHIPBENCH))
    try:
        spec = importlib.util.spec_from_file_location(
            f"reducers.{name}", CHIPBENCH / "reducers" / f"{name}.py"
        )
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
    finally:
        sys.path.pop(0)
    return module.reduce


def _launch(t_dev, dispatch_s, wait_s, **more):
    return {"ts": t_dev + dispatch_s + wait_s, "size": 16, "secs": dispatch_s + wait_s,
            "t_dev": t_dev, "dispatch_s": dispatch_s, "wait_s": wait_s, **more}


@pytest.mark.parametrize(
    "stat, fields, want",
    [
        ("mean", ["pad_s", "put_s"], 1e3 * (0.003 + 0.005 + 0.010) / 3),
        ("max", ["wait_s"], 40.0),
        ("p50", ["wait_s"], 20.0),
        ("mean", ["queue_s"], None),  # no launch carries it
        ("mean", ["pad_s", "slot_s"], 1e3 * 0.104),  # only the one launch that has both
    ],
)
def test_launch_field_stat(stat, fields, want):
    run = {"launches": [
        _launch(1.0, 0.001, 0.010, pad_s=0.001, put_s=0.002),
        _launch(2.0, 0.001, 0.020, pad_s=0.002, put_s=0.003),
        _launch(3.0, 0.001, 0.040, pad_s=0.004, put_s=0.006, slot_s=0.1),
    ]}
    got = _reducer("launch_field_stat")(run, {"fields": fields, "stat": stat, "scale": 1000})
    assert got == (want if want is None else pytest.approx(want))


def test_launch_union_idle_pct():
    reduce = _reducer("launch_union_idle_pct")
    args = {"start": "t_dev", "lengths": ["dispatch_s", "wait_s"]}
    run = {"t0": 10.0, "t1": 20.0, "launches": [
        _launch(9.5, 0.1, 0.9),  # straddles t0: only [10.0, 10.5] counts
        _launch(11.0, 0.5, 1.5),  # [11, 13]
        _launch(12.0, 0.5, 1.5),  # [12, 14] overlaps the one before: union [11, 14]
        _launch(16.0, 0.0, 1.0),  # [16, 17]
    ]}
    assert reduce(run, args) == pytest.approx(100.0 * (1 - (0.5 + 3.0 + 1.0) / 10.0))
    # A program from before the spans, or a backend that is not the engine:
    # the launches are there, the fields are not.
    bare = {"t0": 10.0, "t1": 20.0, "launches": [{"ts": 11.0, "size": 4, "secs": 0.1}]}
    assert reduce(bare, args) is None
    assert reduce({"t0": 10.0, "t1": 20.0, "launches": []}, args) is None


# -- (d) the benchmark's files ---------------------------------------------------

NEW_METRICS = {
    "inbox_wait_ms_mean": ("ms", "verify inbox to RemoteVerifier"),
    "wal_flush_ms_mean": ("ms", "WAL"),
    "verifyd_queue_ms_mean": ("ms", "verifyd dispatcher"),
    "slot_wait_ms_mean": ("ms", "verifyd dispatcher"),
    "staging_ms_mean": ("ms", "verifyd engine"),
    "device_wait_ms_mean": ("ms", "verifyd engine"),
    "device_wait_ms_max": ("ms", "verifyd engine"),
    "engine_idle_pct": ("%", "device"),
    # PR 27: how often, and to what, the engine's serving table promotes.
    "promoted_share": ("ratio", "verifyd engine", "higher", "program_counter"),
    "rung_slots_mean": ("slots", "verifyd engine", "lower", "program_counter"),
    # PR 37: how often a replica launches its next batch ahead of the verdicts
    # it keeps, and what keeping them costs a batch.
    "launched_ahead_share": ("ratio", "verify inbox to RemoteVerifier", "higher", "program_counter"),
    "verdict_held_ms_mean": ("ms", "verify inbox to RemoteVerifier"),
}
# PR 28: what the hold did to each window, in the closed cells only.
CLOSED_ONLY_METRICS = {
    "requests_per_launch": ("requests", "verifyd dispatcher", "higher", "program_span"),
    "window_items_max": ("items", "verifyd dispatcher", "higher", "program_span"),
    "hold_ms_mean": ("ms", "verifyd dispatcher"),
    "held_out_share": ("ratio", "verifyd dispatcher"),
    "in_step_share": ("ratio", "verifyd dispatcher", "higher", "program_span"),
    # PR 29: the share of windows the engine's chunk plan ran as several launches.
    "split_share": ("ratio", "verifyd engine", "higher", "program_counter"),
}
FORMS = {
    ".closed": ("commit_rate", ["f1-sig-wal.closed", "f5-sig-wal.closed"]),
    ".rate": ("reply_p50_ms", ["f1-sig-wal.rate"]),
}
# What the accepted benchmark held (PR 25's, which later PRs may only add
# to): everything in BENCHMARK.json but the entries appended to `per_layer`
# and the configuration and cell PR 28 appended (a `benchmark` PR that edits
# an entry pins its own digest here).
ACCEPTED_PER_LAYER = 27
ACCEPTED_DIGEST = "7f2b2c41f15ac07556e716381fd4915806724ddc56d4b01011fdd21d1e6e3800"
ADDED_CONFIGS = ["f5-sig-wal", "f1-mac-tentative", "f5-sig-wal-x4", "f1-sig-wal-mt", "f10-sig-wal"]
ADDED_CELLS = ["f5-sig-wal.closed", "f1-mac-tentative.closed", "f5-sig-wal-x4.closed",
               "f1-sig-wal-mt.closed", "f10-sig-wal.closed"]
# The metrics of this table that PR 32's cell is listed under too (it reports
# no verify trip, so none of the others).
ALSO_IN_MAC_CELL = {"engine_idle_pct", "wal_flush_ms_mean"}
# PR 36's four-chip cell reports whatever its one-chip twin reports.
X4_CELL, X4_TWIN = "f5-sig-wal-x4.closed", "f5-sig-wal.closed"
# PR 40's: the multi-core replica's cell reports whatever its one-thread twin
# reports. (The f5-sig-wal.rate cell of the same PR was measured and LEFT
# OUT: its second set of six runs did not hold half the bound, PERF.md §7.)
MT_CELL, MT_TWIN = "f1-sig-wal-mt.closed", "f1-sig-wal.closed"
# PR 42's: the n=31 cluster's cell reports whatever its n=16 sibling reports,
# and the two of them the four readers that PR brought.
F10_CELL, F10_TWIN = "f10-sig-wal.closed", "f5-sig-wal.closed"


@pytest.mark.parametrize(
    "name, form",
    [(name, form) for name in sorted(NEW_METRICS) for form in sorted(FORMS)]
    + [(name, ".closed") for name in sorted(CLOSED_ONLY_METRICS)],
)
def test_new_metric_has_its_reader_and_its_entry(name, form):
    unit, layer, better, source = (
        *(NEW_METRICS | CLOSED_ONLY_METRICS)[name], "lower", "program_span"
    )[:4]
    moves, cells = FORMS[form]
    if form == ".closed" and name in ALSO_IN_MAC_CELL:
        cells = cells + ["f1-mac-tentative.closed"]
    if form == ".closed":
        cells = cells + [X4_CELL, MT_CELL, F10_CELL]
    spec = json.loads((CHIPBENCH / "metrics" / f"{name}{form}.json").read_text())
    assert spec["name"] == name + form
    assert (CHIPBENCH / "reducers" / f"{spec['reducer']}.py").is_file()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = [m for m in bench["per_layer"] if m["name"] == name + form]
    assert entry == [{
        "name": name + form, "unit": unit, "better": better, "source": source,
        "layer": layer, "moves": moves, "workloads": cells,
    }]
    assert bench["per_layer"].index(entry[0]) >= ACCEPTED_PER_LAYER


def _read(metric, run):
    """``chipbench/metrics/<metric>.json`` on ``run``, as the harness reads it."""
    spec = json.loads((CHIPBENCH / "metrics" / f"{metric}.json").read_text())
    return _reducer(spec["reducer"])(run, spec.get("args", {}))


def test_the_four_chip_cell_is_listed_wherever_its_twin_is_and_brings_two_readers():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if cells == [F10_TWIN, F10_CELL]:  # PR 42's four readers, in the two one-chip cells
            continue
        assert (X4_CELL in cells) == (X4_TWIN in cells), m["name"]
    # Two readings of the launch lines' new fields, on the reducer that is
    # there, in the two f=5 cells: the chips a window's executables are
    # sharded over (1.0 and 4.0) and the rows a chip of its thinnest chunk.
    for name, field, unit in (("mesh_chips.closed", "devices", "chips"),
                              ("rows_per_chip_mean.closed", "rows_per_chip", "rows")):
        spec = json.loads((CHIPBENCH / "metrics" / f"{name}.json").read_text())
        assert spec == {"name": name, "reducer": "launch_field_stat",
                        "args": {"fields": [field], "stat": "mean"}}
        assert [m for m in bench["per_layer"] if m["name"] == name] == [{
            "name": name, "unit": unit, "better": "higher", "source": "program_counter",
            "layer": "verifyd engine", "moves": "commit_rate",
            "workloads": [X4_TWIN, X4_CELL, F10_CELL],  # PR 42's cell behind them: one chip, 1.0
        }]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("mesh_chips.closed")  # appended by PR 36, as a pair
    assert at >= ACCEPTED_PER_LAYER and names[at + 1] == "rows_per_chip_mean.closed"
    one = [{"devices": 1, "rows_per_chip": r} for r in (256, 1024, 256)]
    four = [{"devices": 4, "rows_per_chip": r} for r in (256, 256, 1024, 256)]
    assert _read("mesh_chips.closed", {"launches": one}) == 1.0
    assert _read("mesh_chips.closed", {"launches": four}) == 4.0
    assert _read("rows_per_chip_mean.closed", {"launches": four}) == 448.0
    # a program from before the fields, as the parent commit is: nothing, and no error
    assert _read("mesh_chips.closed", {"launches": [{"rung": 256}]}) is None
    assert _read("rows_per_chip_mean.closed", {"launches": []}) is None


FUSED = {
    "fused_launch_share.closed": ("commit_rate", ["f1-sig-wal.closed", "f5-sig-wal.closed",
                                                  "f1-mac-tentative.closed", X4_CELL, MT_CELL, F10_CELL]),
    "fused_launch_share.rate": ("reply_p50_ms", ["f1-sig-wal.rate"]),
}


@pytest.mark.parametrize("name", sorted(FUSED))
def test_the_fused_share_has_its_reader_and_its_entry(name):
    """PR 43's two readers: data only, on the reducer that is there, the
    last two entries of ``per_layer``, layer ``kernel``."""
    moves, cells = FUSED[name]
    spec = json.loads((CHIPBENCH / "metrics" / f"{name}.json").read_text())
    assert spec == {"name": name, "reducer": "launch_field_stat",
                    "args": {"fields": ["fused"], "stat": "mean"}}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [m["name"] for m in bench["per_layer"]][-2:] == sorted(FUSED)
    assert [m for m in bench["per_layer"] if m["name"] == name] == [{
        "name": name, "unit": "ratio", "better": "higher", "source": "program_counter",
        "layer": "kernel", "moves": moves, "workloads": cells,
    }]
    assert "kernel" in {m["layer"] for m in bench["per_layer"][:ACCEPTED_PER_LAYER]}


@pytest.mark.parametrize("name", sorted(FUSED))
@pytest.mark.parametrize(
    "launches, want",
    [
        ([{"rung": 4096, "fused": 1.0}] * 3 + [{"rung": 256, "fused": 0.0}], 0.75),
        ([{"rung": 1280, "fused": 0.8}, {"rung": 256, "fused": 0.0}], 0.4),
        ([{"rung": 256, "fused": 0.0}] * 5, 0.0),  # every launch the 256-slot program
        # a window the fallback ran has no engine fields: left out
        ([{"rung": 4096, "fused": 1.0}, {"size": 7, "secs": 0.01}], 1.0),
        # a program from before the field, as the parent commit is: nothing, and no error
        ([{"rung": 4096, "split": 0}, {"rung": 256, "split": 0}], None),
        ([], None),
    ],
)
def test_the_fused_share_over_launch_lines_with_and_without_the_field(name, launches, want):
    got = _read(name, {"launches": launches})
    assert got == want if want is None else got == pytest.approx(want)


def test_the_multicore_cell_is_listed_wherever_its_twin_is():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        cells = m.get("workloads", [])
        if cells == [MT_CELL]:  # the ten readers of what the deployment adds
            continue
        assert (MT_CELL in cells) == (MT_TWIN in cells), m["name"]
        assert "f5-sig-wal.rate" not in cells  # measured in PR 40 and left out


def test_accepted_benchmark_entries_are_unchanged():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    # Configurations and cells are appended, and a cell's name to the lists
    # of the metrics it reports; taken off again, nothing has changed.
    assert [c["name"] for c in bench["configs"]][-len(ADDED_CONFIGS):] == ADDED_CONFIGS
    assert [c["name"] for c in bench["workloads"]][-len(ADDED_CELLS):] == ADDED_CELLS

    def as_accepted(metric: dict) -> dict:
        cells = metric.get("workloads")
        if cells is None or not set(cells) & set(ADDED_CELLS):
            return metric
        kept = [c for c in cells if c not in ADDED_CELLS]
        assert cells == kept + [c for c in ADDED_CELLS if c in cells]  # at the end, in order
        return dict(metric, workloads=kept)

    accepted = dict(
        bench,
        configs=bench["configs"][: -len(ADDED_CONFIGS)],
        workloads=bench["workloads"][: -len(ADDED_CELLS)],
        end_to_end=[as_accepted(m) for m in bench["end_to_end"]],
        per_layer=[as_accepted(m) for m in bench["per_layer"][:ACCEPTED_PER_LAYER]],
    )
    digest = hashlib.sha256(json.dumps(accepted, sort_keys=True).encode()).hexdigest()
    assert digest == ACCEPTED_DIGEST
    # Every metric has a reader file, and every reader file a metric.
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert names == {p.name[: -len(".json")] for p in (CHIPBENCH / "metrics").glob("*.json")}

"""The yardstick's arithmetic on recorded or hand-made inputs."""

import json
import random
import statistics
import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
sys.path.insert(0, str(BENCH_DIR))

import harness  # noqa: E402
import kernel_ops  # noqa: E402
import stats  # noqa: E402
import xplane  # noqa: E402
from traffic_kinds import closed, poisson  # noqa: E402


def metric(name, run):
    return harness.read_metric(BENCH_DIR, name, run)


def test_percentiles_and_spreads():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile([7], 95) == 7
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    runs = [100, 101, 102, 103, 104, 130]
    q1, _, q3 = statistics.quantiles(runs, n=4)
    assert stats.iqr_spread(runs) == pytest.approx((q3 - q1) / 102.5)
    # one far-off run does no harm to the trimmed reading
    assert stats.trimmed_iqr_spread(runs) == pytest.approx(stats.iqr_spread(runs[:5]))


def hand_made_run():
    """Window [100, 110): five requests; one due before the window, one
    never answered, one answered after the window closed."""
    gen = {
        "due": [99.5, 100.0, 102.0, 104.0, 109.0],
        "sent": [99.5, 100.001, 102.004, 104.002, 109.010],
        "done": [100.2, 100.5, 102.7, None, 110.4],
        "rejected": 1,
    }
    return {"t0": 100.0, "t1": 110.0, "seconds": 10.0, "gen": gen,
            "traffic": {"drain_s": 30}, "setup_s": 12.5}


def test_due_time_latency_lateness_and_rate_on_a_hand_made_schedule():
    run = hand_made_run()
    # completed inside the window: 100.2, 100.5, 102.7 (110.4 is outside)
    assert metric("commit_rate", run) == pytest.approx(0.3)
    # due inside: 100.0 (0.5 s), 102.0 (0.7 s), 104.0 (missing: to the end of
    # the drain, 140 - 104 = 36 s), 109.0 (1.4 s)
    assert metric("reply_p50_ms", run) == pytest.approx(1e3 * (0.7 + 1.4) / 2)
    assert metric("reply_p95_ms.rate", run) == pytest.approx(1e3 * (1.4 + 0.85 * (36 - 1.4)))
    assert metric("gen_late_p99_ms.rate", run) == pytest.approx(
        stats.percentile([1.0, 4.0, 2.0, 10.0], 99), rel=1e-6)
    assert metric("gateway_rejects.rate", run) == 1.0
    assert metric("setup_s", run) == 12.5


def test_pad_fill_and_the_histogram_of_slots_run_from_sample_launch_lines():
    """The label is the shape that RAN, the line's ``rung`` and ``chunks``
    (``verifyd --trace`` since PRs 26 and 29), never the smallest shape the
    item count would fit: 80 and 16 items run on 256 slots since PR 27, 1,100
    as 1,024 + 256 since PR 29."""
    lines = [
        '{"ts":1.0,"ev":"verify_batch","size":80,"requests":4,"secs":0.046,"rung":256,"chunks":1}',
        '{"ts":1.1,"ev":"verify_batch","size":16,"requests":1,"secs":0.020,"rung":256,"chunks":1}',
        '{"ts":1.2,"ev":"verify_batch","size":300,"requests":9,"secs":0.080,"rung":1024,"chunks":1}',
        '{"ts":1.3,"ev":"verify_batch","size":1100,"requests":9,"secs":0.090,"rung":1280,"chunks":2}',
        '{"ts":1.4,"ev":"verify_batch","size":5000,"requests":30,"secs":0.300,"rung":5120,"chunks":2}',
        '{"ts":1.5,"ev":"verify_batch","size":1400,"requests":16,"secs":0.030,"rung":1536,"chunks":3}',
        '{"ts":1.6,"ev":"verify_batch","size":7,"requests":1,"secs":0.001}',
    ]
    ladder = [16, 64, 256, 1024, 4096]
    launches = [json.loads(ln) for ln in lines]
    assert [xplane.shapes_run(e, ladder) for e in launches] == [
        (256,), (256,), (1024,), (1024, 256), (4096, 1024), (1024, 256, 256), ()]
    # two chunks that sum to 2,048 are 1,024 + 1,024; no two shapes sum to 300
    assert xplane.shapes_run({"rung": 2048, "chunks": 2}, ladder) == (1024, 1024)
    assert xplane.shapes_run({"rung": 300, "chunks": 2}, ladder) == ()
    got = stats.launch_stats(launches)
    assert got["items_per_launch"] == pytest.approx(7903 / 7)
    # the line without a ``rung`` (no sharded engine behind it) is left out
    slots = 256 + 256 + 1024 + 1280 + 5120 + 1536
    assert got["pad_fill"] == pytest.approx(7896 / slots)
    assert got["launch_ms_p50"] == pytest.approx(46.0)
    assert got["window_max_items"] == 5000
    assert got["rungs"]["256"] == {"launches": 2, "launch_ms_p50": pytest.approx(33.0)}
    run = {"launches": launches[:6], "ladder": ladder}
    assert metric("pad_fill.closed", run) == pytest.approx(7896 / slots)
    # ... which is items_per_launch over rung_slots_mean, to the digit
    assert metric("pad_fill.closed", run) == pytest.approx(
        metric("items_per_launch.closed", run) / metric("rung_slots_mean.closed", run), rel=1e-14)
    assert metric("items_per_launch.rate", dict(run, launches=launches)) == got["items_per_launch"]
    assert metric("launch_ms_p50.closed", {"launches": [], "ladder": ladder}) is None
    assert metric("pad_fill.rate", {"launches": launches[6:], "ladder": ladder}) is None


SCRAPE_A = """# TYPE pbft_phase_prepare_seconds histogram
pbft_phase_prepare_seconds_bucket{replica="0",le="0.5"} 10
pbft_phase_prepare_seconds_sum{replica="0"} 2.5
pbft_phase_prepare_seconds_count{replica="0"} 10
pbft_batch_size_sum{replica="0"} 100
pbft_batch_size_count{replica="0"} 10
pbft_verify_items_total{replica="0"} 500
"""
SCRAPE_B = """pbft_phase_prepare_seconds_bucket{replica="0",le="0.5"} 30
pbft_phase_prepare_seconds_sum{replica="0"} 8.5
pbft_phase_prepare_seconds_count{replica="0"} 30
pbft_batch_size_sum{replica="0"} 400
pbft_batch_size_count{replica="0"} 30
pbft_verify_items_total{replica="0"} 900
"""


def test_histogram_delta_means_from_two_sample_scrapes():
    a, b = stats.parse_prometheus(SCRAPE_A), stats.parse_prometheus(SCRAPE_B)
    assert stats.hist_delta(a, b, "pbft_phase_prepare_seconds") == (6.0, 20.0)
    assert stats.counter_delta(a, b, "pbft_verify_items_total") == 400
    run = hand_made_run()
    run["edge_a"] = {"metrics": [a], "status": [{"view": 0, "wal_fsyncs": 10}]}
    run["edge_b"] = {"metrics": [b], "status": [{"view": 0, "wal_fsyncs": 13}]}
    assert metric("prepare_ms_mean.closed", run) == pytest.approx(300.0)
    assert metric("batch_items_mean.rate", run) == pytest.approx(15.0)
    assert metric("commit_ms_mean.rate", run) is None  # nothing observed: nothing returned
    assert metric("fsyncs_per_req.closed", run) == pytest.approx(1.0)


def tiny_lines():
    """The launch lines of tiny.xplane.pb's three windows: the host's clock
    reads 1000 s where the trace's reads 1,000 ns."""
    return [
        {"size": 10, "rung": 16, "chunks": 1, "t_dev": 1000.011},
        {"size": 80, "rung": 256, "chunks": 1, "t_dev": 1000.013},
        {"size": 10, "rung": 16, "chunks": 1, "t_dev": 1000.089},
    ]


def test_trace_reduction_on_the_small_recorded_trace():
    """tests/data/tiny.xplane.pb (make_tiny_trace.py says what is in it): a
    slice of 90 ms with the device busy for 75; four launches of two
    executables: jit_fn(123) in no span, jit_fn(123) held by two spans,
    jit_fn(456) in one, jit_fn(123) across the slice's edge; host spans of
    engine.verify with 10, 80 and 10 items, whose lines say 16, 256 and 16
    slots."""
    ladder = [16, 64, 256]
    r = xplane.reduce_trace(TESTS / "data" / "tiny.xplane.pb", tiny_lines(), ladder, 1000.095)
    assert r["devices"] == 1 and r["planes"] == 1
    # the window is the slice's span, not first event to last: idle at the
    # edges counts, and what lies beyond the edge does not
    assert r["window_s"] == pytest.approx(90e-3) and r["busy_s"] == pytest.approx(75e-3)
    # jit_fn(456) ran alone in the 80-item span: 256 slots; so jit_fn(123),
    # which that span also holds once, is the 16-slot executable, in the span
    # that two hold and in no span alike
    assert [(x["slots"], x["spans"], pytest.approx(x["seconds"])) for x in r["launches"]] == [
        (16, 0, 3e-3), (16, 2, 20e-3), (256, 1, 48e-3)]
    assert r["modules"]["jit_fn"] == {"launches": 3, "seconds": pytest.approx(71e-3)}
    assert r["idle"]["inside_an_executable_between_its_operations"] == pytest.approx(1e-3)
    assert r["idle"]["inside_engine.verify_host_staging_or_readback"] == pytest.approx(10e-3)
    assert r["idle"]["outside_engine.verify_waiting_for_a_window"] == pytest.approx(4e-3)
    assert r["ops"][0] == ("while.2", pytest.approx(63e-3))  # 5 ms of it beyond the edge
    assert xplane.launches_by_shape(r, "jit_fn") == {
        16: [pytest.approx(3e-3), pytest.approx(20e-3)], 256: [pytest.approx(48e-3)]}
    assert xplane.launches_by_shape(r, "jit_other") == {}
    # the window's launches: three on 16 slots, one on 256, one on 64, which
    # the slice never saw and is left out on both sides
    window = [{"size": 10, "rung": 16}] * 3 + [{"size": 80, "rung": 256}, {"size": 40, "rung": 64}]
    run = {"trace": r, "ladder": ladder, "launches": window,
           "peaks": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}}
    assert metric("device_idle_pct.closed", run) == pytest.approx(100 * 15 / 90)
    assert metric("kernel_ms_per_launch.rate", run) == pytest.approx((3 * 11.5 + 48) / 4)
    ops = 3 * kernel_ops.ed25519_verify(16)["ops"] + kernel_ops.ed25519_verify(256)["ops"]
    assert metric("verify_kernel_roofline.closed", run) == pytest.approx(
        100 * (ops / 393e12) / (3 * 11.5e-3 + 48e-3))
    b = xplane.breakdown(r)
    assert b["device_ops"] == [["while.2", pytest.approx(63e-3)], ["fusion.1", pytest.approx(12e-3)],
                               ["launches_at_16_slots_x2", pytest.approx(23e-3)],
                               ["launches_at_256_slots_x1", pytest.approx(48e-3)]]
    assert len(b["idle_gaps"]) <= 10 and {g[0] for g in b["idle_gaps"][:3]} == {
        f"total_{cls}" for cls in xplane.IDLE_CLASSES}
    # a reader that finds nothing to read returns nothing, never 0: no trace,
    # a slice that saw none of the shapes the window's launches ran at, or a
    # trace read without the run's lines (no launch has a shape then)
    unseen = dict(run, launches=[{"size": 40, "rung": 64}])
    bare = dict(run, trace=xplane.reduce_trace(TESTS / "data" / "tiny.xplane.pb"))
    assert [x["slots"] for x in bare["trace"]["launches"]] == [None] * 3
    for name in ("kernel_ms_per_launch.closed", "verify_kernel_roofline.rate"):
        assert metric(name, dict(run, trace=None)) is None
        assert metric(name, unseen) is None
        assert metric(name, bare) is None
    assert metric("device_idle_pct.rate", dict(run, trace=None)) is None


def test_a_launch_is_told_by_its_executable_not_by_the_span_that_ends_first():
    """Up to PR 33 a launch that two spans held went to the one that ended
    first and had none yet, so a second chunk went to the other span in
    flight. Now every launch says which shapes it may have run at, and an
    executable is one shape."""
    ms = 1e6  # stamps are nanoseconds
    spans = [(0, 50 * ms, 10), (5 * ms, 120 * ms, 300), (52 * ms, 130 * ms, 40)]
    mods = [("a(1)", 10 * ms, 45 * ms), ("a(2)", 46 * ms, 110 * ms), ("a(1)", 111 * ms, 125 * ms)]
    launches = xplane.merge_planes([mods])
    assert [xplane.holders(x, spans) for x in launches] == [[0, 1], [1], [2]]
    plans = [(16,), (1024,), (16,)]
    for x in launches:
        x["may"] = sorted({s for i in xplane.holders(x, spans) for s in plans[i]})
    assert xplane.shape_of_each_executable(launches) == {"a(1)": 16, "a(2)": 1024}
    # a launch in no span, or in a span without a line, says nothing; with
    # nothing said about an executable it has no shape
    assert xplane.holders({"start": 10 * ms, "end": 20 * ms}, [(15 * ms, 40 * ms, 7)]) == []
    assert xplane.shape_of_each_executable([{"name": "a(3)", "may": None}]) == {}
    # two executables that could both only be the one shape: the lines are
    # not this trace's, and nothing of that name is labelled
    clash = [{"name": "a(1)", "may": [16]}, {"name": "a(2)", "may": [16]}, {"name": "b(1)", "may": [64]}]
    assert xplane.shape_of_each_executable(clash) == {"b(1)": 64}


def test_kernel_operation_count():
    one = kernel_ops.ed25519_verify(1)
    assert one == {"ops": 2 * 1024 * 3864, "bytes": 129}
    assert kernel_ops.ed25519_verify(256)["ops"] == 256 * one["ops"]


def test_every_seed_gets_the_same_arrivals_in_another_order():
    params = {"rate_per_s": 900, "identities": 32}
    a, _ = poisson.build(params, random.Random(1), 10.0)
    b, resend = poisson.build(params, random.Random(2), 10.0)
    assert not resend and len(a) == len(b) == 9000
    gaps = lambda arr: sorted(round(y[0] - x[0], 9) for x, y in zip([(0.0, 0)] + arr, arr))  # noqa: E731
    assert gaps(a) == gaps(b) and a != b
    assert sorted(i for _, i in a) == sorted(i for _, i in b)
    assert a[-1][0] == pytest.approx(10.0, rel=0.01)  # the mean gap is 1/rate
    c, resend = closed.build({"identities": 8, "outstanding_per_identity": 32}, None, 1.0)
    assert resend and len(c) == 256 and {i for _, i in c} == set(range(8))


def test_file_system_type_and_peaks():
    assert harness.fs_type(Path("/proc")) == "proc"
    assert harness.peaks_for(BENCH_DIR, "tpu", "TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(harness.BenchFailure):
        harness.peaks_for(BENCH_DIR, "tpu", "TPU v9")  # an unknown device is an error
    with pytest.raises(harness.BenchFailure):
        harness.peaks_for(BENCH_DIR, "cpu", "TPU v5 lite")  # so is another platform


def test_reference_signable_matches_what_replicas_sign():
    from reference import ed25519_ref as ref
    from reference import state_machine as sm

    seed = bytes(range(32))
    reply = {"view": 0, "timestamp": 7, "client": "gw/cb1-0", "replica": 1, "result": "awesome!"}
    reply["sig"] = ref.sign(seed, sm.reply_signable(reply)).hex()
    pubs = [b"\0" * 32, ref.public_key(seed), b"\0" * 32, b"\0" * 32]
    other = dict(reply, replica=2)
    assert sm.quorum_result([reply], 0, 4, pubs, ref.verify) == "awesome!"
    assert sm.quorum_result([reply, other], 1, 4, pubs, ref.verify) is None  # one valid vote
    assert sm.execute("anything") == "awesome!"

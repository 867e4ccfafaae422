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


def test_pad_fill_and_rung_histogram_from_sample_launch_lines():
    lines = [
        '{"ts":1.0,"ev":"verify_batch","replica":"service","size":80,"requests":4,"rejected":0,"secs":0.046}',
        '{"ts":1.1,"ev":"verify_batch","replica":"service","size":16,"requests":1,"rejected":7,"secs":0.020}',
        '{"ts":1.2,"ev":"verify_batch","replica":"service","size":300,"requests":9,"rejected":0,"secs":0.080}',
        '{"ts":1.3,"ev":"verify_batch","replica":"service","size":257,"requests":9,"rejected":0,"secs":0.090}',
        '{"ts":1.4,"ev":"verify_batch","replica":"service","size":5000,"requests":30,"rejected":0,"secs":0.300}',
    ]
    ladder = [16, 64, 256, 1024, 4096]
    launches = [json.loads(ln) for ln in lines]
    assert [stats.rung_of(e["size"], ladder) for e in launches] == [256, 16, 1024, 1024, 8192]
    got = stats.launch_stats(launches, ladder)
    assert got["items_per_launch"] == pytest.approx(5653 / 5)
    assert got["pad_fill"] == pytest.approx(5653 / (256 + 16 + 1024 + 1024 + 8192))
    assert got["launch_ms_p50"] == pytest.approx(80.0)
    assert got["window_max_items"] == 5000
    assert got["rungs"]["1024"] == {"launches": 2, "launch_ms_p50": pytest.approx(85.0)}
    run = {"launches": launches, "ladder": ladder}
    assert metric("pad_fill.closed", run) == got["pad_fill"]
    assert metric("items_per_launch.rate", run) == got["items_per_launch"]
    assert metric("launch_ms_p50.closed", {"launches": [], "ladder": ladder}) is None


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


def test_trace_reduction_on_the_small_recorded_trace():
    """tests/data/tiny.xplane.pb (make_tiny_trace.py says what is in it): a
    slice of 90 ms with the device busy for 75; four launches of jit_fn, one
    in no span, one held by two spans, one in one, one across the slice's
    edge; host spans of engine.verify with 10, 80 and 10 items."""
    r = xplane.reduce_trace(TESTS / "data" / "tiny.xplane.pb")
    assert r["devices"] == 1
    # the window is the slice's span, not first event to last: idle at the
    # edges counts, and what lies beyond the edge does not
    assert r["window_s"] == pytest.approx(90e-3) and r["busy_s"] == pytest.approx(75e-3)
    assert [(x["items"], pytest.approx(x["seconds"])) for x in r["launches"]] == [
        (None, 3e-3), (10, 20e-3), (80, 48e-3)]
    assert r["modules"]["jit_fn"] == {"launches": 3, "seconds": pytest.approx(71e-3)}
    assert r["idle"]["inside_an_executable_between_its_operations"] == pytest.approx(1e-3)
    assert r["idle"]["inside_engine.verify_host_staging_or_readback"] == pytest.approx(10e-3)
    assert r["idle"]["outside_engine.verify_waiting_for_a_window"] == pytest.approx(4e-3)
    assert r["ops"][0] == ("while.2", pytest.approx(63e-3))  # 5 ms of it beyond the edge
    assert xplane.device_seconds_by_rung(r, "jit_fn", [16, 64, 256]) == {
        16: [pytest.approx(20e-3)], 256: [pytest.approx(48e-3)]}
    # the window's launches: three at the 16 rung, one at 256, one at 64,
    # which the slice never saw and is left out on both sides
    window = [{"size": 10}] * 3 + [{"size": 80}, {"size": 40}]
    run = {"trace": r, "ladder": [16, 64, 256], "launches": window,
           "peaks": {"int8_ops_per_s": 393e12, "hbm_bytes_per_s": 819e9}}
    assert metric("device_idle_pct.closed", run) == pytest.approx(100 * 15 / 90)
    assert metric("kernel_ms_per_launch.rate", run) == pytest.approx((3 * 20 + 48) / 4)
    ops = 3 * kernel_ops.ed25519_verify(16)["ops"] + kernel_ops.ed25519_verify(256)["ops"]
    assert metric("verify_kernel_roofline.closed", run) == pytest.approx(
        100 * (ops / 393e12) / 108e-3)
    b = xplane.breakdown(r, run["ladder"])
    assert b["device_ops"] == [["while.2", pytest.approx(63e-3)], ["fusion.1", pytest.approx(12e-3)],
                               ["launches_at_16_slots_x1", pytest.approx(20e-3)],
                               ["launches_at_256_slots_x1", pytest.approx(48e-3)]]
    assert len(b["idle_gaps"]) <= 10
    # a reader that finds nothing to read returns nothing, never 0: no trace,
    # or a slice that saw none of the rungs the window's launches ran at
    unseen = dict(run, launches=[{"size": 40}])
    for name in ("kernel_ms_per_launch.closed", "verify_kernel_roofline.rate"):
        assert metric(name, dict(run, trace=None)) is None
        assert metric(name, unseen) is None
    assert metric("device_idle_pct.rate", dict(run, trace=None)) is None


def test_a_launch_belongs_to_the_span_that_holds_it_and_ends_first():
    ms = 1e6  # stamps are nanoseconds
    spans = [(0, 50 * ms, 10), (5 * ms, 120 * ms, 300), (52 * ms, 130 * ms, 40)]
    mods = [("m", 10 * ms, 45 * ms), ("m", 46 * ms, 110 * ms), ("m", 111 * ms, 125 * ms)]
    assert [m[3] for m in xplane.match_launches(mods, spans)] == [10, 300, 40]
    # a window beyond the top rung runs in chunks inside one span
    assert [m[3] for m in xplane.match_launches(
        [("m", 10 * ms, 20 * ms), ("m", 21 * ms, 30 * ms)], [(0, 40 * ms, 5000)])] == [5000, 5000]
    assert xplane.match_launches([("m", 10 * ms, 20 * ms)], [(15 * ms, 40 * ms, 7)])[0][3] is None


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

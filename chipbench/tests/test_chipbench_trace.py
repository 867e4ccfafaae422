"""The traced run's reading on hand-made traces (``data/*.json``, built by
``handtrace.py``): a launch is labelled by the padded shape that RAN, a
split window is as many launches as it had chunks, two windows in flight
keep their own chunks, N device planes are one launch counted per chip, and a
launch that the trace's end cut short is left out.
"""

import sys
from pathlib import Path

import pytest

TESTS = Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
sys.path[:0] = [str(BENCH_DIR), str(TESTS)]

import handtrace  # noqa: E402
import harness  # noqa: E402
import kernel_ops  # noqa: E402
import xplane  # noqa: E402

PEAKS = harness.peaks_for(BENCH_DIR, "tpu", "TPU v5 lite")
INT8 = PEAKS["int8_ops_per_s"]


def read(name, tmp_path, window=None):
    """-> (the reduced trace, a run as the readers take it, whose window's
    launches are the trace's own lines unless given)."""
    t = handtrace.build(name, tmp_path)
    reduced = xplane.reduce_trace(t["path"], t["lines"], t["ladder"], t["slice_end_s"])
    run = {"trace": reduced, "ladder": t["ladder"], "peaks": PEAKS,
           "launches": t["lines"] if window is None else window}
    return reduced, run


def metric(name, run):
    return harness.read_metric(BENCH_DIR, name, run)


def seen(reduced):
    return [(x["slots"], pytest.approx(x["seconds"], abs=1e-6)) for x in reduced["launches"]]


def test_a_launch_of_40_items_on_the_256_slot_shape_is_labelled_256(tmp_path):
    reduced, run = read("small_window", tmp_path)
    assert seen(reduced) == [(256, 5.31e-3)]
    assert [e[0] for e in xplane.breakdown(reduced)["device_ops"]][-1] == "launches_at_256_slots_x1"
    assert metric("kernel_ms_per_launch.closed", run) == pytest.approx(5.31, abs=1e-3)
    assert metric("pad_fill.closed", run) == pytest.approx(40 / 256)
    # the operations are those of the 256 slots that ran, not of the 64 the
    # items would fit
    assert metric("verify_kernel_roofline.closed", run) == pytest.approx(
        100 * kernel_ops.ed25519_verify(256)["ops"] / INT8 / 5.31e-3, rel=1e-3)


def test_a_1100_item_window_is_two_launches_each_with_its_own_device_time(tmp_path):
    reduced, run = read("split_window", tmp_path)
    assert seen(reduced) == [(1024, 13.47e-3), (256, 5.31e-3)]
    names = [e[0] for e in xplane.breakdown(reduced)["device_ops"]]
    assert names[-2:] == ["launches_at_256_slots_x1", "launches_at_1024_slots_x1"]
    # one window, two launches: the mean device time of a launch, and the
    # work of 1,280 slots over the time of both (up to PR 33: 4,096 slots of
    # work over one chunk's time)
    assert metric("kernel_ms_per_launch.closed", run) == pytest.approx((13.47 + 5.31) / 2, abs=1e-3)
    assert metric("verify_kernel_roofline.closed", run) == pytest.approx(
        100 * kernel_ops.ed25519_verify(1280)["ops"] / INT8 / 18.78e-3, rel=1e-3)
    assert metric("pad_fill.closed", run) == pytest.approx(1100 / 1280)


def test_two_windows_in_flight_keep_their_own_chunks(tmp_path):
    reduced, run = read("two_in_flight", tmp_path)
    assert seen(reduced) == [(1024, 13.47e-3), (256, 5.31e-3), (256, 5.31e-3), (1024, 13.47e-3)]
    assert [x["spans"] for x in reduced["launches"]] == [2, 2, 1, 1]
    assert xplane.launches_by_shape(reduced, "jit_fn") == {
        256: [pytest.approx(5.31e-3, abs=1e-6)] * 2, 1024: [pytest.approx(13.47e-3, abs=1e-6)] * 2}
    # the share does not move with how many windows were split: a window of
    # the same lines with every split one left out reads the same
    whole = metric("verify_kernel_roofline.closed", run)
    unsplit = [e for e in run["launches"] if e["chunks"] == 1]
    assert metric("verify_kernel_roofline.closed", dict(run, launches=unsplit)) == pytest.approx(
        whole, rel=0.10)


def test_two_planes_are_one_launch_counted_per_chip(tmp_path):
    one, run_one = read("one_plane", tmp_path)
    two, run_two = read("two_planes", tmp_path)
    assert (one["devices"], one["planes"], two["devices"], two["planes"]) == (1, 1, 2, 2)
    # ONE launch, its device time the longest of its planes; the launch the
    # slice's end cuts on one plane is left out
    assert seen(one) == [(128, 5.0e-3)] and seen(two) == [(256, 5.0e-3)]
    assert two["modules"]["jit_fn"] == {"launches": 1, "seconds": pytest.approx(5.0e-3, abs=1e-6)}
    # busy time is averaged over the planes: 4.9 + 5.0 and 5.0 + 4.9 ms
    assert two["busy_s"] == pytest.approx(9.9e-3, abs=1e-6)
    # the same work a chip (128 rows in 5 ms) reads the same share of ONE
    # chip's peak, not twice it
    share = metric("verify_kernel_roofline.closed", run_one)
    assert share == pytest.approx(100 * kernel_ops.ed25519_verify(128)["ops"] / INT8 / 5.0e-3, rel=1e-3)
    assert metric("verify_kernel_roofline.closed", run_two) == pytest.approx(share, rel=1e-3)
    assert metric("kernel_ms_per_launch.closed", run_two) == pytest.approx(5.0, abs=1e-3)


def test_a_launch_that_the_traces_end_cut_short_is_left_out(tmp_path):
    reduced, run = read("cut_launch", tmp_path)
    # the third event ends inside the slice, 2.19 ms long with four of its
    # executable's ten operations: not a launch of 2.19 ms
    assert seen(reduced) == [(256, 5.32e-3), (256, 5.32e-3)] and reduced["cut"] == 1
    assert reduced["modules"]["jit_fn"] == {"launches": 2, "seconds": pytest.approx(10.64e-3, abs=1e-6)}
    assert metric("kernel_ms_per_launch.closed", run) == pytest.approx(5.32, abs=1e-3)
    assert [e[0] for e in xplane.breakdown(reduced)["device_ops"]][-1] == "launches_at_256_slots_x2"
    # what the device did record of it still counts as busy
    assert reduced["busy_s"] == pytest.approx((5.32 + 5.32 + 2.19) * 1e-3, abs=1e-6)
    for name in ("small_window", "split_window", "two_in_flight", "two_planes"):
        assert read(name, tmp_path)[0]["cut"] == 0


@pytest.mark.parametrize("slots,ms", [(16, 42.50), (64, 42.02), (256, 5.31), (1024, 13.47), (4096, 50.69)])
def test_no_share_of_the_roofline_can_pass_100(slots, ms, tmp_path):
    """The count in kernel_ops.py is a floor of the kernel's work (carries,
    selects and SHA-512 left out) and the peak the chip's highest published
    integer rate, so a share is the least time over a longer one. At the
    device times PERF.md section 5 records a shape, on one plane or on four."""
    need = kernel_ops.ed25519_verify(slots)
    assert need["ops"] / INT8 > need["bytes"] / PEAKS["hbm_bytes_per_s"]  # compute bound
    for planes in (1, 4):
        trace = {"planes": planes, "launches": [
            {"module": "jit_fn", "slots": slots, "seconds": ms * 1e-3 / planes, "spans": 1}]}
        run = {"trace": trace, "ladder": [16, 64, 256, 1024, 4096], "peaks": PEAKS,
               "launches": [{"size": 1, "rung": slots, "chunks": 1}]}
        share = metric("verify_kernel_roofline.closed", run)
        assert share == pytest.approx(100 * need["ops"] / INT8 / (ms * 1e-3), rel=1e-9)
        assert 0 < share < 1.0

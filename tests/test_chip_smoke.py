"""chip_smoke.py's own logic, exercised on the CPU.

The smoke only ever passes on a TPU (pinned at the bottom: it exits
non-zero, fast and without a result line under JAX_PLATFORMS=cpu). Its two
stages are plain functions of a verify-service address, so here they run at
a tiny size against an in-process daemon whose ENGINE is the native C++
pool wearing a counting stub — every check in them (oracle agreement item
by item, coalescing, replica agreement, fallback counts, engine items ==
items sent) is driven for real, and each is shown to fail when it should."""

import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from pbft_tpu import native
from pbft_tpu.net import VerifyServiceDaemon

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402

pytestmark = pytest.mark.skipif(
    not native.available(), reason="no C++ toolchain for pbftd"
)


class _NativeEngine:
    """Stands where the sharded JAX engine stands; verifies on the native
    pool. ``lie`` flips the verdict of one reject class to show the
    smoke's item-by-item comparison has teeth."""

    platform = "tpu"
    device_kind = "native pool behind an engine stub"
    devices_seen = device_count = 1
    warmed_sizes = (16, 64)
    stats = {
        "cold_compile_s": 0.0,
        "warm_load_s": 0.0,
        "serving_table": {"16": 16, "64": 64},  # the stage logs it
        "chunk_plan": {},  # and this
    }

    def __init__(self, lie=None):
        self._lie = lie

    def init_backend(self):
        pass

    def warm(self):
        return self.stats

    def memory_peak_bytes(self):
        return None

    def verify(self, items):
        out = [bool(v) for v in native.verify_batch(items)]
        if self._lie is not None:
            out = [self._lie(item, v) for item, v in zip(items, out)]
        return out


@pytest.fixture
def service(tmp_path):
    """A ready daemon + its per-launch trace; yields a starter so a test
    can choose the engine."""
    started = []

    def start(engine):
        trace = tmp_path / "trace.jsonl"
        daemon = VerifyServiceDaemon(
            backend="jax", engine=engine, trace_path=str(trace)
        ).start(wait_ready=True)
        started.append(daemon)
        return daemon, trace

    yield start
    for daemon in started:
        daemon.stop()


def test_both_stages_pass_when_the_engine_is_honest(service):
    daemon, trace = service(_NativeEngine())
    chip_smoke.device_stage(
        daemon.address, seed=7, ladder=(16, 64), trace_path=trace
    )
    # (The stage itself checked, from the trace, that the four
    # quarter-windows left merged at the top shape.)
    assert daemon.engine_items == 16 + 64 + 3 * 64 + 64
    children = []
    chip_smoke.deployment_stage(
        daemon.address, children, requests=64, clients=4, window=4
    )
    assert len(children) == 5  # four pbftd + the gateway
    assert all(proc.poll() is not None for _, proc in children)
    assert daemon.fallback_items == 0


def test_device_stage_catches_one_wrong_verdict(service):
    """A device that accepts S >= L (everything else right) must fail the
    stage, naming the item's class — a count of rejects would not do."""
    from pbft_tpu.crypto import ref

    def accept_big_s(item, verdict):
        return verdict or int.from_bytes(item[2][32:], "little") >= ref.L

    daemon, trace = service(_NativeEngine(lie=accept_big_s))
    with pytest.raises(chip_smoke.SmokeFailure, match=r"S >= L.*device says True"):
        chip_smoke.device_stage(daemon.address, seed=7, ladder=(16, 64))


def _four_chips(shapes, lie=None):
    """The real engine over four virtual devices, its arithmetic the native
    host verifier's (``_f5_x4_rehearse.host_arithmetic``), saying ``tpu`` so
    that the stage takes it."""
    from _f5_x4_rehearse import host_arithmetic

    from pbft_tpu.net import ShardedVerifyEngine

    class Engine(ShardedVerifyEngine):
        def init_backend(self):
            super().init_backend()
            self.platform = "tpu"
            self.devices_seen = self.device_count

    # 20 ms a launch: the stage's three blocker windows have to hold both
    # launch slots while its four part-windows arrive and queue together.
    return Engine(shapes=shapes, devices=4, kernel=host_arithmetic(lie, delay_s=0.02))


def test_device_stage_plants_every_class_in_every_chips_rows(service, monkeypatch):
    """On a mesh of four a window that fills its shape carries the seven
    rejects and the control in EACH quarter, and its launch line has to say
    that it ran as a quarter of its rows on each of four chips."""
    engine = _four_chips((32, 64))
    daemon, trace = service(engine)
    engine._route({32: 0.001, 64: 0.002})  # smallest-fit, nothing split, whatever this host read
    windows = []
    real = chip_smoke.make_window

    def noting(rng, pool, size, oracle, shards=1):
        items, classes = real(rng, pool, size, oracle, shards)
        windows.append((size, shards, classes))
        return items, classes

    monkeypatch.setattr(chip_smoke, "make_window", noting)
    chip_smoke.device_stage(daemon.address, seed=11, ladder=(32, 64), trace_path=trace)
    # 32 slots are 8 rows a chip: just room for the eight classes; 16-item
    # quarter-windows (merged into the 64 shape) are planted as before.
    assert [(size, shards) for size, shards, _ in windows] == (
        [(32, 4), (64, 4)] + [(64, 4)] * 3 + [(16, 1)] * 4
    )
    for size, shards, classes in windows:
        rows = size // shards
        for shard in range(shards):
            names = [name for pos, name in classes.items() if pos // rows == shard]
            assert len(names) == len(set(names)) == chip_smoke.N_CLASSES
    assert daemon.status_json()["launches_by_rows_per_chip"].keys() == {"8", "16"}


def test_device_stage_catches_a_chip_that_decides_one_class_wrongly(service):
    """The fourth chip's rows alone accept S >= L: a window's few planted
    items need not sit there, one of every class in every chip's rows does."""
    from pbft_tpu.crypto import ref

    def last_chip_accepts_big_s(row, item, verdict):
        # rows 24-31 of the 32-slot executable, 48-63 of the 64-slot one
        return verdict or (row >= 24 and int.from_bytes(item[2][32:], "little") >= ref.L)

    engine = _four_chips((32,), lie=last_chip_accepts_big_s)
    daemon, trace = service(engine)
    with pytest.raises(chip_smoke.SmokeFailure, match=r"rung 32: item (2[4-9]|3[01]) \(S >= L\): device says True"):
        chip_smoke.device_stage(daemon.address, seed=11, ladder=(32,))


def test_deployment_stage_fails_when_the_service_dies_mid_run(
    service, tmp_path, monkeypatch
):
    """verifyd gone during the deployment: the replicas keep committing on
    their host fallback (the guarantee) and the smoke FAILS for it."""
    monkeypatch.setattr(chip_smoke, "OUT_DIR", tmp_path / "out")
    daemon, _ = service(_NativeEngine())
    children = []

    def kill_soon():
        deadline = time.monotonic() + 30
        while daemon.engine_items == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        daemon.stop()

    killer = threading.Thread(target=kill_soon)
    killer.start()
    try:
        with pytest.raises(chip_smoke.SmokeFailure):
            chip_smoke.deployment_stage(
                daemon.address, children, requests=512, clients=4, window=4
            )
    finally:
        killer.join(60)
    assert all(proc.poll() is not None for _, proc in children)


def test_chip_smoke_exits_nonzero_fast_without_a_tpu():
    """The whole script, as the driver runs it, in a sandbox: no TPU, so a
    non-zero exit, no result line, and inside a minute."""
    t0 = time.monotonic()
    out = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py")],
        capture_output=True,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        timeout=120,
    )
    assert out.returncode != 0
    assert time.monotonic() - t0 < 60
    assert "no TPU" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_in_a_directory_fails_without_a_result(tmp_path):
    """The script without the program proves nothing and must say so."""
    import shutil

    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True,
        text=True,
        cwd=tmp_path,
        env=env,
        timeout=60,
    )
    assert out.returncode != 0
    assert out.stdout == ""

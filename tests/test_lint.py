"""Tests for the static-analysis layer itself (ISSUE 8).

Two halves:

1. The clean tree passes every pass (this is the tier-1 wiring for
   scripts/pbft_lint.py — runtime drift fails the build here).
2. Each pass actually TRIPS on its violation class, proven against a
   shadow tree: a copy of exactly the files the passes scan, with one
   deliberate violation injected — a divergent cross-runtime constant, a
   blocking call inside ``async def``, an unregistered metric. The entry
   point must exit nonzero on each.

Plus the @slow sanitizer-matrix arm: scripts/sanitize.py builds the
strict/TSan/ASan+UBSan flavors of core_test + core/race_stress.cc and
must report zero unsuppressed findings.
"""

from __future__ import annotations

import json
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from pbft_tpu import analysis  # noqa: E402
from pbft_tpu.analysis import (  # noqa: E402
    async_blocking,
    constants,
    metrics_lint,
    sockets,
)

LINT = REPO / "scripts" / "pbft_lint.py"


def _shadow_tree(tmp_path: pathlib.Path) -> pathlib.Path:
    """Copy exactly the files the passes scan into a fresh tree."""
    root = tmp_path / "tree"
    for src in analysis.scanned_files(REPO):
        rel = src.relative_to(REPO)
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(src, dst)
    return root


def _run_lint(root: pathlib.Path, passes: str = None):
    cmd = [sys.executable, str(LINT), "--root", str(root)]
    if passes:
        cmd += ["--passes", passes]
    return subprocess.run(cmd, capture_output=True, text=True)


# -- 1. the clean tree -------------------------------------------------------

def test_clean_tree_all_passes():
    results = analysis.run_all(REPO)
    flat = [e for errs in results.values() for e in errs]
    assert flat == [], "\n".join(flat)


def test_entry_point_clean_tree_exit_zero():
    proc = _run_lint(REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all passes clean" in proc.stdout


def test_entry_point_usage():
    proc = _run_lint(REPO, passes="no-such-pass")
    assert proc.returncode == 2


# -- 2. each violation class trips its pass ----------------------------------

def test_divergent_constant_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    msgs = root / "pbft_tpu" / "consensus" / "messages.py"
    text = msgs.read_text()
    assert "WIRE_BINARY_MAGIC = 0xB2" in text
    msgs.write_text(text.replace(
        "WIRE_BINARY_MAGIC = 0xB2", "WIRE_BINARY_MAGIC = 0xB3"))
    errors = constants.check(root)
    assert any("wire binary magic" in e for e in errors), errors
    proc = _run_lint(root, passes="constants")
    assert proc.returncode == 1
    assert "wire binary magic" in proc.stdout


def test_divergent_protocol_version_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    sec = root / "pbft_tpu" / "net" / "secure.py"
    text = sec.read_text()
    assert 'PROTOCOL_VERSION = "pbft-tpu/1.3.0"' in text
    sec.write_text(text.replace(
        'PROTOCOL_VERSION = "pbft-tpu/1.3.0"',
        'PROTOCOL_VERSION = "pbft-tpu/1.4.0"'))
    errors = constants.check(root)
    assert any("protocol version (current)" in e for e in errors), errors


def test_divergent_mac_constants_trip(tmp_path):
    """ISSUE 14 pairs: a drifted MAC tag length, domain label, or frame
    code each fails the build — one byte of drift and the
    reference's MAC vectors stop matching pbftd's."""
    root = _shadow_tree(tmp_path)
    sec = root / "pbft_tpu" / "net" / "secure.py"
    text = sec.read_text()
    assert "MAC_TAG_LEN = 16" in text
    sec.write_text(text.replace("MAC_TAG_LEN = 16", "MAC_TAG_LEN = 12"))
    errors = constants.check(root)
    assert any("MAC tag length" in e for e in errors), errors

    root2 = _shadow_tree(tmp_path / "b")
    sec2 = root2 / "pbft_tpu" / "net" / "secure.py"
    sec2.write_text(sec2.read_text().replace(
        'MAC_CONTEXT = "pbft-tpu-auth1|"', 'MAC_CONTEXT = "pbft-tpu-auth2|"'))
    errors = constants.check(root2)
    assert any("MAC domain-separation label" in e for e in errors), errors

    root3 = _shadow_tree(tmp_path / "c")
    msgs = root3 / "pbft_tpu" / "consensus" / "messages.py"
    msgs.write_text(msgs.read_text().replace(
        "_BIN_PREPARE_MAC = 0x13", "_BIN_PREPARE_MAC = 0x17"))
    errors = constants.check(root3)
    assert any("binary tag: prepare (MAC)" in e for e in errors), errors


def test_divergent_tentative_field_trips(tmp_path):
    """The tentative-reply member name is SIGNED content: a renamed
    field forks every tentative reply's signable bytes across runtimes."""
    root = _shadow_tree(tmp_path)
    msgs = root / "pbft_tpu" / "consensus" / "messages.py"
    text = msgs.read_text()
    assert 'TENTATIVE_FIELD = "tentative"' in text
    msgs.write_text(text.replace(
        'TENTATIVE_FIELD = "tentative"', 'TENTATIVE_FIELD = "tent"'))
    errors = constants.check(root)
    assert any("tentative-reply field tag" in e for e in errors), errors


def test_divergent_fastpath_default_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    cfg = root / "pbft_tpu" / "consensus" / "config.py"
    text = cfg.read_text()
    assert 'fastpath: str = "sig"' in text
    cfg.write_text(text.replace(
        'fastpath: str = "sig"', 'fastpath: str = "mac"'))
    errors = constants.check(root)
    assert any("ClusterConfig default: fastpath" in e for e in errors), errors


def test_divergent_config_default_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    cfg = root / "pbft_tpu" / "consensus" / "config.py"
    cfg.write_text(cfg.read_text().replace(
        "watermark_window: int = 256", "watermark_window: int = 128"))
    errors = constants.check(root)
    assert any("watermark_window" in e for e in errors), errors


def test_divergent_wal_constants_trip(tmp_path):
    """ISSUE 15 pairs: a drifted WAL magic, record tag, or wal_fsync
    config default each fails the build — the on-disk format is the
    recovery contract between pbftd and the reference (a pbftd-written
    log must replay in the Python tooling byte-for-byte, and a sparse
    network.json must mean fsync-on to both)."""
    root = _shadow_tree(tmp_path)
    w = root / "pbft_tpu" / "consensus" / "wal.py"
    text = w.read_text()
    assert 'WAL_MAGIC = b"PBFTWAL1"' in text
    w.write_text(text.replace(
        'WAL_MAGIC = b"PBFTWAL1"', 'WAL_MAGIC = b"PBFTWAL2"'))
    errors = constants.check(root)
    assert any("WAL file magic" in e for e in errors), errors

    root2 = _shadow_tree(tmp_path / "b")
    hdr = root2 / "core" / "wal.h"
    hdr.write_text(hdr.read_text().replace(
        "kWalRecCheckpoint = 0x03", "kWalRecCheckpoint = 0x04"))
    errors = constants.check(root2)
    assert any("WAL record tag: checkpoint" in e for e in errors), errors

    root3 = _shadow_tree(tmp_path / "c")
    cfg = root3 / "pbft_tpu" / "consensus" / "config.py"
    cfg.write_text(cfg.read_text().replace(
        "wal_fsync: bool = True", "wal_fsync: bool = False"))
    errors = constants.check(root3)
    assert any(
        "ClusterConfig default: wal_fsync" in e for e in errors
    ), errors


def test_blocking_call_in_async_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    fixture = root / "pbft_tpu" / "net" / "fixture_blocking.py"
    fixture.write_text(
        "import time\n"
        "\n"
        "\n"
        "async def stall_the_loop():\n"
        "    time.sleep(1)  # the violation\n"
    )
    errors = async_blocking.check(root)
    assert any("time.sleep" in e and "stall_the_loop" in e for e in errors), (
        errors)
    proc = _run_lint(root, passes="async-blocking")
    assert proc.returncode == 1
    assert "time.sleep" in proc.stdout


def test_blocking_socket_and_subprocess_trip(tmp_path):
    root = _shadow_tree(tmp_path)
    fixture = root / "pbft_tpu" / "net" / "fixture_blocking2.py"
    fixture.write_text(
        "import subprocess\n"
        "\n"
        "\n"
        "async def bad_subprocess():\n"
        "    subprocess.run(['true'])\n"
        "\n"
        "\n"
        "async def bad_socket(sock):\n"
        "    return sock.recv(4096)\n"
        "\n"
        "\n"
        "async def fine(loop, sock):\n"
        "    # passing the callable (not calling it) is loop-safe\n"
        "    await loop.run_in_executor(None, sock.close)\n"
        "\n"
        "\n"
        "async def nested_sync_ok():\n"
        "    def helper():\n"
        "        import time\n"
        "        time.sleep(0)  # runs wherever it's called, not the loop\n"
        "    return helper\n"
    )
    errors = async_blocking.check(root)
    assert any("subprocess.run" in e for e in errors), errors
    assert any("sock.recv" in e for e in errors), errors
    assert not any("nested_sync_ok" in e for e in errors), errors
    assert not any("'fine'" in e for e in errors), errors


def test_unregistered_metric_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    fixture = root / "pbft_tpu" / "fixture_metrics.py"
    fixture.write_text(
        "def emit(registry):\n"
        "    registry.counter('pbft_totally_unregistered_total').inc()\n"
    )
    errors = metrics_lint.check(root)
    assert any("pbft_totally_unregistered_total" in e for e in errors), errors
    proc = _run_lint(root, passes="metrics")
    assert proc.returncode == 1
    assert "pbft_totally_unregistered_total" in proc.stdout


def test_unregistered_metric_in_emitter_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    gateway = root / "pbft_tpu" / "net" / "gateway.py"
    text = gateway.read_text()
    anchor = '"pbft_gateway_writes_total"'
    assert anchor in text
    gateway.write_text(text.replace(anchor, '"pbft_gateway_writes_renamed_total"', 1))
    errors = metrics_lint.check(root)
    assert any("pbft_gateway_writes_renamed_total" in e for e in errors), errors
    # The manifest's own name is then recorded by nobody: the other half.
    assert any("'pbft_gateway_writes_total' is never recorded" in e for e in errors), errors


def test_wrong_metric_kind_trips(tmp_path):
    root = _shadow_tree(tmp_path)
    fixture = root / "pbft_tpu" / "fixture_kind.py"
    fixture.write_text(
        "def emit(registry):\n"
        "    registry.gauge('pbft_executed_total').set(1)\n"  # it's a counter
    )
    errors = metrics_lint.check(root)
    assert any("pbft_executed_total" in e and "gauge" in e for e in errors), (
        errors)


def test_untuned_python_dial_trips(tmp_path):
    """sockets pass (ISSUE 10): stripping the TCP_NODELAY setsockopt from
    the client's dial helper trips the socket-discipline lint."""
    root = _shadow_tree(tmp_path)
    cl = root / "pbft_tpu" / "net" / "client.py"
    text = cl.read_text()
    assert "TCP_NODELAY" in text
    cl.write_text(
        "\n".join(
            line
            for line in text.splitlines()
            if "TCP_NODELAY" not in line
        )
    )
    errors = sockets.check(root)
    assert any("client.py" in e and "TCP_NODELAY" in e for e in errors), errors
    proc = _run_lint(root, passes="sockets")
    assert proc.returncode == 1


def test_untuned_cxx_socket_trips(tmp_path):
    """sockets pass, C++ side: a stream socket() site whose tuning call
    is stripped fails the lint."""
    root = _shadow_tree(tmp_path)
    net = root / "core" / "net.cc"
    text = net.read_text()
    assert "tune_stream_socket(fd);" in text
    # Strip the tune call inside dial_socket (the first occurrence after
    # the AF_INET/SOCK_STREAM creation) — a new dial site forgetting the
    # call looks exactly like this.
    net.write_text(text.replace("  tune_stream_socket(fd);\n", "", 1))
    errors = sockets.check(root)
    assert any("net.cc" in e for e in errors), errors


def test_divergent_gateway_prefix_trips(tmp_path):
    """constants pass: the gateway routing-token prefix is a cross-runtime
    switch (reply fan-back vs dial-back) — drift fails the build."""
    root = _shadow_tree(tmp_path)
    gw = root / "pbft_tpu" / "net" / "gateway.py"
    gw.write_text(gw.read_text().replace(
        'GATEWAY_CLIENT_PREFIX = "gw/"', 'GATEWAY_CLIENT_PREFIX = "gx/"'))
    errors = constants.check(root)
    assert any("gateway client-token prefix" in e for e in errors), errors


def test_divergent_health_constants_trip(tmp_path):
    """ISSUE 16 pairs: the health-document version, the silent-stall
    threshold, and the snapshot cadence are operational contracts shared
    by pbftd's /status route, the detector library, and the pbft_top /
    endurance tooling — drift in any of them makes a gate judge one
    runtime by the other's thresholds."""
    root = _shadow_tree(tmp_path)
    ts = root / "pbft_tpu" / "utils" / "trace_schema.py"
    text = ts.read_text()
    assert "HEALTH_DOC_VERSION = 1" in text
    ts.write_text(text.replace(
        "HEALTH_DOC_VERSION = 1", "HEALTH_DOC_VERSION = 2"))
    errors = constants.check(root)
    assert any("health document version" in e for e in errors), errors

    root2 = _shadow_tree(tmp_path / "b")
    hp = root2 / "pbft_tpu" / "analysis" / "health.py"
    text = hp.read_text()
    assert "HEALTH_STALL_SECONDS = 5" in text
    hp.write_text(text.replace(
        "HEALTH_STALL_SECONDS = 5", "HEALTH_STALL_SECONDS = 9"))
    errors = constants.check(root2)
    assert any("health stall threshold seconds" in e for e in errors), errors

    root3 = _shadow_tree(tmp_path / "c")
    hdr = root3 / "core" / "net.h"
    text = hdr.read_text()
    assert "kHealthSnapshotIntervalS = 2" in text
    hdr.write_text(text.replace(
        "kHealthSnapshotIntervalS = 2", "kHealthSnapshotIntervalS = 4"))
    errors = constants.check(root3)
    assert any(
        "health snapshot interval seconds" in e for e in errors
    ), errors


def test_missing_health_gauge_in_cxx_table_trips(tmp_path):
    """A health gauge dropped from metrics.cc's kGaugeNames (so pbftd
    would stop exporting it) fails the manifest cross-check."""
    root = _shadow_tree(tmp_path)
    mc = root / "core" / "metrics.cc"
    text = mc.read_text()
    assert '"pbft_inbox_depth",' in text
    mc.write_text(text.replace('    "pbft_inbox_depth",\n', '', 1))
    errors = metrics_lint.check(root)
    assert any(
        "kGaugeNames" in e and "pbft_inbox_depth" in e for e in errors
    ), errors


def test_scanned_files_exist():
    """The shadow-tree contract: every scanned path exists in the repo
    (a rename must update the pass specs, not silently skip)."""
    for path in analysis.scanned_files(REPO):
        assert path.exists(), f"scanned file missing: {path}"


# -- 3. the sanitizer matrix (@slow) ------------------------------------------

@pytest.mark.slow
def test_sanitizer_matrix_clean(tmp_path):
    """Build + run the full flavor matrix (strict, TSan, ASan+UBSan) of
    core_test and core/race_stress.cc: zero unsuppressed findings and
    zero test failures, with the machine-readable summary intact."""
    summary_path = tmp_path / "sanitize_summary.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "sanitize.py"),
         "--json", str(summary_path)],
        capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    summary = json.loads(summary_path.read_text())
    assert summary["ok"]
    flavors = {f["flavor"] for f in summary["flavors"]}
    assert flavors == {"strict", "tsan", "asan-ubsan"}
    for flavor in summary["flavors"]:
        assert flavor["findings"] == 0, flavor
        for name, binary in flavor["binaries"].items():
            assert binary["exit"] == 0, (flavor["flavor"], name, binary)

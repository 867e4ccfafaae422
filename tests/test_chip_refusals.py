"""The ways the served path could quietly run on the CPU instead of the
chip, each pinned to fail loudly (all on CPU, no device needed): bench.py's
default arm, the native build's staleness check, the one-process-per-chip
launcher rule, and that no switch chooses a multiply path. (verifyd's own refusals live in
test_service_coalesce.py, chip_smoke.py's in test_chip_smoke.py.)"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from pbft_tpu import native

REPO = Path(__file__).resolve().parent.parent


def test_bench_default_arm_exits_1_with_an_error_line_without_a_tpu():
    """No TPU service comes up -> one error line, exit 1. Never a CPU
    number under the device metric's name."""
    from pbft_tpu.net.launcher import free_ports

    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PBFT_VERIFY_SERVICE=f"127.0.0.1:{free_ports(1)[0]}",  # nobody there
        PBFT_SERVICE_WARM_BUDGET_S="60",
    )
    for arm in ("PBFT_BENCH_NATIVE", "PBFT_BENCH_CPU", "PBFT_BENCH_CONSENSUS"):
        env.pop(arm, None)
    out = subprocess.run(
        [sys.executable, str(REPO / "bench.py")],
        capture_output=True,
        text=True,
        env=env,
        timeout=120,
    )
    assert out.returncode == 1, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert len(lines) == 1
    result = json.loads(lines[0])
    assert result["value"] == 0.0 and "backend" not in result
    assert "no TPU" in result["error"]


# -- native.build(): decided from the sources, not from a file existing ------


@pytest.fixture
def fake_tree(tmp_path, monkeypatch):
    """A stand-in checkout (core/ with two sources, an up-to-date
    build-core/) and a recording builder in place of cmake/g++."""
    root = tmp_path / "checkout"
    (root / "core").mkdir(parents=True)
    (root / "core" / "net.cc").write_text("int net;\n")
    (root / "core" / "CMakeLists.txt").write_text("project(x)\n")
    build_dir = root / "build-core"
    monkeypatch.setattr(native, "_REPO_ROOT", root)
    monkeypatch.setattr(native, "_BUILD_DIR", build_dir)
    monkeypatch.setattr(native, "_LIB_PATH", build_dir / "libpbftcore.so")
    builds = []

    def fake_build():
        builds.append(1)
        build_dir.mkdir(exist_ok=True)
        for name in native._ARTIFACTS:
            (build_dir / name).write_text("binary")

    monkeypatch.setattr(native, "_build_direct", fake_build)
    monkeypatch.setattr(native.shutil, "which", lambda tool: None)  # -> direct
    native.build()
    assert len(builds) == 1
    return root, build_dir, builds


def test_build_is_a_noop_while_sources_and_artifacts_are_current(fake_tree):
    _, _, builds = fake_tree
    native.build()
    native.build()
    assert len(builds) == 1


def test_build_rebuilds_when_a_core_source_changed(fake_tree):
    """A changed core/*.cc can not be served by the previous pbftd."""
    root, _, builds = fake_tree
    (root / "core" / "net.cc").write_text("int net; int newer;\n")
    native.build()
    assert len(builds) == 2
    native.build()
    assert len(builds) == 2


def test_build_rebuilds_when_pbftd_is_missing(fake_tree):
    """The library alone proves nothing: LocalCluster needs pbftd."""
    _, build_dir, builds = fake_tree
    (build_dir / "pbftd").unlink()
    native.build()
    assert len(builds) == 2 and (build_dir / "pbftd").exists()


def test_build_does_not_trust_a_build_dir_from_another_path(
    fake_tree, tmp_path, monkeypatch
):
    """The tree copied elsewhere WITH its build directory (what the chip
    tool does): same sources, but the artifacts and the CMake cache name
    the old path. Rebuilt, and cmake starts from a clean directory."""
    import shutil

    root, build_dir, builds = fake_tree
    (build_dir / "CMakeCache.txt").write_text(
        f"CMAKE_HOME_DIRECTORY:INTERNAL={root / 'core'}\n"
        f"CMAKE_CACHEFILE_DIR:INTERNAL={build_dir}\n"
    )
    moved = tmp_path / "elsewhere"
    shutil.copytree(root, moved)
    monkeypatch.setattr(native, "_REPO_ROOT", moved)
    monkeypatch.setattr(native, "_BUILD_DIR", moved / "build-core")
    monkeypatch.setattr(native, "_LIB_PATH", moved / "build-core" / "libpbftcore.so")
    # This time through the cmake path, with the real stale-cache check.
    monkeypatch.setattr(native.shutil, "which", lambda tool: f"/usr/bin/{tool}")
    steps = []

    def fake_step(cmd):
        steps.append((cmd[:2], (moved / "build-core" / "CMakeCache.txt").exists()))
        (moved / "build-core").mkdir(exist_ok=True)
        for name in native._ARTIFACTS:
            (moved / "build-core" / name).write_text("binary")

    monkeypatch.setattr(native, "_run_build_step", fake_step)
    native.build()
    assert [s[0] for s in steps] == [["cmake", "-S"], ["cmake", "--build"]]
    assert steps[0][1] is False  # the foreign cache was gone before configure


def test_a_failed_build_shows_the_compilers_words(monkeypatch, tmp_path):
    """build() used to run the compiler with capture_output and drop what
    it said; available() then read the failure as "not built"."""
    with pytest.raises(native.NativeBuildError, match="(?s)exit 3.*boom on line 7"):
        native._run_build_step(
            [sys.executable, "-c",
             "import sys; sys.stderr.write('boom on line 7'); sys.exit(3)"]
        )
    # ...and available() does not turn that into False.
    monkeypatch.setattr(native, "_lib", None)

    def broken():
        raise native.NativeBuildError("compiler said no")

    monkeypatch.setattr(native, "build", broken)
    with pytest.raises(native.NativeBuildError):
        native.available()


# -- one process per chip ------------------------------------------------------


def test_launcher_has_no_in_process_way_onto_the_chip():
    """A replica is pbftd, which never touches JAX: the one way onto the
    chip is verifyd's address. ``verifier="jax"`` (the in-process arm of the
    Python replica that is gone) is refused with a pointer at verifyd,
    whatever JAX_PLATFORMS says."""
    from pbft_tpu.net import LocalCluster

    with pytest.raises(ValueError, match="verifyd"):
        LocalCluster(n=4, verifier="jax")
    LocalCluster(n=4, verifier="127.0.0.1:7600")  # verifyd: fine
    LocalCluster(n=4, verifier="cpu")  # the native pool: fine


# -- no switch chooses a multiply path ------------------------------------------


def test_no_switch_chooses_a_multiply_path(monkeypatch):
    """Up to PR 42 ``PBFT_PALLAS=1`` chose the Pallas kernels and was an
    error off the TPU. Since PR 43 nothing in the environment chooses: the
    VMEM chains are taken by the backend and the rows a chip alone
    (``ed25519.chains_for``), so the old switches are inert, on a CPU every
    shape runs the XLA chains, and on a TPU ``PBFT_FIELD_MUL`` is not read."""
    from pbft_tpu.crypto import ed25519, field
    import jax.numpy as jnp

    for name in ("PBFT_PALLAS", "PBFT_PALLAS_INTERPRET"):
        monkeypatch.setenv(name, "1")
    monkeypatch.setenv("PBFT_PALLAS_TB", "8")
    assert ed25519._use_pallas(jnp.zeros((4096, 32), jnp.uint8)) is False
    assert ed25519.chains_for(4096) == "xla" and ed25519.chains_for(4096, "tpu") == "vmem"
    # The XLA multiply: PBFT_FIELD_MUL may name either lowering off the TPU
    # (the CPU dry run asks for conv); on a TPU backend it is conv, unasked.
    import jax

    monkeypatch.setenv("PBFT_FIELD_MUL", "conv")
    assert field._pick_mul() is field._mul_conv
    monkeypatch.setenv("PBFT_FIELD_MUL", "schoolbook")
    assert field._pick_mul() is field._mul_schoolbook
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert field._pick_mul() is field._mul_conv
    monkeypatch.delenv("PBFT_FIELD_MUL")
    assert field._pick_mul() is field._mul_conv
    # and no file of the package or the scripts reads the old switches
    root = Path(__file__).resolve().parent.parent
    for path in [*root.glob("pbft_tpu/**/*.py"), *root.glob("scripts/*.py"),
                 root / "chip_smoke.py", root / "bench.py", root / "__graft_entry__.py"]:
        assert "PBFT_PALLAS" not in path.read_text(), path

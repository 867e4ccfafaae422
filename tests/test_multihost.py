"""Multi-host distributed verification: 2 REAL jax processes over gloo.

Exercises the non-degenerate branches of pbft_tpu/parallel/multihost.py
(jax.distributed.initialize, make_array_from_process_local_data, the psum
crossing a process boundary) that the single-process tests cannot reach.
Each process is one "host" with 4 virtual CPU devices; the 8-device mesh
spans both, and both must read back identical globally-replicated quorum
verdicts.
"""

import json
import os
import socket
import subprocess
import sys
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # two cold kernel compiles in subprocesses

_WORKER = Path(__file__).parent / "multihost_worker.py"
_REPO = str(Path(__file__).resolve().parent.parent)


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_quorum_certify_agrees(tmp_path):
    port = _free_port()
    # The workers place their own compile cache (force_cpu ->
    # utils/cache.configure_compile_cache).
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    # stdout/stderr go to FILES, not pipes: a worker spewing more than a
    # pipe buffer of JAX warnings before the gloo barrier would otherwise
    # block on write while the sibling blocks at the barrier.
    procs, logs = [], []
    for pid in range(2):
        out = open(tmp_path / f"worker-{pid}.out", "w+")
        err = open(tmp_path / f"worker-{pid}.err", "w+")
        logs.append((out, err))
        procs.append(
            subprocess.Popen(
                [sys.executable, str(_WORKER), str(port), str(pid), "2"],
                stdout=out,
                stderr=err,
                env=env,
                text=True,
            )
        )
    outs = []
    try:
        for p, (out, err) in zip(procs, logs):
            rc = p.wait(timeout=600)
            out.seek(0), err.seek(0)
            assert rc == 0, f"worker failed:\n{err.read()[-4000:]}"
            outs.append(json.loads(out.read().strip().splitlines()[-1]))
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        for out, err in logs:
            out.close(), err.close()

    for o in outs:
        assert o["devices"] == 8  # the mesh spans both processes
        # Rounds 0,1,3: 4 valid sigs each (>= threshold 3). Round 2: two
        # corrupted signatures leave 2 valid (< 3) -> not certified.
        assert o["counts"] == [4, 4, 2, 4]
        assert o["certified"] == [True, True, False, True]
    # Both hosts read back the SAME replicated verdicts.
    assert outs[0]["counts"] == outs[1]["counts"]
    assert outs[0]["certified"] == outs[1]["certified"]

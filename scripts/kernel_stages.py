#!/usr/bin/env python
"""kernel_stages — what one launch of the verify kernel costs on the local
device(s), and where inside the launch the time goes.

For each ``--slots`` shape the engine's own executable is built over the
local devices (``ShardedVerifyEngine``: the mesh, ``lower_sharded``, one
``jit_fn`` a shape) and timed by ``ShardedVerifyEngine._measure``'s method:
the all-pad window through ``pad_batch`` and the executable on the host
block, once untimed, then the least of a few timed launches on the host's
clock. With ``--trace`` a profiler session is held round ``--launches`` more
launches of each shape and the device time is read from the session's
``.xplane.pb``: a launch's whole time from the ``XLA Modules`` line, and
its split by ``jax.named_scope`` (``sha512_challenge`` / ``decompress`` /
``ladder`` / ``compress``, the four stages ``ed25519.verify_kernel`` names)
from the ``tf_op`` stat of each operation on the ``XLA Ops`` line: the union
of the intervals of a stage's operations, a launch.

No benchmark cell runs this. It is the table a kernel change starts from
(``PERF.md`` §5 "Kernel stages"):

    python scripts/kernel_stages.py --slots 256 1024 4096 --trace
    python scripts/kernel_stages.py --read chiprun_out/kernel_stages/4096

One JSON line a shape on stdout: ``slots``, ``devices``, ``rows_per_device``,
``chains`` (``vmem`` / ``xla``: read from the executable), ``compile_s``,
``cache_hit``, ``launch_ms`` (host clock) and, traced, ``device_ms`` and
``stage_ms``. Times are of the device the process finds: on a CPU they are
a CPU's, and the line says so (``platform``).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

STAGES = ("sha512_challenge", "decompress", "ladder", "compress")
OUT_DIR = REPO / "chiprun_out" / "kernel_stages"


def _union_ps(intervals) -> int:
    total, at = 0, None
    for start, end in sorted(intervals):
        if at is None or start > at:
            total += end - start
            at = end
        elif end > at:
            total += end - at
            at = end
    return total


def read_stages(trace_dir) -> dict:
    """{"launches": n, "device_ms": a launch, "stage_ms": {stage: a launch,
    "other": what no stage covers}} from the newest ``.xplane.pb`` under ``trace_dir``: the
    first device plane's ``jit_fn`` launches and the operations inside them.
    Reads the raw protobuf: ``tf_op`` lives in the event METADATA's stats,
    which ``jax.profiler.ProfileData`` does not show."""
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    files = sorted(pathlib.Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    space = xplane_pb2.XSpace()
    space.ParseFromString(files[-1].read_bytes())
    plane = next(p for p in space.planes if p.name.startswith("/device:TPU:"))
    stat_name = {i: m.name for i, m in plane.stat_metadata.items()}

    def tf_op(meta) -> str:
        for st in meta.stats:
            if stat_name.get(st.metadata_id) == "tf_op":
                return st.str_value or stat_name.get(st.ref_value, "")
        return ""

    stage_of = {}
    for i, meta in plane.event_metadata.items():
        parts = set(tf_op(meta).split("/"))
        stage_of[i] = next((s for s in STAGES if s in parts), None)
    lines = {line.name: line for line in plane.lines}
    launches = [
        (e.offset_ps, e.offset_ps + e.duration_ps)
        for e in lines["XLA Modules"].events
        if plane.event_metadata[e.metadata_id].name.startswith("jit_fn")
    ]
    by_stage: dict = {s: [] for s in STAGES}
    for e in lines["XLA Ops"].events:
        start, end = e.offset_ps, e.offset_ps + e.duration_ps
        stage = stage_of[e.metadata_id]
        if stage and any(lo <= start and end <= hi for lo, hi in launches):
            by_stage[stage].append((start, end))
    n = max(1, len(launches))
    ms = 1e-9
    whole = sum(hi - lo for lo, hi in launches)
    named = _union_ps([iv for ivs in by_stage.values() for iv in ivs])
    stage_ms = {s: round(_union_ps(iv) * ms / n, 4) for s, iv in by_stage.items()}
    # What no stage's operations cover: the glue between them and the gaps.
    stage_ms["other"] = round((whole - named) * ms / n, 4)
    return {"launches": len(launches), "device_ms": round(whole * ms / n, 4), "stage_ms": stage_ms}


def measure(slots: int, trace: bool, launches: int) -> dict:
    import numpy as np
    import jax

    from pbft_tpu.crypto.batch import pad_batch
    from pbft_tpu.net.verify_service import ShardedVerifyEngine

    engine = ShardedVerifyEngine(shapes=[slots])
    stats = engine.warm()  # compiles, self-tests and times the shape (_measure)
    shape = stats["per_shape"][0]
    out = {
        "slots": shape["size"],
        "platform": engine.platform,
        "device_kind": engine.device_kind,
        "devices": len(shape["devices"]),
        "rows_per_device": shape["rows_per_device"],
        "chains": shape["chains"],
        "compile_s": shape["seconds"],
        "cache_hit": shape["cache_hit"],
        "launch_ms": round(1e3 * shape["launch_s"], 4),
    }
    if trace:
        compiled = engine._compiled[shape["size"]]
        trace_dir = OUT_DIR / str(shape["size"])
        jax.profiler.start_trace(str(trace_dir))
        for _ in range(launches):
            np.asarray(compiled(pad_batch([], shape["size"])[0]))
        jax.profiler.stop_trace()
        # In a child: TensorFlow's protobuf module stays out of the process
        # that holds the chip.
        read = subprocess.run(
            [sys.executable, __file__, "--read", str(trace_dir)],
            capture_output=True, text=True,
        )
        if read.returncode:
            out["trace_error"] = read.stderr.strip()[-400:]
        else:
            out.update(json.loads(read.stdout))
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--slots", type=int, nargs="+", default=[4096])
    parser.add_argument("--trace", action="store_true")
    parser.add_argument("--launches", type=int, default=4, help="traced launches a shape")
    parser.add_argument("--read", metavar="DIR", help="reduce a trace written earlier")
    args = parser.parse_args(argv)
    if args.read:
        print(json.dumps(read_stages(args.read)))
        return 0
    for slots in args.slots:
        print(json.dumps(measure(slots, args.trace, args.launches)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""What a traced slice costs, piece by piece, in one process on the chip.

    trace_cost.py [--shapes 256,1024] [--windows 40,40,300,1100] [--variants a,b,..] [--load N] [--through]

Warms the program's own ``ShardedVerifyEngine`` on a few shapes, then for
every variant of the profiler's options holds a session open round the same
few ``engine.verify`` calls and times the pieces apart: starting the session,
``stop()`` (the device's events collected into an xspace and serialised),
writing those bytes, ``export()`` (what ``stop_and_export`` adds: the
TensorBoard directory with its ``trace.json.gz``), and the benchmark's own
reading of the file. Each variant's line also counts the events a plane and
line, so that the cost can be written as seconds an executable launch a
device plane (``PERF.md`` section 3) and a cell reckoned before it is asked
for. ``--load N``: N threads go on verifying 40-item windows while the
session is stopped, as a serving ``verifyd`` does; with ``--through`` they
start before the slice does, so that a launch is in flight where the slice
ends, and each variant's line says where every executable's event on the
first device plane lies against the slice's end and how many operations it
holds (what the trace's end does to a launch: ``xplane.WHOLE``). Nothing here
is run by a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
ROOT = HERE.parent
sys.path[:0] = [str(HERE), str(ROOT)]

import xplane  # noqa: E402

VARIANTS = {
    # what verifyd_wrap.py asks for (the library's default mode) and two of
    # the coarser modes: the same events on the same lines, and ``stop()``
    # takes 3 to 10 s for five executables under any of them, from one
    # session to the next more than from one mode to the next
    "host2": {"host_tracer_level": 2},
    "only_xla": {"host_tracer_level": 2, "advanced": {"tpu_trace_mode": "TRACE_ONLY_XLA"}},
    "compute": {"host_tracer_level": 2, "advanced": {"tpu_trace_mode": "TRACE_COMPUTE"}},
}


def options_of(spec: dict):
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = spec.get("host_tracer_level", 2)
    if "advanced" in spec:
        options.advanced_configuration = spec["advanced"]
    return options


def census(blob: bytes) -> dict:
    """{plane: {line: events}} of a serialised xspace, device planes and the
    host lines that hold the benchmark's spans."""
    from jax.profiler import ProfileData

    out: dict = {}
    for plane in ProfileData.from_serialized_xspace(blob).planes:
        lines = {}
        for line in plane.lines:
            n = sum(1 for _ in line.events)
            if xplane.DEVICE_PLANE.fullmatch(plane.name) or n:
                lines[line.name] = lines.get(line.name, 0) + n
        if xplane.DEVICE_PLANE.fullmatch(plane.name):
            out[plane.name] = lines
        else:
            out[plane.name] = {"lines": len(lines), "events": sum(lines.values())}
    return out


def at_the_end(path) -> dict:
    """Every executable's event on the first device plane, as milliseconds
    before the slice's end (start, end), its length and the operations that
    start inside it; and how long before the slice's end the last operation
    the device recorded ends."""
    from bisect import bisect_left

    _, hi, devices, _ = xplane._read_slice(path)
    _, ops, modules = devices[0]
    starts = sorted(s for _, s, _ in ops)
    ms = 1e-6
    return {
        "last_operation_ends_ms_before_the_slice_does": (hi - max(e for _, _, e in ops)) * ms,
        "executables": [
            [name, (hi - s) * ms, (hi - e) * ms, (e - s) * ms,
             bisect_left(starts, e) - bisect_left(starts, s)]
            for name, s, e in modules
        ],
    }


def one(name: str, spec: dict, engine, windows: list, out_dir: Path, export: bool,
        load: int = 0, through: bool = False) -> dict:
    import jax
    from jax._src.lib import _profiler

    rec: dict = {"variant": name, "spec": spec}
    mark = time.monotonic()
    session = _profiler.ProfilerSession(options_of(spec))
    rec["start_s"] = time.monotonic() - mark
    serving = threading.Event()
    served = [0] * load

    def serve(k: int) -> None:
        while not serving.is_set():
            engine.verify(windows[0])
            served[k] += 1
            time.sleep(0.0 if through else 0.01)

    threads = [threading.Thread(target=serve, args=(k,), daemon=True) for k in range(load)]
    if through:
        for t in threads:
            t.start()
    mark = time.monotonic()
    with jax.profiler.TraceAnnotation(xplane.SLICE_SPAN):
        for items in windows:
            with jax.profiler.TraceAnnotation(xplane.ENGINE_SPAN, items=len(items)):
                engine.verify(items)
    rec["slice_s"] = time.monotonic() - mark
    if not through:
        for t in threads:
            t.start()
    mark = time.monotonic()
    blob = session.stop()
    rec["stop_s"] = time.monotonic() - mark
    serving.set()
    for t in threads:
        t.join(30)
    rec["launches_while_stopping"] = sum(served)
    rec["xspace_bytes"] = len(blob)
    mark = time.monotonic()
    path = xplane.write_xspace(blob, out_dir / name)
    rec["write_s"] = time.monotonic() - mark
    if export:
        mark = time.monotonic()
        session.export(blob, str(out_dir / f"{name}.export"))
        rec["export_s"] = time.monotonic() - mark
        shutil.rmtree(out_dir / f"{name}.export")  # 100 MB and more, read by nobody
    mark = time.monotonic()
    rec["census"] = census(blob)
    rec["census_s"] = time.monotonic() - mark
    mark = time.monotonic()
    try:
        reduced = xplane.reduce_trace(path)
        rec["read"] = {
            "devices": reduced["devices"], "window_s": reduced["window_s"],
            "busy_s": reduced["busy_s"], "ops": reduced["ops"][:4],
            "launches": [[x["module"], x.get("spans"), x["seconds"]] for x in reduced["launches"]],
            "cut": reduced["cut"],
        }
        if through:
            rec["at_the_end"] = at_the_end(path)
    except (ValueError, KeyError) as e:
        rec["read"] = f"{type(e).__name__}: {e}"
    rec["read_s"] = time.monotonic() - mark
    path.unlink()
    return rec


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--shapes", default="256,1024")
    p.add_argument("--windows", default="40,40,300,1100")
    p.add_argument("--variants", default=",".join(VARIANTS))
    p.add_argument("--load", type=int, default=0)
    p.add_argument("--through", action="store_true")
    p.add_argument("--out", default=str(ROOT / "chiprun_out" / "trace_cost"))
    args = p.parse_args()
    from pbft_tpu.net.verify_service import ShardedVerifyEngine

    engine = ShardedVerifyEngine(shapes=[int(s) for s in args.shapes.split(",")])
    mark = time.monotonic()
    stats = engine.warm()
    print(json.dumps({"warm_s": time.monotonic() - mark, "platform": engine.platform,
                      "devices": engine.device_count,
                      "per_shape": stats.get("per_shape")}), flush=True)
    windows = [[(os.urandom(32), os.urandom(32), os.urandom(64))] * int(n)
               for n in args.windows.split(",")]
    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)
    with open(out_dir / "variants.jsonl", "a") as fh:
        for k, name in enumerate(args.variants.split(",")):
            try:
                rec = one(name, VARIANTS[name], engine, windows, out_dir, export=k == 0,
                          load=args.load, through=args.through)
            except Exception as e:  # noqa: BLE001 - an option the chip's library refuses
                rec = {"variant": name, "error": f"{type(e).__name__}: {e}"}
            line = json.dumps(rec)
            print(line, flush=True)
            fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

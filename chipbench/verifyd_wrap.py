#!/usr/bin/env python3
"""verifyd, as the program starts it, with two things the benchmark needs
from inside the process that holds the chip and the program has no hook
for: the device's peak memory, and a profiler trace of a few seconds.

It runs the same ``pbft_tpu.net.verify_service.main`` entry with the same
flags as ``scripts/verifyd.py``. The only differences: ``engine.verify`` is
wrapped in a ``jax.profiler.TraceAnnotation`` that carries the number of
items (free while no trace runs), so that each launch on the device can be
told by the padded shape it ran at and an idle gap named as inside or
outside a launch; and a thread blocks on a FIFO for two commands from the
harness:

    mem <path>              write {"memory_peak_bytes": ..} of the fullest chip
    trace <seconds> <dir>   a profiler session with a span ``chipbench.slice``
                            of that length inside it (the slice's edges in
                            the trace's own clock), its xspace written under
                            <dir> where ``xplane.find_xplane`` looks and
                            nothing else, then <dir>/done with the slice's
                            stamps on the host's monotonic clock

Nothing else: the controls of ``correct`` and the CPU rehearsal's stub
engine live in ``tools/verifyd_control.py``, which no benchmark run starts.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent)]

from xplane import ENGINE_SPAN, SLICE_SPAN, write_xspace  # noqa: E402


def _memory_peak_bytes() -> dict:
    import jax

    peaks = []
    for dev in jax.local_devices():
        stats = dev.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"memory_peak_bytes": max(peaks), "per_device": peaks}


def _trace(seconds: float, out_dir: str) -> None:
    """The session's ``stop()`` gives the xspace as it was collected; what
    ``jax.profiler.stop_trace`` adds to it, TensorBoard's directory with a
    ``trace.json.gz`` of every event, nobody reads (``PERF.md`` section 3
    has what each piece costs)."""
    import jax
    from jax._src.lib import _profiler

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    t0 = time.monotonic()
    session = _profiler.ProfilerSession(options)
    with jax.profiler.TraceAnnotation(SLICE_SPAN):
        time.sleep(seconds)
    t_off = time.monotonic()
    blob = session.stop()
    t_stopped = time.monotonic()
    write_xspace(blob, out_dir)
    Path(out_dir, "done").write_text(json.dumps(
        {"asked": t0, "off": t_off, "collected": t_stopped, "stopped": time.monotonic(),
         "xspace_bytes": len(blob)}
    ))


def _control_loop(fifo: str) -> None:
    while True:
        with open(fifo) as fh:  # blocks until the harness opens it to write
            for line in fh:
                # A thread a command: stopping a trace takes many seconds,
                # and a ``mem`` behind it must not wait for that.
                threading.Thread(target=_command, args=(line,), daemon=True).start()


def _command(line: str) -> None:
    words = line.split()
    try:
        if words[:1] == ["mem"]:
            tmp = words[1] + ".tmp"
            Path(tmp).write_text(json.dumps(_memory_peak_bytes()))
            os.rename(tmp, words[1])
        elif words[:1] == ["trace"]:
            _trace(float(words[1]), words[2])
    except Exception as e:  # noqa: BLE001 - report, keep serving
        print(f"verifyd_wrap: {line.strip()!r} failed: {e!r}", file=sys.stderr, flush=True)


def traced(base):
    """``base`` with every ``verify`` call inside a span of the profiler."""

    class Engine(base):
        def verify(self, items):
            import jax

            with jax.profiler.TraceAnnotation(ENGINE_SPAN, items=len(items)):
                return super().verify(items)

    return Engine


def main(argv=None, engine=None) -> None:
    """``engine``: the class served in ``traced(ShardedVerifyEngine)``'s
    place (``tools/verifyd_control.py`` only)."""
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--control-fifo", required=True)
    args, verifyd_argv = parser.parse_known_args(argv)

    from pbft_tpu.net.verify_service import ShardedVerifyEngine, main as verifyd_main

    threading.Thread(
        target=_control_loop, args=(args.control_fifo,), daemon=True
    ).start()
    verifyd_main(verifyd_argv, engine=(engine or traced(ShardedVerifyEngine))())


if __name__ == "__main__":
    main()

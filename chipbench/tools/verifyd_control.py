#!/usr/bin/env python3
"""``verifyd_wrap.py`` with an engine that a benchmark run never serves:
the control of ``correct`` and the CPU rehearsal's stub. Started by the
tests and by ``hunt.py`` only; ``run.py`` has no way to reach it.

``--control accept-all``: the device computes every verdict as always, and
the engine then answers yes to everything — the broken guarantee ("a
signature the reference rejects never counts") that ``correct`` has to
catch.

``--stub-engine``: no kernel is compiled, the program's native host
verifier answers in the engine's place, and the status says platform
``cpu``, so a run that looks for a chip prints no result. It writes the
fields the sharded engine writes on a launch line (one chunk a window on the
smallest shape that fits it, the host verifier's time as ``wait_s``), so
that in a rehearsal every reader of the lines has something to read.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import verifyd_wrap  # noqa: E402


def stub(base):
    class StubEngine(base):
        def init_backend(self):
            self.platform, self.device_kind = "cpu", "stub"
            self.devices_seen = self.device_count = 1
            self._mesh = object()

        def warm(self):
            self._compiled = dict.fromkeys(self._want_shapes)
            self.stats = {"cache_dir": None, "per_shape": [], "compiled": 0,
                          "cache_hits": 0, "cold_compile_s": 0.0, "warm_load_s": 0.0}
            return self.stats

        def verify(self, items):
            from pbft_tpu import native
            from pbft_tpu.utils.trace import current_span

            t_dev = time.monotonic()
            verdicts = [bool(v) for v in native.verify_batch(items)]
            span = current_span()
            if span is not None:
                top = max(self._want_shapes)
                chunks = -(-len(items) // top)
                last = len(items) - (chunks - 1) * top
                fit = min(s for s in self._want_shapes if s >= last)
                span.update(pad_s=0.0, dispatch_s=0.0, unpack_s=0.0,
                            wait_s=round(time.monotonic() - t_dev, 6), t_dev=round(t_dev, 6),
                            rung=(chunks - 1) * top + fit, promoted=0, chunks=chunks,
                            split=int(chunks > 1))
            return verdicts

    return StubEngine


def accept_all(base):
    class AcceptAll(base):
        def verify(self, items):
            return [True] * len(super().verify(items))

    return AcceptAll


def main() -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--control", choices=["accept-all"], default=None)
    parser.add_argument("--stub-engine", action="store_true")
    args, rest = parser.parse_known_args()

    from pbft_tpu.net.verify_service import ShardedVerifyEngine

    engine = stub(ShardedVerifyEngine) if args.stub_engine else ShardedVerifyEngine
    engine = verifyd_wrap.traced(engine)
    if args.control == "accept-all":
        engine = accept_all(engine)
    verifyd_wrap.main(rest, engine=engine)


if __name__ == "__main__":
    main()

#!/usr/bin/env python
"""verify_status — introspect a running verify service (scripts/verifyd.py).

Sends the 0xFFFFFFFF JSON-status probe and pretty-prints what the daemon
is actually doing: state, platform and device kind, devices seen and in
the mesh, warmed window shapes, engine vs fallback dispatch counts, and
the once-per-deploy compile timings per shape — which shapes the
persistent compile cache answered (warm) and which were traced+compiled
(cold) — what one launch of each shape costs (``launch_s``, read at
warm-up), the serving table made from those costs (``16→256`` = a window
that fits 16 slots runs on the 256-slot program) and how many launches it
promoted, the chunk plan (``1025-1280→1024+256`` = a window of that many
items runs as two launches) and how many windows it split, the running total of each launch stage (queue, slot, pad, put,
dispatch, wait, unpack), the launches by shape run, by which exit of
the hold cut their window and by the rows a chip their thinnest chunk
gave, the slowest launch so far with the step that held it, the launches
that stalled (over a second in flight: each left a ``launch_stalled``
record in the trace and on stderr), and the device's peak memory.

    python scripts/verify_status.py                      # default target
    python scripts/verify_status.py 127.0.0.1:7600
    PBFT_VERIFY_SERVICE=host:7600 python scripts/verify_status.py --json

Exit codes: 0 reachable, 1 unreachable/no answer.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "target",
        nargs="?",
        default=os.environ.get("PBFT_VERIFY_SERVICE", "127.0.0.1:7600"),
        help="host:port or unix-socket path (default: $PBFT_VERIFY_SERVICE "
        "or 127.0.0.1:7600)",
    )
    parser.add_argument("--timeout", type=float, default=2.0)
    parser.add_argument("--json", action="store_true", help="raw status JSON")
    args = parser.parse_args(argv)

    from pbft_tpu.net.verify_service import probe_status_json, serving_table_text

    status = probe_status_json(args.target, timeout=args.timeout)
    if status is None:
        print(
            f"verify_status: no JSON status from {args.target} "
            "(unreachable, pre-handshake legacy service, or not a verify "
            "service)",
            file=sys.stderr,
        )
        return 1
    if args.json:
        print(json.dumps(status, sort_keys=True))
        return 0

    print(f"verify service @ {args.target}")
    print(f"  state           {status.get('state', '?')}")
    print(f"  devices         {status.get('devices', 0)}")
    if "uptime_s" in status:
        print(f"  uptime          {status['uptime_s']:.1f}s")
    shapes = status.get("warmed_shapes") or []
    print(
        "  warmed shapes   %s"
        % (", ".join(str(s) for s in shapes) if shapes else "(none)")
    )
    warm = status.get("warm_stats") or {}
    if warm:
        cold = warm.get("cold_compile_s")
        if cold is not None:
            print(f"  cold compile    {cold:.3f}s (traced+compiled shapes)")
        loaded = warm.get("warm_load_s")
        if loaded is not None:
            print(f"  warm load       {loaded:.3f}s (shapes the compile cache answered)")
        costs = [
            f"{shape['size']}: {1e3 * shape['launch_s']:.2f} ms"
            for shape in warm.get("per_shape") or []
            if "launch_s" in shape
        ]
        if costs:
            print("  launch cost     " + "  ".join(costs))
        chains = [
            f"{shape['size']}: {shape['chains']}"
            for shape in warm.get("per_shape") or []
            if "chains" in shape
        ]
        if chains:
            print("  multiply chains " + "  ".join(chains) + "  (%d launches with slots "
                  "on the VMEM chains)" % status.get("fused_launches", 0))
        table = warm.get("serving_table")
        if table:
            print("  serving table   %s  (%d launches promoted)" % (
                serving_table_text(table), status.get("promoted_launches", 0),
            ))
        plan = warm.get("chunk_plan")
        if plan is not None:
            print("  chunk plan      %s  (%d launches split)" % (
                serving_table_text(plan) or "one shape a window",
                status.get("split_launches", 0),
            ))
        for k in sorted(warm):
            if k in ("cold_compile_s", "warm_load_s", "serving_table", "chunk_plan"):
                continue
            print(f"  {k:<15} {warm[k]}")
    # Where launches spend their time, for seeing a stall without --trace:
    # running totals per stage, and the slowest launch with the step that
    # held it.
    stages = status.get("stage_seconds") or {}
    if stages:
        print("  stage seconds   " + "  ".join(
            f"{name.removesuffix('_s')} {secs:.3f}" for name, secs in stages.items()
        ))
    by_rung = status.get("launches_by_rung") or {}
    if by_rung:
        print("  launches        " + "  ".join(
            f"{rung} slots: {n}" for rung, n in sorted(by_rung.items(), key=lambda kv: int(kv[0]))
        ) + "  (hold ran out %d, in step %d)" % (
            status.get("held_out_launches", 0), status.get("in_step_launches", 0)))
    if "block_items" in status:
        print("  block path      %d items reached an executable as the rows they came off "
              "the wire as, %d went to a backend as a list of triples"
              % (status["block_items"], status.get("listed_items", 0)))
    if "windows_cut_full" in status:
        print("  full windows    %d cut at the largest window with requests left queued, "
              "at most %d items behind a cut"
              % (status["windows_cut_full"], status.get("overflow_items_max", 0)))
    by_rows = status.get("launches_by_rows_per_chip") or {}
    if by_rows:
        print("  rows a chip     " + "  ".join(
            f"{rows}: {n}" for rows, n in sorted(by_rows.items(), key=lambda kv: int(kv[0]))
        ) + "  (the thinnest chunk of each launch, over %d chip(s))" % status.get("devices", 0))
    slowest = status.get("slowest_launch")
    if slowest:
        print(
            "  slowest launch  {secs:.3f}s, {size} items at rung {rung}, longest "
            "step {stage}, {ago_s:.0f}s ago".format(**slowest)
        )
    if "stalls" in status:
        print("  stalls          %d launch(es) over a second in flight, the longest "
              "%.3fs (launch_stalled records: --trace file and stderr)"
              % (status["stalls"], status.get("longest_stall_s", 0.0)))
    peak = status.get("memory_peak_bytes")
    if peak is not None:
        print(f"  device memory   peak {peak / 2**20:.1f} MiB on the fullest device")
    # Anything else the daemon reports rides along un-dropped.
    known = {
        "state", "devices", "uptime_s", "warmed_shapes", "warm_stats",
        "stage_seconds", "slowest_launch", "memory_peak_bytes",
        "promoted_launches", "split_launches", "fused_launches", "launches_by_rung",
        "held_out_launches",
        "in_step_launches", "launches_by_rows_per_chip", "stalls", "longest_stall_s",
        "windows_cut_full", "overflow_items_max", "block_items", "listed_items",
    }
    for k in sorted(set(status) - known):
        print(f"  {k:<15} {status[k]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The spread hunt: where does the run-to-run spread of a cell come from?

    hunt.py h1 --workload W --runs 8 --seconds 51      one verifyd, N fresh clusters
    hunt.py h2 --workload W --runs 6 --seconds 51      the whole run N times (fresh verifyd)
    hunt.py control --workload W --runs 3 --seconds 8  the control of `correct`: accept-all engine
    hunt.py resample FILE [--lengths 20,30,40]         what shorter windows would have read

``h1`` and ``h2`` print one line per run (the end-to-end reading and, beside
it, everything that might move with it: items per launch, launches per
second, the rungs the launches ran at with the median launch time on each,
batch size, fsyncs per request, generator lateness, views, CPU seconds of
every child in the window) and write everything, with the completions of
every 100 ms, to ``chiprun_out/hunt/<tag>.json``. Levers, each tried as one
more call: ``--population`` (closed population times this factor) and
``--set cluster.KEY=JSON`` (one value of the configuration replaced for this
call, from run ``--set-from`` on; ``cluster.wal_fsync=false`` is the control
of the WAL's comparisons).
``resample`` reads such a file and gives, for each window length,
what each run would have read over its first ``length`` seconds and the
spread of those readings, taken the way the benchmark's bounds are.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(HERE))

import harness  # noqa: E402
import stats  # noqa: E402
import xplane  # noqa: E402
from reducers import hist_delta_mean, lateness_percentile, status_delta_per_request  # noqa: E402

OUT = harness.ROOT / "chiprun_out" / "hunt"
BIN_S = 0.1


def summarize(run: dict) -> dict:
    gen, t0, t1 = run["gen"], run["t0"], run["t1"]
    seconds = run["seconds"]
    bins = [0] * int(round(seconds / BIN_S))
    lat = []
    for due, done in zip(gen["due"], gen["done"]):
        if done is not None and t0 <= done < t1:
            bins[min(len(bins) - 1, int((done - t0) / BIN_S))] += 1
        if done is not None and t0 <= due < t1:
            lat.append((round(due - t0, 4), round(done - due, 5)))
    launch = stats.launch_stats(run["launches"])
    done_n = sum(bins)

    def hist(name, replicas="primary", scale=1e3):
        return hist_delta_mean.reduce(
            run, {"histogram": name, "replicas": replicas, "scale": scale})

    lats = [v for _, v in lat]
    return {
        "seed": run["seed"],
        "commit_rate": done_n / seconds,
        "reply_p50_ms": 1e3 * stats.percentile(lats, 50) if lats else None,
        "reply_p95_ms": 1e3 * stats.percentile(lats, 95) if lats else None,
        "items_per_launch": launch.get("items_per_launch"),
        "launches_per_s": launch.get("launches", 0) / seconds,
        "pad_fill": launch.get("pad_fill"),
        "launch_ms_p50": launch.get("launch_ms_p50"),
        "rungs": launch.get("rungs"),
        "batch_items_mean": hist("pbft_batch_size", scale=1.0),
        "prepare_ms_mean": hist("pbft_phase_prepare_seconds"),
        "commit_ms_mean": hist("pbft_phase_commit_seconds"),
        "verify_rtt_ms_mean": hist("pbft_verify_seconds", "all"),
        "fsyncs_per_req": status_delta_per_request.reduce(run, {"field": "wal_fsyncs"}),
        "gen_late_p99_ms": lateness_percentile.reduce(run, {"q": 99}),
        "views": [d["view"] for d in run["final"]["status"]],
        "failed": sum(d is None for d in gen["done"]),
        "cpu_window_s": run["cpu_window"],
        "bins": bins,
        "latencies": lat if run["traffic"]["kind"] != "closed" else None,
        "probe": run["probe"],
        "gen_late_max_ms": lateness_percentile.reduce(run, {"q": 100}),
        "launch_log": [(round(e["ts"] - t0, 3), e["size"], round(e["secs"], 4))
                       for e in run["launches"]],
        "wal": [
            {k: d[k] for k in ("wal_fsyncs", "wal_appends", "executed_upto", "executed",
                               "verify_batches", "broadcasts")}
            for d in run["final"]["status"]
        ],
    }


def show(k: int, s: dict) -> None:
    cpu = s["cpu_window_s"]
    replicas = [v for n, v in cpu.items() if n.startswith("pbftd")]
    rungs = " ".join(
        f"{r}:{v['launches']}@{v['launch_ms_p50']:.1f}ms" for r, v in (s["rungs"] or {}).items()
    )
    per_s = [sum(s["bins"][i : i + 10]) for i in range(0, len(s["bins"]), 10)]
    print(
        f"run {k} seed {s['seed']}: commit_rate {s['commit_rate']:.2f} "
        f"p50 {s['reply_p50_ms'] or 0:.1f} p95 {s['reply_p95_ms'] or 0:.1f} | "
        f"items/launch {s['items_per_launch']:.1f} launches/s {s['launches_per_s']:.1f} "
        f"fill {s['pad_fill']:.3f} launch_ms {s['launch_ms_p50']:.1f} rungs [{rungs}] | "
        f"batch {s['batch_items_mean']:.2f} prep {s['prepare_ms_mean']:.0f} "
        f"commit {s['commit_ms_mean']:.0f} rtt {s['verify_rtt_ms_mean']:.1f} "
        f"fsync/req {s['fsyncs_per_req']:.4f} late_p99 {s['gen_late_p99_ms']:.2f} "
        f"views {max(s['views'])} failed {s['failed']} | cpu verifyd {cpu.get('verifyd', 0):.1f} "
        f"replicas {sum(replicas):.1f} (max {max(replicas):.1f}) gateway {cpu.get('gateway', 0):.1f} "
        f"loadgen {sum(v for n, v in cpu.items() if n.startswith('loadgen')):.1f}",
        flush=True,
    )
    print(f"   per second: {per_s}", flush=True)
    print("   WAL by replica, fsyncs per sequence number / appends per sequence number / "
          "fsyncs per verify batch: " + ", ".join(
        f"{d['wal_fsyncs'] / max(1, d['executed_upto']):.3f} / "
        f"{d['wal_appends'] / max(1, d['executed_upto']):.3f} / "
        f"{d['wal_fsyncs'] / max(1, d['verify_batches']):.3f} "
        f"(upto {d['executed_upto']}, executed {d['executed']}, fsyncs {d['wal_fsyncs']}, "
        f"verify batches {d['verify_batches']}, broadcasts {d['broadcasts']})"
        for d in s["wal"]), flush=True)


def spreads(values: list) -> str:
    if len(values) < 3:
        return "n<3"
    med = statistics.median(values)
    return (
        f"median {med:.2f} min {min(values):.2f} max {max(values):.2f} "
        f"range {100 * (max(values) - min(values)) / med:.2f}% "
        f"iqr {100 * stats.iqr_spread(values):.2f}% "
        f"iqr-without-farthest {100 * stats.trimmed_iqr_spread(values):.2f}%"
    )


def hunt(args) -> int:
    bench = harness.load_benchmark()
    loaded = harness.load_cell(bench, args.workload)
    as_filed = json.loads(json.dumps(loaded["config"]))
    changed = json.loads(json.dumps(as_filed))
    for item in args.set:
        key, value = item.split("=", 1)
        group, field = key.split(".")
        changed[group][field] = json.loads(value)
    print(f"hunt {args.mode} {args.workload} runs {args.runs} seconds {args.seconds} "
          f"set {args.set} population x{args.population} "
          f"cores {len(os.sched_getaffinity(0))}", flush=True)
    OUT.mkdir(parents=True, exist_ok=True)
    populations = [float(x) for x in str(args.population).split(",")]
    out = []
    children = verifyd = None

    def fresh_verifyd():
        shutil.rmtree(harness.WORK, ignore_errors=True)
        harness.WORK.mkdir(parents=True)
        os.environ["TMPDIR"] = str(harness.WORK)
        ch = harness.Children()
        broken = (["--stub-engine"] if args.rehearse else []) + (
            ["--control", "accept-all"] if args.mode == "control" else [])
        v = harness.Verifyd(harness.WORK, loaded["config"]["verifyd"], ch,
                            [str(HERE / "tools" / "verifyd_control.py"), *broken] if broken else ())
        t = time.monotonic()
        ready = v.wait_ready(not args.rehearse)
        print(f"verifyd ready in {time.monotonic() - t:.1f}s: {ready['device_kind']} "
              f"warm {ready.get('warm_stats', {}).get('per_shape')}", flush=True)
        return ch, v, ready

    try:
        from pbft_tpu import native

        native.build()
        for k in range(args.runs):
            seed = args.seed + 7919 * k
            if verifyd is None or args.mode == "h2":
                if verifyd is not None:
                    children.stop_all()
                    verifyd.close()
                children, verifyd, ready = fresh_verifyd()
            loaded["config"] = changed if k >= args.set_from else as_filed
            probe = harness.Probe(loaded["traffic"]["probe"], seed)
            run = harness.serve_window(
                loaded, verifyd, probe, ready, seed=seed, seconds=args.seconds,
                trace=args.trace_last and k == args.runs - 1, children=children, work=harness.WORK,
                population_scale=populations[k % len(populations)],
            )
            children.procs = [p for p in children.procs if p[0] == "verifyd"]
            fb = harness.fallbacks(run)
            bad = [c for c in harness.compare_run(run) if not harness._OPS[c[2]](c[1], c[3])]
            s = summarize(run)
            if run["trace"] is not None:
                s["trace"] = {k2: run["trace"][k2] for k2 in ("window_s", "busy_s", "idle")}
                s["trace"]["ms_by_rung"] = {
                    rung: [round(1e3 * v, 3) for v in secs] for rung, secs in sorted(
                        xplane.launches_by_shape(run["trace"], None).items())}
                s["trace"]["ops"] = run["trace"]["ops"][:10]
                print(f"   trace: {json.dumps(s['trace'])}", flush=True)
            s["fallbacks"], s["not_ok"] = fb, bad
            s["population"] = populations[k % len(populations)]
            s["set"] = args.set if k >= args.set_from else []
            out.append(s)
            show(k, s)
            print(f"   population x{s['population']} set {s['set']} correct {not bad} fallbacks {fb} "
                  f"not ok {bad} probe {s['probe']}", flush=True)
            tag = f"{args.mode}-{args.workload}-{args.tag}"
            (OUT / f"{tag}.json").write_text(json.dumps(
                {"mode": args.mode, "workload": args.workload, "seconds": args.seconds,
                 "set": args.set, "population": args.population,
                 "runs": out}))
    finally:
        if children is not None:
            children.stop_all()
        if verifyd is not None:
            verifyd.close()
    for key in ("commit_rate", "reply_p50_ms", "reply_p95_ms"):
        vals = [s[key] for s in out if s[key] is not None]
        print(f"{key}: {spreads(vals)}", flush=True)
    resample_runs(out, args.seconds, [20, 30, 40])
    return 0


def window_reading(s: dict, length: float, key: str):
    if key == "commit_rate":
        return sum(s["bins"][: int(round(length / BIN_S))]) / length
    lat = [v for due, v in (s["latencies"] or []) if due < length]
    if not lat:
        return None
    return 1e3 * stats.percentile(lat, 50 if key == "reply_p50_ms" else 95)


def resample_runs(runs: list, seconds: float, lengths) -> None:
    for key in ("commit_rate", "reply_p50_ms", "reply_p95_ms"):
        if key != "commit_rate" and not runs[0].get("latencies"):
            continue
        for length in [x for x in lengths if x < seconds] + [seconds]:
            vals = [window_reading(s, length, key) for s in runs]
            print(f"{key} over the first {length:g}s: {spreads(vals)}", flush=True)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("mode", choices=["h1", "h2", "control", "resample"])
    p.add_argument("file", nargs="?")
    p.add_argument("--workload")
    p.add_argument("--runs", type=int, default=8)
    p.add_argument("--seconds", type=float, default=51)
    p.add_argument("--seed", type=int, default=2147480001)
    p.add_argument("--set", action="append", default=[], metavar="GROUP.KEY=JSON")
    p.add_argument("--set-from", type=int, default=0,
                   help="the runs from this one on get --set; those before run as filed")
    p.add_argument("--population", default="1",
                   help="closed population factor; a comma list cycles over the runs")
    p.add_argument("--tag", default="base")
    p.add_argument("--lengths", default="20,30,40")
    p.add_argument("--trace-last", action="store_true",
                   help="profile a slice of the last run's window")
    p.add_argument("--rehearse", action="store_true",
                   help="CPU rehearsal of the tool itself: stub engine, no chip")
    args = p.parse_args()
    if args.mode == "resample":
        data = json.loads(Path(args.file).read_text())
        resample_runs(data["runs"], data["seconds"], [float(x) for x in args.lengths.split(",")])
        return 0
    return hunt(args)


if __name__ == "__main__":
    sys.exit(main())

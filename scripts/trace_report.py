#!/usr/bin/env python
"""Summarize pbft_tpu JSONL traces (pbftd --trace, verifyd --trace).

Reads one or more per-replica trace files and prints, per replica and
cluster-wide: verify-batch count/size/time percentiles, batching-window
efficiency (items per launch — the number the TPU batching design exists
to maximize), rejected-signature totals, and view-change events.

Given the replicas' traces AND verifyd's launch log (``verifyd --trace``),
it also prints the idle-interval table (ISSUE 38): for every interval of
5 ms or more in which verifyd had nothing in flight (the complement of the
union of ``[t_dev, t_dev + dispatch_s + wait_s]``, what the benchmark's
``engine_idle_pct`` reads), what each replica's net loop spent between the
two of its ``verify_batch`` lines that bracket the interval (their
``loop_us``: the loop clock's running totals by kind of work), and the
``queue_s`` / ``hold_s`` of the launch that ended it: the device's
"waiting for a window" put down to verifyd's hold, to replicas at work
(which stage), or to replicas themselves in ``wait`` (the chip is then
waiting for the clients). Every stamp is CLOCK_MONOTONIC on one host.
For a replica run with ``--net-threads`` above 1 (ISSUE 40) a line says how
busy its shard threads and its pipeline threads were and what the hand-off
to the consensus thread took (the batch lines' ``shard_us``, ``pipe_us``,
``handoff``).

Usage: python scripts/trace_report.py /path/to/trace-dir-or-files...
"""

from __future__ import annotations

import bisect
import json
import pathlib
import sys
from collections import Counter

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))

from pbft_tpu.utils.trace_schema import LOOP_STAGES  # noqa: E402

# The shortest interval with nothing in flight that gets a row.
IDLE_GAP_S = 0.005


def _pct(sorted_vals, q: float):
    if not sorted_vals:
        return 0
    return sorted_vals[min(len(sorted_vals) - 1, int(q * len(sorted_vals)))]


def load(path: pathlib.Path):
    events = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return events


# The consensus_span phase chain (utils/trace_schema.py): per-transition
# latencies reported as p50/p90 when a trace carries span events.
_SPAN_PHASES = [
    ("request", "pre_prepare"),
    ("pre_prepare", "prepared"),
    ("prepared", "committed"),
    ("committed", "executed"),
]


def _batch_sizes(events) -> dict:
    """{(view, seq) -> sealed batch size} from batch_sealed events."""
    sizes = {}
    for e in events:
        if e.get("ev") != "batch_sealed":
            continue
        try:
            sizes[(int(e["view"]), int(e["seq"]))] = int(e["batch"])
        except (KeyError, TypeError, ValueError):
            continue
    return sizes


def _span_summary(spans, batches=None) -> str:
    """One-line per-phase latency summary for consensus_span events.

    Spans are per (view, seq) — per ROUND — and a batched round carries
    many requests (ISSUE 4), so segment times must not be read as
    per-request numbers. When batch_sealed data is available the execute
    segment (the only one whose cost scales with occupancy) also reports
    its per-request amortization, and the caller prints the mean batch."""
    batches = batches or {}
    parts = []
    for a, b in _SPAN_PHASES:
        rows = [
            (e[b] - e[a], batches.get((e.get("view"), e.get("seq")), 1))
            for e in spans
            if isinstance(e.get(a), (int, float))
            and isinstance(e.get(b), (int, float))
        ]
        if not rows:
            continue
        durs = sorted(r[0] for r in rows)
        label = (
            f"{b} p50={_pct(durs, 0.5) * 1e3:.2f}ms "
            f"p90={_pct(durs, 0.9) * 1e3:.2f}ms"
        )
        if b == "executed" and batches:
            per_req = sorted(d / max(1, n) for d, n in rows)
            label += f" ({_pct(per_req, 0.5) * 1e3:.2f}ms/req)"
        parts.append(label)
    e2e = sorted(
        e["executed"] - (e.get("request", e.get("pre_prepare")))
        for e in spans
        if isinstance(e.get("executed"), (int, float))
        and isinstance(e.get("request", e.get("pre_prepare")), (int, float))
    )
    if e2e:
        parts.append(
            f"e2e p50={_pct(e2e, 0.5) * 1e3:.2f}ms "
            f"p90={_pct(e2e, 0.9) * 1e3:.2f}ms"
        )
    # Tentative runs: a span that closed at PREPARED has no committed stamp
    # (so the two segments above are rightly absent for it); the commit
    # quorum that followed is in the commit_lag lines.
    tentative = sum(
        1 for e in spans
        if isinstance(e.get("prepared"), (int, float)) and "committed" not in e
    )
    if tentative:
        parts.append(f"{tentative} executed at PREPARED (tentative)")
    return ", ".join(parts)


def _commit_lag_summary(events) -> str:
    lags = sorted(
        e["lag_s"] for e in events
        if e.get("ev") == "commit_lag" and isinstance(e.get("lag_s"), (int, float))
    )
    if not lags:
        return ""
    return (
        f"commit lag p50={_pct(lags, 0.5) * 1e3:.2f}ms "
        f"p90={_pct(lags, 0.9) * 1e3:.2f}ms over {len(lags)} sequence numbers"
    )


def _ahead_summary(batches) -> str:
    """pbftd's batch lines say (0/1 field ``ahead``) whether the replica
    launched its next batch before it worked through this one's verdicts."""
    said = [e["ahead"] for e in batches if "ahead" in e]
    if not said:
        return ""
    return f", launched ahead {sum(said)}/{len(said)} ({sum(said) / len(said):.0%})"


def idle_intervals(launches, min_gap: float = IDLE_GAP_S) -> list:
    """[(start, end, the launch that ended it)]: the intervals of at least
    ``min_gap`` seconds, between the first launch and the last, in which
    no launch of verifyd's log was in flight."""
    spans = sorted(
        (
            (e["t_dev"], e["t_dev"] + e["dispatch_s"] + e["wait_s"], e)
            for e in launches
            if all(isinstance(e.get(k), (int, float)) for k in ("t_dev", "dispatch_s", "wait_s"))
        ),
        key=lambda s: s[0],
    )
    gaps = []
    busy_until = spans[0][1] if spans else 0.0
    for start, end, e in spans[1:]:
        if start - busy_until >= min_gap:
            gaps.append((busy_until, start, e))
        busy_until = max(busy_until, end)
    return gaps


def loop_between(lines, start: float, end: float):
    """What one replica's loop spent, by stage, between the last of its
    lines at or before ``start`` and the first at or after ``end``
    (``lines``: its verify_batch lines that carry ``loop_us``, by ts):
    (bracket's length in seconds, {stage: microseconds}); None where the
    interval lies before its first line or after its last."""
    stamps = [e["ts"] for e in lines]
    lo = bisect.bisect_right(stamps, start) - 1
    hi = bisect.bisect_left(stamps, end)
    if lo < 0 or hi >= len(lines):
        return None
    a, b = lines[lo], lines[hi]
    return b["ts"] - a["ts"], {
        stage: after - before
        for stage, before, after in zip(LOOP_STAGES, a["loop_us"], b["loop_us"])
    }


def idle_table(launches, by_replica, top: int = 0) -> list:
    """One row an idle interval (``idle_intervals``), longest first (the
    ``top`` longest where given): its start and length, the launch that
    ended it, each replica's loop between its bracketing lines, and to
    what the interval is put down."""
    rows = []
    for start, end, ender in idle_intervals(launches):
        gap = end - start
        loops = {
            rid: loop_between(lines, start, end) for rid, lines in sorted(by_replica.items())
        }
        pooled = Counter()
        for found in loops.values():
            if found:
                pooled.update(found[1])
        # The part of the gap during which verifyd HAD a request and sat
        # on it: the oldest request of the ending launch's window arrived
        # queue_s before the cut, and the cut came slot_s + pad_s before
        # the first dispatch.
        held = min(gap, sum(ender.get(k, 0.0) for k in ("queue_s", "slot_s", "pad_s")))
        spent = sum(pooled.values())
        if held >= gap / 2:
            verdict = "verifyd's hold"
        elif not spent:
            verdict = "no replica line brackets it"
        elif pooled["wait"] >= spent / 2:
            verdict = "replicas in wait (the chip waits for the clients)"
        else:
            work = max((s for s in LOOP_STAGES if s != "wait"), key=lambda s: pooled[s])
            verdict = f"replicas at work: {work}"
        rows.append({
            "start": start, "gap_s": gap, "held_s": held, "verdict": verdict,
            "ender": {k: ender.get(k) for k in ("size", "requests", "queue_s", "hold_s")},
            "loops": loops, "pooled": dict(pooled),
        })
    rows.sort(key=lambda r: -r["gap_s"])
    return rows[:top] if top else rows


def print_idle_table(launches, by_replica, top: int = 12) -> list:
    rows = idle_table(launches, by_replica)
    if not rows:
        return rows
    t_first = min(e["t_dev"] for e in launches if "t_dev" in e)
    t_last = max(e["ts"] for e in launches)
    by_verdict = Counter()
    for r in rows:
        by_verdict[r["verdict"]] += r["gap_s"]
    print(
        f"verifyd idle: {len(rows)} intervals of {1e3 * IDLE_GAP_S:.0f} ms or more with nothing "
        f"in flight, {sum(r['gap_s'] for r in rows):.3f}s of {t_last - t_first:.3f}s; put down to: "
        + "; ".join(f"{v} {s:.3f}s" for v, s in by_verdict.most_common())
    )
    print(f"the {min(top, len(rows))} longest (a replica's columns: ms between its two "
          "bracketing lines, then the share of each stage): " + " ".join(LOOP_STAGES))
    for r in rows[:top]:
        e = r["ender"]
        print(
            f"  at {r['start'] - t_first:9.3f}s idle {1e3 * r['gap_s']:7.2f} ms, ended by a launch of "
            f"{e['size']} items from {e['requests']} (queue {1e3 * (e['queue_s'] or 0):.2f} ms, hold "
            f"{1e3 * (e['hold_s'] or 0):.2f} ms, a request waiting {1e3 * r['held_s']:.2f} ms of it): "
            f"{r['verdict']}"
        )
        for rid, found in r["loops"].items():
            if not found:
                print(f"      replica {rid}: no bracketing lines")
                continue
            secs, split = found
            all_us = sum(split.values()) or 1
            print(f"      replica {rid}: {1e3 * secs:7.2f} ms  " + " ".join(
                f"{split[s] / all_us:.2f}" for s in LOOP_STAGES))
    return rows


def front_end_summary(lines) -> str:
    """A sharded replica's front end between its first and last batch line
    (``pbftd --net-threads`` above 1; ISSUE 40): the busy share of its shard
    threads and of its pipeline threads (1 - wait over all four stages, from
    the lines' ``shard_us`` / ``pipe_us``) and the hand-off's mean (the
    lines' ``handoff``: drains and their seconds). "" where the lines carry
    none of it."""
    have = [e for e in lines if "shard_us" in e and "pipe_us" in e]
    if len(have) < 2:
        return ""
    a, b = have[0], have[-1]
    parts = []
    for key, what in (("shard_us", "shards"), ("pipe_us", "pipelines")):
        spent = sum(b[key]) - sum(a[key])
        if spent > 0:
            parts.append(f"{what} busy {1 - (b[key][0] - a[key][0]) / spent:.2f}")
    drains = b["handoff"][0] - a["handoff"][0]
    if drains > 0:
        parts.append(f"hand-off mean {1e3 * (b['handoff'][1] - a['handoff'][1]) / drains:.2f}ms "
                     f"over {drains} drains")
    return ", ".join(parts)


def report(files) -> dict:
    launches: list = []  # verifyd's launch lines, where a log of its is given
    by_replica: dict = {}  # replica -> its batch lines that carry loop_us
    total = {
        "batches": 0,
        "items": 0,
        "rejected": 0,
        "secs": 0.0,
        "vcs": 0,
        "spans": 0,
    }
    for path in files:
        events = load(path)
        vb = [e for e in events if e.get("ev") == "verify_batch"]
        for e in vb:
            if "t_dev" in e:
                launches.append(e)
            elif isinstance(e.get("loop_us"), list):
                by_replica.setdefault(e["replica"], []).append(e)
        front = front_end_summary(vb)
        if front:
            print(f"{path.name}: front end: {front}")
        applied = sorted(e["apply_s"] for e in vb if "apply_s" in e)
        if applied:
            print(
                f"{path.name}: a batch's verdicts worked through in p50="
                f"{_pct(applied, 0.5) * 1e3:.2f}ms p90={_pct(applied, 0.9) * 1e3:.2f}ms "
                f"(apply_s, {len(applied)} kept batches)"
            )
        # Failed merged windows (service trace): their per-request retries
        # are the verify_batch events; surface the failure count so a run
        # with backend trouble reads as such.
        failed = [e for e in events if e.get("ev") == "verify_window_failed"]
        if failed:
            print(f"{path.name}: {len(failed)} FAILED merged windows")
        # pbftd emits "view_change_start" (core/net.cc
        # trace_view_change).
        vcs = [e for e in events if e.get("ev") == "view_change_start"]
        spans = [e for e in events if e.get("ev") == "consensus_span"]
        deadline_fired = [
            e for e in events if e.get("ev") == "verify_deadline_fired"
        ]
        if deadline_fired:
            print(
                f"{path.name}: {len(deadline_fired)} verify deadlines fired "
                "(wedged async verifier -> CPU safety net)"
            )
        sizes = sorted(e["size"] for e in vb)
        secs = sorted(e["secs"] for e in vb)
        rejected = sum(e.get("rejected", 0) for e in vb)
        total["batches"] += len(vb)
        total["items"] += sum(sizes)
        total["rejected"] += rejected
        total["secs"] += sum(secs)
        total["vcs"] += len(vcs)
        total["spans"] += len(spans)
        batches = _batch_sizes(events)
        if batches:
            sizes_b = list(batches.values())
            total["sealed_windows"] = total.get("sealed_windows", 0) + len(
                sizes_b
            )
            total["sealed_requests"] = total.get("sealed_requests", 0) + sum(
                sizes_b
            )
            print(
                f"{path.name}: {len(sizes_b)} sealed batches, mean batch "
                f"{sum(sizes_b) / len(sizes_b):.2f}/window "
                f"(spans below are per ROUND, not per request)"
            )
        if spans:
            print(f"{path.name}: {len(spans)} consensus spans: "
                  + _span_summary(spans, batches))
        lag = _commit_lag_summary(events)
        if lag:
            print(f"{path.name}: {lag}")
        if vb:
            span = vb[-1]["ts"] - vb[0]["ts"] or 1e-9
            print(
                f"{path.name}: {len(vb)} batches, {sum(sizes)} items "
                f"(size p50={_pct(sizes, 0.5)} p90={_pct(sizes, 0.9)} "
                f"max={sizes[-1]}), verify p50={_pct(secs, 0.5) * 1e3:.2f}ms "
                f"p90={_pct(secs, 0.9) * 1e3:.2f}ms, "
                f"{sum(sizes) / span:.0f} items/s, rejected={rejected}, "
                f"view_changes={len(vcs)}" + _ahead_summary(vb)
            )
        else:
            print(f"{path.name}: no verify_batch events")
        # The verify service's lines say where each window ran: over how
        # many chips, and how many rows a chip its thinnest chunk gave.
        sharded = [e for e in vb if "devices" in e and "rows_per_chip" in e]
        if sharded:
            chips = sorted({e["devices"] for e in sharded})
            by_rows = Counter(e["rows_per_chip"] for e in sharded)
            print(
                f"{path.name}: {len(sharded)} launches sharded over "
                f"{'/'.join(map(str, chips))} chip(s); rows a chip of the "
                "thinnest chunk: "
                + "  ".join(f"{rows}: {n}" for rows, n in sorted(by_rows.items()))
            )
    if total["batches"]:
        print(
            f"cluster: {total['items']} verifications in {total['batches']} "
            f"launches = {total['items'] / total['batches']:.1f} items/launch "
            f"(batching-window efficiency), {total['rejected']} rejected, "
            f"{total['vcs']} view changes, "
            f"{total['secs']:.2f}s total verify time"
        )
    if total.get("sealed_windows"):
        print(
            f"cluster: {total['sealed_requests']} requests over "
            f"{total['sealed_windows']} sealed windows = mean batch "
            f"{total['sealed_requests'] / total['sealed_windows']:.2f} "
            "(the round->request attribution factor)"
        )
    if total["spans"]:
        print(
            f"cluster: {total['spans']} consensus spans "
            "(per-(view,seq) breakdowns: scripts/consensus_timeline.py)"
        )
    if launches and by_replica:
        for lines in by_replica.values():
            lines.sort(key=lambda e: e["ts"])
        total["idle_intervals"] = print_idle_table(launches, by_replica)
    return total


def expand_trace_args(args) -> list:
    """Directory args expand to their sorted *.jsonl files, including one
    level of subdirectories (the harness's --trace-dir writes per-config
    cfg<i>/ subdirs); file args pass through. Single source of the
    trace-layout rule."""
    files = []
    for arg in args:
        p = pathlib.Path(arg)
        if p.is_dir():
            files.extend(sorted(p.glob("*.jsonl")) + sorted(p.glob("*/*.jsonl")))
        else:
            files.append(p)
    return files


def main() -> None:
    if len(sys.argv) < 2:
        sys.exit(__doc__)
    files = expand_trace_args(sys.argv[1:])
    if not files:
        sys.exit("no trace files found")
    report(files)


if __name__ == "__main__":
    main()

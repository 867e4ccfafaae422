"""Arithmetic the yardstick rests on: percentiles, spreads, histogram deltas.

Kept apart from the harness so that each piece has a test on hand-made
inputs (``tests/test_arithmetic.py``).
"""

from __future__ import annotations

import math
import re
import statistics


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the
    two nearest order statistics; raises on an empty sample."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def iqr_spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, quartiles as ``statistics.quantiles(values, n=4)`` gives them:
    the spread the benchmark's bounds are set from."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def trimmed_iqr_spread(values) -> float:
    """The same spread with the run farthest from the median left out:
    how the driver reads a set when it judges a bound as too tight."""
    med = statistics.median(values)
    far = max(range(len(values)), key=lambda i: abs(values[i] - med))
    return iqr_spread([v for i, v in enumerate(values) if i != far])


_SAMPLE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(?:\{([^}]*)\})? (\S+)$")


def parse_prometheus(text: str) -> dict:
    """Prometheus text format -> {(name, labels-without-replica): value}.
    ``labels`` is the label string with the ``replica`` label dropped, so
    that one replica's scrapes line up by key."""
    out = {}
    for line in text.splitlines():
        m = _SAMPLE.match(line)
        if not m:
            continue
        name, labels, value = m.groups()
        kept = ",".join(
            p for p in (labels or "").split(",") if p and not p.startswith("replica=")
        )
        out[(name, kept)] = float(value)
    return out


def hist_delta(before: dict, after: dict, name: str) -> tuple:
    """(sum, count) a histogram gained between two parsed scrapes."""
    d_sum = after.get((name + "_sum", ""), 0.0) - before.get((name + "_sum", ""), 0.0)
    d_cnt = after.get((name + "_count", ""), 0.0) - before.get((name + "_count", ""), 0.0)
    return d_sum, d_cnt


def counter_delta(before: dict, after: dict, name: str) -> float:
    return after.get((name, ""), 0.0) - before.get((name, ""), 0.0)


def launch_stats(launches) -> dict:
    """From ``verify_batch`` records ({size, requests, secs, rung}): items
    per launch, useful items over the padded slots that RAN (the record's
    ``rung``, summed over a window's chunks; records without it, from a
    backend that is not the sharded engine, are left out of the fill),
    ``secs`` percentiles and the histogram of slots run with the median
    ``secs`` on each."""
    if not launches:
        return {}
    sizes = [e["size"] for e in launches]
    padded = [e for e in launches if e.get("rung")]
    by_slots: dict = {}
    for e in launches:
        by_slots.setdefault(e.get("rung"), []).append(e["secs"])
    out = {
        "launches": len(launches),
        "items": sum(sizes),
        "items_per_launch": sum(sizes) / len(launches),
        "window_max_items": max(sizes),
        "launch_ms_p50": 1e3 * percentile([e["secs"] for e in launches], 50),
        "rungs": {
            str(slots): {
                "launches": len(secs),
                "launch_ms_p50": 1e3 * percentile(secs, 50),
            }
            for slots, secs in sorted(by_slots.items(), key=lambda kv: kv[0] or 0)
        },
    }
    if padded:
        out["pad_fill"] = sum(e["size"] for e in padded) / sum(e["rung"] for e in padded)
    return out

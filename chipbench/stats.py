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


def rung_of(size: int, ladder) -> int:
    """The padded shape a launch of ``size`` items ran at: the smallest
    rung that holds it; beyond the top it runs in top-rung chunks."""
    for rung in ladder:
        if size <= rung:
            return rung
    top = ladder[-1]
    return -(-size // top) * top


def launch_stats(launches, ladder) -> dict:
    """From ``verify_batch`` records ({size, requests, secs}): items per
    launch, useful items over padded slots, ``secs`` percentiles and the
    histogram of rungs with the median ``secs`` on each."""
    if not launches:
        return {}
    sizes = [e["size"] for e in launches]
    rungs = [rung_of(s, ladder) for s in sizes]
    by_rung: dict = {}
    for rung, e in zip(rungs, launches):
        by_rung.setdefault(rung, []).append(e["secs"])
    return {
        "launches": len(launches),
        "items": sum(sizes),
        "items_per_launch": sum(sizes) / len(launches),
        "pad_fill": sum(sizes) / sum(rungs),
        "window_max_items": max(sizes),
        "launch_ms_p50": 1e3 * percentile([e["secs"] for e in launches], 50),
        "rungs": {
            str(rung): {
                "launches": len(secs),
                "launch_ms_p50": 1e3 * percentile(secs, 50),
            }
            for rung, secs in sorted(by_rung.items())
        },
    }

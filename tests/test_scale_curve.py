"""scripts/scale_curve.py wiring (ISSUE 10): the n=4 smoke in tier-1,
bench_compare compatibility (per-n grouping included), and the f=5/f=10
sustained arms behind @slow.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(name: str):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "scripts" / f"{name}.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _check_row(row: dict, n: int) -> None:
    assert row["replicas"] == n
    assert row["completed_pct"] >= 99.0, row
    for key in (
        "rounds_per_sec",
        "requests_per_sec",
        "reply_p50_ms",
        "reply_p99_ms",
        "mean_batch",
    ):
        assert isinstance(row[key], (int, float)), key
    assert row["requests_per_sec"] > 0
    assert row["reply_p99_ms"] >= row["reply_p50_ms"] >= 0


def test_scale_curve_n4_smoke(tmp_path):
    """One sustained n=4 point through the gateway tier, emitted as
    bench_compare-compatible JSONL and gated per-n (--group-by)."""
    scale_curve = _load("scale_curve")
    bench_compare = _load("bench_compare")

    row = scale_curve.run_point(
        n=4, clients=4, requests_each=5, window=4, batch=16,
        batch_flush_us=2000, gateways=1, deadline_s=240,
    )
    _check_row(row, 4)
    assert row["mean_batch"] >= 1.0

    out = tmp_path / "curve.jsonl"
    out.write_text(json.dumps(row) + "\n")
    runs = bench_compare.load_runs(str(out))
    assert len(runs) == 1

    # Same file as old AND new: zero delta, exit 0 — both flat and
    # per-replicas-grouped (the scale-curve gating mode).
    assert bench_compare.main([str(out), str(out)]) == 0
    assert bench_compare.main(
        [str(out), str(out), "--group-by", "replicas"]
    ) == 0

    # A synthetic regression in one n-group trips the grouped gate.
    worse = dict(row, requests_per_sec=row["requests_per_sec"] * 0.5)
    bad = tmp_path / "worse.jsonl"
    bad.write_text(json.dumps(worse) + "\n")
    assert bench_compare.main(
        [str(out), str(bad), "--group-by", "replicas",
         "--metric", "requests_per_sec", "--max-regress-pct", "10"]
    ) == 1


def test_bench_compare_group_by_partitions():
    """Grouping keeps each n's runs separate: an n=31 slowdown must not
    hide behind an n=4 speedup in a merged median."""
    bench_compare = _load("bench_compare")
    old = [
        {"replicas": 4, "requests_per_sec": 100.0},
        {"replicas": 31, "requests_per_sec": 10.0},
    ]
    new = [
        {"replicas": 4, "requests_per_sec": 200.0},
        {"replicas": 31, "requests_per_sec": 5.0},
    ]
    report = bench_compare.compare_grouped(
        old, new, "replicas", ["requests_per_sec"], 10.0
    )
    assert report["replicas=4:requests_per_sec"]["regressed"] is False
    assert report["replicas=31:requests_per_sec"]["regressed"] is True


@pytest.mark.slow
def test_scale_curve_f5_f10_sustained(tmp_path):
    """The acceptance run: sustained n=16 (f=5, >=8 identities, 256-req
    batching windows) and n=31 (f=10) on one box, JSONL that
    bench_compare accepts with per-n grouping."""
    scale_curve = _load("scale_curve")
    bench_compare = _load("bench_compare")

    rows = []
    for n, clients, reqs in ((16, 8, 8), (31, 8, 4)):
        row = scale_curve.run_point(
            n=n, clients=clients, requests_each=reqs, window=4, batch=256,
            batch_flush_us=4000, gateways=1, deadline_s=900,
        )
        _check_row(row, n)
        rows.append(row)
    out = tmp_path / "curve.jsonl"
    out.write_text("".join(json.dumps(r) + "\n" for r in rows))
    assert bench_compare.main(
        [str(out), str(out), "--group-by", "replicas"]
    ) == 0

"""The reduction from a profiler trace (``.xplane.pb``) to device numbers.

One function, ``reduce_trace``, reads the traced slice of a run with
``jax.profiler.ProfileData`` and returns plain numbers; the per-layer readers
in ``reducers/`` pick theirs out of it. Nothing here runs on a device, so the
arithmetic has a test on a small hand-made trace (``tests/data/tiny.xplane.pb``,
written by ``tests/data/make_tiny_trace.py``).

What is read:

- the host plane's ``chipbench.slice`` span, which ``verifyd_wrap.py`` holds
  open for the length asked: the slice's two edges in the trace's own clock.
  Everything is cut to it, so a device that idles at the slice's edge counts
  as idle;
- device planes, ``/device:TPU:<n>``: the ``XLA Ops`` line holds one event
  per operation that ran on the chip, the ``XLA Modules`` line one event per
  launch of a compiled executable;
- the host plane's ``engine.verify`` spans, which ``verifyd_wrap.py`` puts
  round every ``ShardedVerifyEngine.verify`` call, each with its ``items``.

Busy time is the union of the operations' intervals. A launch on the device
belongs to the ``engine.verify`` span that holds it, so it is known how many
items it carried and hence which padded shape ran. An idle gap of a device is
named by what the host was doing in it: inside an ``engine.verify`` span
(staging a window, or reading verdicts back), outside every span (waiting
for a window), or inside an executable between two of its operations.
"""

from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENGINE_SPAN = "engine.verify"  # verifyd_wrap.py opens both
SLICE_SPAN = "chipbench.slice"
SLACK_NS = 1e6  # the host's clock and the device's, against launches of 5 ms and more


def find_xplane(trace_dir) -> Path:
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def total(intervals) -> float:
    return sum(end - start for start, end in intervals)


def overlap(a, b) -> float:
    """Total length two merged interval lists share."""
    i = j = 0
    acc = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            acc += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return acc


def gaps(busy, lo: float, hi: float) -> list:
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append([at, start])
        at = max(at, end)
    if hi > at:
        out.append([at, hi])
    return out


def _short(name: str) -> str:
    """An operation's event name is its whole HLO line: "%while.227 = (s32[]...".
    Keep what names it."""
    return name.split(" = ")[0].lstrip("%")[:64]


def _events(line):
    return [(_short(e.name), e.start_ns, e.start_ns + e.duration_ns) for e in line.events]


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


def match_launches(modules, spans) -> list:
    """-> [(module, start, end, items or None)]. A launch belongs to the
    span that holds it. Where two spans hold it (two launches in flight),
    it is the one that ends first and has no launch yet: the device runs
    launches in the order they were dispatched, and a span ends when its
    verdicts are back. A span that has one may take more (a window beyond
    the top rung runs in chunks). ``SLACK_NS`` allows for the two clocks."""
    taken: set = set()
    out = []
    for name, start, end in sorted(modules, key=lambda m: m[2]):
        holding = [
            (s_end, i) for i, (s_start, s_end, _) in enumerate(spans)
            if s_start - SLACK_NS <= start and end <= s_end + SLACK_NS
        ]
        free = [h for h in holding if h[1] not in taken]
        items = None
        if holding:
            _, i = min(free or holding)
            taken.add(i)
            items = spans[i][2]
        out.append((name, start, end, items))
    return out


def _read_slice(path):
    """-> (lo, hi, [(plane, ops, modules)], [(start, end, items)])"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices, spans, edges = [], [], []
    for plane in data.planes:
        if DEVICE_PLANE.fullmatch(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    modules = _events(line)
            devices.append((plane.name, ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ENGINE_SPAN:
                        items = dict(e.stats).get("items")
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, items))
                    elif e.name == SLICE_SPAN:
                        edges.append((e.start_ns, e.start_ns + e.duration_ns))
    if not devices:
        raise ValueError(f"{path}: no /device:TPU:<n> plane in the trace")
    if len(edges) != 1:
        raise ValueError(f"{path}: {len(edges)} {SLICE_SPAN} spans in the trace, not one")
    return edges[0][0], edges[0][1], devices, spans


def reduce_trace(path) -> dict:
    """The slice -> {window_s, busy_s, devices, launches [{module, items,
    seconds}] (those wholly inside the slice), modules {name: {launches,
    seconds}}, ops [(name, seconds)], idle {class: seconds}, longest_gaps
    [(class, seconds)]}; seconds throughout, busy and idle averaged over the
    device planes."""
    lo, hi, devices, spans = _read_slice(path)
    busy_s = 0.0
    op_seconds: dict = {}
    module_stats: dict = {}
    launches: list = []
    idle = {"inside_an_executable_between_its_operations": 0.0,
            "inside_engine.verify_host_staging_or_readback": 0.0,
            "outside_engine.verify_waiting_for_a_window": 0.0}
    longest: list = []
    span_union = union(_clip(((s, e) for s, e, _ in spans), lo, hi))
    for _, ops, modules in devices:
        busy = union(_clip(((s, e) for _, s, e in ops), lo, hi))
        busy_s += total(busy)
        for name, s, e in ops:
            inside = min(e, hi) - max(s, lo)
            if inside > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + inside
        for name, s, e, items in match_launches(modules, spans):
            if s < lo or e > hi:
                continue
            # "jit_fn(1234567890)" -> "jit_fn": the number is a fingerprint.
            short = name.split("(")[0]
            stat = module_stats.setdefault(short, {"launches": 0, "seconds": 0.0})
            stat["launches"] += 1
            stat["seconds"] += e - s
            launches.append({"module": short, "items": items, "seconds": (e - s) * 1e-9})
        module_union = union(_clip(((s, e) for _, s, e in modules), lo, hi))
        for gap in gaps(busy, lo, hi):
            g = [gap]
            in_module = overlap(g, module_union)
            rest = (gap[1] - gap[0]) - in_module
            in_span = min(rest, max(0.0, overlap(g, span_union) - in_module))
            parts = {
                "inside_an_executable_between_its_operations": in_module,
                "inside_engine.verify_host_staging_or_readback": in_span,
                "outside_engine.verify_waiting_for_a_window": rest - in_span,
            }
            for cls, ns in parts.items():
                idle[cls] += ns
            cls = max(parts, key=parts.get)
            longest.append((cls, gap[1] - gap[0]))
    n = len(devices)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_s * ns / n,
        "devices": n,
        "launches": launches,
        "modules": {
            name: {"launches": st["launches"], "seconds": st["seconds"] * ns}
            for name, st in module_stats.items()
        },
        "ops": sorted(
            ((name, sec * ns) for name, sec in op_seconds.items()),
            key=lambda kv: -kv[1],
        ),
        "idle": {cls: sec * ns / n for cls, sec in idle.items()},
        "longest_gaps": [
            (cls, sec * ns) for cls, sec in sorted(longest, key=lambda kv: -kv[1])[:6]
        ],
    }


def device_seconds_by_rung(reduced: dict, module, ladder) -> dict:
    """{rung: [device seconds of each launch of ``module`` that ran at it]}
    from the launches whose item count is known; every module for None."""
    import stats

    out: dict = {}
    for launch in reduced["launches"]:
        if launch["items"] and module in (None, launch["module"]):
            out.setdefault(stats.rung_of(launch["items"], ladder), []).append(launch["seconds"])
    return out


def breakdown(reduced: dict, ladder=()) -> dict:
    """The result line's ``breakdown``: at most 10 entries a list. The
    device's operations by time, then the launches by the padded shape they
    ran at (seconds of all of them; the count is in the name)."""
    idle = [[f"total_{cls}", sec] for cls, sec in reduced["idle"].items()]
    idle += [[f"longest_{cls}", sec] for cls, sec in reduced["longest_gaps"]]
    by_rung = [
        [f"launches_at_{rung}_slots_x{len(secs)}", sum(secs)]
        for rung, secs in sorted(device_seconds_by_rung(reduced, None, ladder).items())
    ] if ladder else []
    ops = [[name, sec] for name, sec in reduced["ops"][: 10 - len(by_rung[:5])]]
    return {"device_ops": ops + by_rung[:5], "idle_gaps": idle[:10]}

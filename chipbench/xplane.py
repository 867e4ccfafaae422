"""The reduction from a profiler trace (``.xplane.pb``) to device numbers.

One function, ``reduce_trace``, reads the traced slice of a run with
``jax.profiler.ProfileData`` and returns plain numbers; the per-layer readers
in ``reducers/`` pick theirs out of it. Nothing here runs on a device, so the
arithmetic has tests on small hand-made traces (``tests/data/``).

What is read:

- the host plane's ``chipbench.slice`` span, which ``verifyd_wrap.py`` holds
  open for the length asked: the slice's two edges in the trace's own clock.
  Everything is cut to it, so a device that idles at the slice's edge counts
  as idle;
- device planes, ``/device:TPU:<n>``: the ``XLA Ops`` line holds one event
  per operation that ran on the chip, the ``XLA Modules`` line one event per
  launch of a compiled executable, named ``jit_fn(<fingerprint>)``;
- the host plane's ``engine.verify`` spans, which ``verifyd_wrap.py`` puts
  round every ``ShardedVerifyEngine.verify`` call, each with its ``items``.

Busy time is the union of the operations' intervals. An idle gap of a device
is named by what the host was doing in it: inside an ``engine.verify`` span
(staging a window, or reading verdicts back), outside every span (waiting
for a window), or inside an executable between two of its operations.

**One launch, N planes.** An executable sharded over N chips shows as one
event on each of N device planes. ``merge_planes`` makes one launch of them,
its device time the longest of its planes; ``planes`` says how many carried
it, so that a reader can set the work of ``slots / planes`` rows against one
chip's peak. Busy and idle time are averaged over the device planes.

**The shape a launch ran at** is what the program says it ran, never what
its item count would fit: ``verifyd --trace`` writes a line a window with
``size`` (items), ``rung`` (the padded slots run, summed over the window's
chunks), ``chunks`` and ``t_dev`` (the first dispatch, on the host's clock).
``label`` finds each ``engine.verify`` span's line (the same ``size``, its
``t_dev`` inside the span: the slice's end is known on both clocks), hence
the shapes the span's chunks ran at (``shapes_run``). A compiled shape is one
executable and so one fingerprint: every launch in the slice says which
shapes it may have run at (those of the spans that hold it), an executable
is what all its launches allow, and a shape that one executable has taken
is no other's; a span that alone holds all its chunks ran them in the order
of its plan. So a second chunk is never handed to the other span in flight,
and a launch that two spans hold is still told by its fingerprint.

**A launch the trace's end cut.** The device stops recording some
milliseconds before the host's slice span closes, so a launch in flight there
is an event that ends inside the slice with part of its operations and part
of its time (1.29 and 2.19 ms of a 5.32 ms executable, PR 35). An executable
runs the same operations in every launch: a launch that shows under ``WHOLE``
of the operations its executable's fullest launch shows is left out, like one
that crosses the slice's edge, and counted under ``cut``.
"""

from __future__ import annotations

import re
import socket
import time
from bisect import bisect_left
from pathlib import Path

DEVICE_PLANE = re.compile(r"/device:TPU:\d+")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENGINE_SPAN = "engine.verify"  # verifyd_wrap.py opens both
SLICE_SPAN = "chipbench.slice"
SLACK_NS = 1e6  # the host's clock and the device's, against launches of 5 ms and more
WHOLE = 0.98  # of the operations the fullest launch of its executable shows
IDLE_CLASSES = (
    "inside_an_executable_between_its_operations",
    "inside_engine.verify_host_staging_or_readback",
    "outside_engine.verify_waiting_for_a_window",
)


def write_xspace(blob: bytes, trace_dir) -> Path:
    """The serialised xspace of a profiler session where TensorBoard's
    export would have put it, and nothing beside it."""
    run = Path(trace_dir, "plugins", "profile", time.strftime("%Y_%m_%d_%H_%M_%S"))
    run.mkdir(parents=True, exist_ok=True)
    path = run / f"{socket.gethostname()}.xplane.pb"
    path.write_bytes(blob)
    return path


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
    short: dict = {}  # some hundred names for some hundred thousand events
    out = []
    for e in line.events:
        name = e.name
        if name not in short:
            short[name] = _short(name)
        out.append((short[name], e.start_ns, e.start_ns + e.duration_ns))
    return out


def _plain(name: str) -> str:
    """"jit_fn(1234567890)" -> "jit_fn": the number is a fingerprint."""
    return name.split("(")[0]


def _clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if min(e, hi) > max(s, lo)]


# -- the shapes a window ran at, from its launch line ---------------------------------


def shapes_run(line: dict, ladder) -> tuple:
    """The padded shapes the window of a ``verify_batch`` line ran at, one
    a chunk, largest first (the order the engine dispatches them in): the
    ``chunks`` shapes of the ladder that sum to ``rung``. With up to four
    chunks on a ladder that grows fourfold there is one such cover; beyond
    that the one with the most of the largest shapes, which is how the
    engine cuts a window above its top shape. Nothing for a line without
    the two fields (a backend that is not the sharded engine)."""
    if "rung" not in line:
        return ()
    return _cover(int(line["rung"]), int(line.get("chunks", 1)), sorted(ladder, reverse=True)) or ()


def _cover(slots: int, chunks: int, shapes: list):
    if chunks == 0 or not shapes:
        return () if slots == 0 and chunks == 0 else None
    for k, shape in enumerate(shapes):
        if shape * chunks < slots:
            return None  # not even the largest shape left reaches the sum
        if shape <= slots:
            rest = _cover(slots - shape, chunks - 1, shapes[k:])
            if rest is not None:
                return (shape,) + rest
    return None


def label(spans, lines, ladder, instant) -> list:
    """-> per span, the shapes its window ran at (``()`` where no line is
    its). ``spans``: [(start ns, end ns, items)] on the trace's clock;
    ``lines``: the run's ``verify_batch`` records; ``instant``: one moment
    as (the trace's nanoseconds, the host's monotonic seconds)."""
    at_ns, at_s = instant
    by_size: dict = {}
    for rec in lines:
        if "t_dev" in rec:
            by_size.setdefault(rec["size"], []).append((rec["t_dev"], rec))
    for recs in by_size.values():
        recs.sort(key=lambda r: r[0])
    slack = SLACK_NS * 1e-9
    out = []
    for start, end, items in spans:
        lo = at_s + (start - at_ns) * 1e-9 - slack
        hi = at_s + (end - at_ns) * 1e-9 + slack
        recs = by_size.get(items, [])
        at = bisect_left(recs, lo, key=lambda r: r[0])
        found = recs[at][1] if at < len(recs) and recs[at][0] <= hi else None
        out.append(shapes_run(found, ladder) if found else ())
    return out


# -- launches ----------------------------------------------------------------------------


def merge_planes(planes: list) -> list:
    """[[(name, start, end)] a plane] -> [{name, start, end, seconds,
    planes}], by start: an executable sharded over N chips is one event a
    plane and ONE launch, from the first plane's start to the last one's end,
    its device time the longest a plane took. Events of two planes are the
    same launch where they carry the same name and share at least half of
    the shorter one's length (the chips of a mesh start a launch within
    microseconds of each other, and a plane runs one launch at a time)."""
    open_: list = []
    out: list = []
    events = sorted(
        ((s, e, name, p) for p, modules in enumerate(planes) for name, s, e in modules)
    )
    for s, e, name, p in events:
        home = None
        for launch in open_:
            if launch["name"] == name and p not in launch["_on"]:
                shared = min(e, launch["end"]) - max(s, launch["start"])
                if 2 * shared >= min(e - s, launch["end"] - launch["start"]):
                    home = launch
                    break
        if home is None:
            home = {"name": name, "start": s, "end": e, "seconds": 0.0, "_on": set()}
            open_.append(home)
            out.append(home)
        home["_on"].add(p)
        home["end"] = max(home["end"], e)
        home["seconds"] = max(home["seconds"], e - s)
        open_ = [x for x in open_ if x["end"] > s]
    for launch in out:
        launch["planes"] = len(launch.pop("_on"))
    return out


def holders(launch: dict, spans) -> list:
    """Indices of the spans that hold the launch, ``SLACK_NS`` allowed for
    the two clocks."""
    return [
        i for i, (s_start, s_end, _) in enumerate(spans)
        if s_start - SLACK_NS <= launch["start"] and launch["end"] <= s_end + SLACK_NS
    ]


def shape_of_each_executable(launches) -> dict:
    """{fingerprinted name: slots}, from each launch's ``may`` (the shapes of
    the spans that hold it; None where one of them has no line). An
    executable is one shape, so what ALL its launches allow, and a shape
    that one executable has taken is no other's: settled one by one. Kept
    apart by the executables' plain name (``jit_fn``), so that another
    program on the device takes no shape from this one. A name whose
    executables come out with no shape, or two with the same, is left out
    whole: its lines or spans are not this trace's."""
    groups: dict = {}
    for launch in launches:
        if launch["may"]:
            may = groups.setdefault(_plain(launch["name"]), {})
            allowed = set(launch["may"])
            may[launch["name"]] = may.get(launch["name"], allowed) & allowed
    out: dict = {}
    for may in groups.values():
        settled: dict = {}
        while True:
            new = {n: next(iter(c)) for n, c in may.items() if len(c) == 1 and n not in settled}
            if not new:
                break
            settled.update(new)
            for name, c in may.items():
                if name not in settled:
                    c -= set(new.values())
        if all(may.values()) and len(set(settled.values())) == len(settled):
            out.update(settled)
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
                    modules = [(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events]
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


def reduce_trace(path, lines=(), ladder=(), slice_end_s: float = None) -> dict:
    """The slice -> {window_s, busy_s, devices, planes (how many of them a
    launch runs on), launches [{module, slots, seconds, spans}] (those wholly
    inside the slice, on every one of those planes and with all their
    operations recorded; ``spans``: how many ``engine.verify`` spans hold
    it), cut (launches left out because the trace's end cut their
    operations short), modules {name: {launches, seconds}}, ops
    [(name, seconds)], idle {class: seconds}, longest_gaps [(class,
    seconds)]}; seconds throughout; busy, idle and the operations' seconds
    averaged over the device planes. ``lines`` (the run's ``verify_batch``
    records), ``ladder`` and ``slice_end_s`` (the slice's end on the host's
    monotonic clock) give each launch its ``slots``; None without them."""
    lo, hi, devices, spans = _read_slice(path)
    busy_s = 0.0
    op_seconds: dict = {}
    idle = dict.fromkeys(IDLE_CLASSES, 0.0)
    longest: list = []
    span_union = union(_clip(((s, e) for s, e, _ in spans), lo, hi))
    for _, ops, modules in devices:
        busy = union(_clip(((s, e) for _, s, e in ops), lo, hi))
        busy_s += total(busy)
        for name, s, e in ops:
            inside = min(e, hi) - max(s, lo)
            if inside > 0:
                op_seconds[name] = op_seconds.get(name, 0.0) + inside
        module_union = union(_clip(((s, e) for _, s, e in modules), lo, hi))
        for gap in gaps(busy, lo, hi):
            g = [gap]
            in_module = overlap(g, module_union)
            rest = (gap[1] - gap[0]) - in_module
            in_span = min(rest, max(0.0, overlap(g, span_union) - in_module))
            parts = dict(zip(IDLE_CLASSES, (in_module, in_span, rest - in_span)))
            for cls, ns in parts.items():
                idle[cls] += ns
            longest.append((max(parts, key=parts.get), gap[1] - gap[0]))
    merged = merge_planes([modules for _, _, modules in devices])
    width = max((x["planes"] for x in merged), default=1)
    shapes = label(spans, lines, ladder, (hi, slice_end_s or 0.0))
    for x in merged:
        x["held_by"] = holders(x, spans)
        held = [shapes[i] for i in x["held_by"]]
        x["may"] = sorted({s for plan in held for s in plan}) if held and all(held) else None
    for i, plan in enumerate(shapes):
        # A span that alone holds as many launches as its window had chunks
        # ran them in the order it dispatched them, which is the plan's.
        own = [x for x in merged if x["held_by"] == [i]]
        if plan and len(own) == len(plan):
            for x, slots in zip(own, plan):
                x["may"] = [slots]
    shape_of = shape_of_each_executable(merged)
    op_starts = [sorted(s for _, s, _ in ops) for _, ops, _ in devices]
    most: dict = {}
    for x in merged:
        x["ops"] = min(bisect_left(at, x["end"]) - bisect_left(at, x["start"]) for at in op_starts)
        most[x["name"]] = max(most.get(x["name"], 0), x["ops"])
    launches: list = []
    module_stats: dict = {}
    cut = 0
    ns = 1e-9
    for x in merged:
        if x["start"] < lo or x["end"] > hi or x["planes"] < width:
            continue
        if x["ops"] < WHOLE * most[x["name"]]:
            cut += 1
            continue
        short = _plain(x["name"])
        stat = module_stats.setdefault(short, {"launches": 0, "seconds": 0.0})
        stat["launches"] += 1
        stat["seconds"] += x["seconds"] * ns
        launches.append({
            "module": short, "slots": shape_of.get(x["name"]), "seconds": x["seconds"] * ns,
            "spans": len(x["held_by"]),
        })
    n = len(devices)
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy_s * ns / n,
        "devices": n,
        "planes": width,
        "launches": launches,
        "cut": cut,
        "modules": module_stats,
        "ops": sorted(
            ((name, sec * ns / n) for name, sec in op_seconds.items()),
            key=lambda kv: -kv[1],
        ),
        "idle": {cls: sec * ns / n for cls, sec in idle.items()},
        "longest_gaps": [
            (cls, sec * ns) for cls, sec in sorted(longest, key=lambda kv: -kv[1])[:6]
        ],
    }


def launches_by_shape(reduced: dict, module) -> dict:
    """{slots: [device seconds of each launch of ``module`` that ran at
    it]} from the launches whose shape is known; every module for None."""
    out: dict = {}
    for launch in reduced["launches"]:
        if launch["slots"] and module in (None, launch["module"]):
            out.setdefault(launch["slots"], []).append(launch["seconds"])
    return out


def breakdown(reduced: dict) -> dict:
    """The result line's ``breakdown``: at most 10 entries a list. The
    device's operations by time, then the launches by the padded shape they
    ran at (seconds of all of them; the count is in the name)."""
    idle = [[f"total_{cls}", sec] for cls, sec in reduced["idle"].items()]
    idle += [[f"longest_{cls}", sec] for cls, sec in reduced["longest_gaps"]]
    by_shape = [
        [f"launches_at_{slots}_slots_x{len(secs)}", sum(secs)]
        for slots, secs in sorted(launches_by_shape(reduced, None).items())
    ][:5]
    ops = [[name, sec] for name, sec in reduced["ops"][: 10 - len(by_shape)]]
    return {"device_ops": ops + by_shape, "idle_gaps": idle[:10]}

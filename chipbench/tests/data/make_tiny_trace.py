#!/usr/bin/env python3
"""Writes ``tiny.xplane.pb``, the hand-made trace the arithmetic is tested on
(needs TensorFlow's ``xplane_pb2``; the tests only read the file).

Times in units of 10 ms. One slice ``chipbench.slice`` [0.5, 9.5]. Host spans of ``engine.verify``:
A [1.0, 4.0] with 10 items, B [1.2, 8.6] with 80 (two launches in flight),
C [8.8, 12.0] with 10. One TPU plane with four launches of ``jit_fn``:
m0 [0.6, 0.9] in no span, m1 [1.5, 3.5] held by A and B (A ends first: A's),
m2 [3.6, 8.4] in B, m3 [9.0, 10.5] across the slice's edge. Operations:
fusion.1 [0.6, 0.75], [0.75, 0.9], [1.5, 2.0], [3.6, 4.0]; while.2 [2.1, 3.5],
[4.0, 8.4], [9.0, 10.5]: busy 75 ms of the slice's 90. Every launch inside
the slice shows both operations of its executable (``xplane.WHOLE``).
"""

from pathlib import Path

from tensorflow.tsl.profiler.protobuf import xplane_pb2

MS = 10**10  # the figures below are in units of 10 ms; picoseconds


def plane(space, name, lines):
    """lines: {line name: [(event name, start ms, end ms, {stat: value})]}"""
    p = space.planes.add(name=name)
    ids: dict = {}
    stat_ids: dict = {}
    for line_name, events in lines.items():
        line = p.lines.add(name=line_name, timestamp_ns=1000)
        for name_, start, end, stats in events:
            if name_ not in ids:
                ids[name_] = len(ids) + 1
                p.event_metadata[ids[name_]].id = ids[name_]
                p.event_metadata[ids[name_]].name = name_
            ev = line.events.add(metadata_id=ids[name_], offset_ps=round(start * MS),
                                 duration_ps=round((end - start) * MS))
            for key, value in stats.items():
                if key not in stat_ids:
                    stat_ids[key] = len(stat_ids) + 1
                    p.stat_metadata[stat_ids[key]].id = stat_ids[key]
                    p.stat_metadata[stat_ids[key]].name = key
                ev.stats.add(metadata_id=stat_ids[key], int64_value=value)


def main() -> None:
    space = xplane_pb2.XSpace()
    plane(space, "/device:TPU:0", {
        "XLA Modules": [("jit_fn(123)", 0.6, 0.9, {}), ("jit_fn(123)", 1.5, 3.5, {}),
                        ("jit_fn(456)", 3.6, 8.4, {}), ("jit_fn(123)", 9.0, 10.5, {})],
        "XLA Ops": [("%fusion.1 = s32[8,16,32]{2,1,0} fusion(s32[] %p)", 0.6, 0.75, {}),
                    ("%fusion.1 = s32[8,16,32]{2,1,0} fusion(s32[] %p)", 0.75, 0.9, {}),
                    ("%fusion.1 = s32[8,16,32]{2,1,0} fusion(s32[] %p)", 1.5, 2.0, {}),
                    ("%while.2 = (s32[]) while((s32[]) %t)", 2.1, 3.5, {}),
                    ("%fusion.1 = s32[8,16,32]{2,1,0} fusion(s32[] %p)", 3.6, 4.0, {}),
                    ("%while.2 = (s32[]) while((s32[]) %t)", 4.0, 8.4, {}),
                    ("%while.2 = (s32[]) while((s32[]) %t)", 9.0, 10.5, {})],
        "Steps": [("0", 0.6, 10.5, {})],
    })
    plane(space, "/host:CPU", {
        "control": [("chipbench.slice", 0.5, 9.5, {})],
        "launch-thread-1": [("engine.verify", 1.0, 4.0, {"items": 10}),
                            ("PjitFunction(fn)", 1.1, 1.2, {}),
                            ("engine.verify", 8.8, 12.0, {"items": 10})],
        "launch-thread-2": [("engine.verify", 1.2, 8.6, {"items": 80})],
    })
    out = Path(__file__).resolve().parent / "tiny.xplane.pb"
    out.write_bytes(space.SerializeToString())
    print(f"wrote {out} ({out.stat().st_size} bytes)")


if __name__ == "__main__":
    main()

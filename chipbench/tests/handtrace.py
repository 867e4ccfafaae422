"""Hand-made traces for the arithmetic's tests: ``data/<name>.json`` says in
milliseconds what ran where, ``build`` makes the xspace of it (through the
text form of the proto, which JAX itself parses) and the ``verify_batch``
lines that go with it.

    {"about": ..., "ladder": [..], "slice": [start, end],
     "windows": [{"span": [start, end], "t_dev": ms, "size": items,
                  "rung": slots run, "chunks": n}, ..],
     "planes": [{"modules": [[name, start, end], ..], "ops": [[name, start, end], ..]}, ..]}

A window is an ``engine.verify`` span on the host plane (a thread each) and
its launch line. The trace's clock starts at ``TRACE_0_NS``, the host's
monotonic clock reads ``MONO_0_S`` at that instant.
"""

import json
from pathlib import Path

DATA = Path(__file__).resolve().parent / "data"
TRACE_0_NS = 1_700_000_000_000_000_000
MONO_0_S = 5000.0


def _plane(name: str, lines: dict) -> str:
    """lines: {line name: [(event name, start ms, end ms, {stat: int})]}"""
    names: dict = {}
    stats: dict = {}
    out = [f'planes {{ name: "{name}"']
    for line, events in lines.items():
        out.append(f'lines {{ name: "{line}" timestamp_ns: {TRACE_0_NS}')
        for event, start, end, event_stats in events:
            eid = names.setdefault(event, len(names) + 1)
            fields = "".join(
                f" stats {{ metadata_id: {stats.setdefault(k, len(stats) + 1)} int64_value: {v} }}"
                for k, v in event_stats.items()
            )
            out.append(f"events {{ metadata_id: {eid} offset_ps: {round(start * 1e9)} "
                       f"duration_ps: {round((end - start) * 1e9)}{fields} }}")
        out.append("}")
    for event, eid in names.items():
        out.append(f'event_metadata {{ key: {eid} value {{ id: {eid} name: "{event}" }} }}')
    for stat, sid in stats.items():
        out.append(f'stat_metadata {{ key: {sid} value {{ id: {sid} name: "{stat}" }} }}')
    out.append("}")
    return "\n".join(out)


def build(name: str, out_dir: Path) -> dict:
    """-> {path, lines, ladder, slice_end_s}: what ``xplane.reduce_trace``
    takes, from ``data/<name>.json``."""
    from jax.profiler import ProfileData

    spec = json.loads((DATA / f"{name}.json").read_text())
    host = {"control": [("chipbench.slice", *spec["slice"], {})]}
    lines = []
    for k, w in enumerate(spec["windows"]):
        host[f"handler-{k}"] = [("engine.verify", *w["span"], {"items": w["size"]})]
        line = {key: w[key] for key in ("size", "rung", "chunks") if key in w}
        lines.append(dict(line, ev="verify_batch", secs=(w["span"][1] - w["span"][0]) / 1e3,
                          ts=MONO_0_S + w["span"][1] / 1e3, t_dev=MONO_0_S + w["t_dev"] / 1e3))
    text = "\n".join(
        [_plane(f"/device:TPU:{n}", {
            "XLA Modules": [(m, s, e, {}) for m, s, e in plane["modules"]],
            "XLA Ops": [(o, s, e, {}) for o, s, e in plane.get("ops", plane["modules"])],
        }) for n, plane in enumerate(spec["planes"])] + [_plane("/host:CPU", host)]
    )
    path = Path(out_dir) / f"{name}.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    return {"path": path, "lines": lines, "ladder": spec["ladder"],
            "slice_end_s": MONO_0_S + spec["slice"][1] / 1e3}

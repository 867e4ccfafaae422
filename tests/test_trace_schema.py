"""Tier-1 wiring for the schema lint (scripts/check_trace_schema.py): the
Python emitters and pbftd cannot drift from the event/metric manifest
(pbft_tpu/utils/trace_schema.py) without failing here."""

import importlib.util
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_lint():
    spec = importlib.util.spec_from_file_location(
        "check_trace_schema", REPO / "scripts" / "check_trace_schema.py"
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_emitters_match_manifest():
    errors = _load_lint().check()
    assert errors == [], "\n".join(errors)


def test_native_runtime_names_match_manifest():
    """Runtime half of the parity contract: the names the NATIVE runtime
    compiled in (core/metrics.cc tables via capi.cc) must equal the
    manifest's net.cc sets. Skipped where the native core isn't built —
    the static lint above still covers the sources."""
    from pbft_tpu import native

    if not native.available():
        pytest.skip("native core not built")
    import ctypes

    from pbft_tpu.utils import trace_schema

    lib = native.lib()
    for fn in ("pbft_metric_names", "pbft_trace_event_names"):
        if not hasattr(lib, fn):
            pytest.fail(f"stale libpbftcore.so: missing {fn}; rebuild")

    def names(fn):
        func = getattr(lib, fn)
        func.restype = ctypes.c_size_t
        buf = ctypes.create_string_buffer(8192)
        n = func(buf, len(buf))
        assert 0 < n < len(buf)
        return set(buf.value.decode().split("\n"))

    want_metrics = {
        name
        for name, (_, emitters) in trace_schema.METRIC_SCHEMAS.items()
        if "net.cc" in emitters
    }
    assert names("pbft_metric_names") == want_metrics
    want_events = {
        name
        for name, schema in trace_schema.EVENT_SCHEMAS.items()
        if "net.cc" in schema["emitters"]
    }
    assert names("pbft_trace_event_names") == want_events

"""pbft_tpu.utils — structured logging / tracing / metrics.

The reference's observability was ~110 println! calls, several inside the
poll hot loop (SURVEY.md §5 — a real throughput hazard); here tracing is
structured JSONL events behind a level check, off by default, and never
in the per-signature hot path (batch boundaries only), and metrics are a
Prometheus-style registry with the same one-attribute-check-when-disabled
discipline (utils/metrics.py). Event/metric names are contracted between
pbftd and the Python processes by utils/trace_schema.py.
"""

from .flight import FlightRecorder
from .metrics import (
    ConsensusSpans,
    MetricsRegistry,
    count_open_fds,
    read_rss_bytes,
    start_metrics_server,
)
from .trace import Tracer

__all__ = [
    "ConsensusSpans",
    "FlightRecorder",
    "MetricsRegistry",
    "Tracer",
    "count_open_fds",
    "read_rss_bytes",
    "start_metrics_server",
]

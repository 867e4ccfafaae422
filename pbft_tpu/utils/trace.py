"""Structured JSONL tracing for the Python processes.

Events are single JSON lines: {"ts": <monotonic>, "ev": <name>, ...fields}.
Disabled (no-op, one attribute check) unless a sink is set — tracing must
never tax the batching hot loop the way the reference's println!-in-poll
did (reference src/handler.rs:265,:269; SURVEY.md §5).
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import IO, Iterator, Optional


class Tracer:
    def __init__(self, sink: Optional[IO[str]] = None):
        self.sink = sink
        self._lock = threading.Lock()

    @property
    def enabled(self) -> bool:
        return self.sink is not None

    def event(self, ev: str, **fields) -> None:
        if self.sink is None:
            return
        rec = {"ts": round(time.monotonic(), 6), "ev": ev}
        rec.update(fields)
        # default=str: a non-JSON-serializable field value (a stray bytes
        # digest, an enum, a numpy scalar) degrades to its str() form
        # instead of throwing in the batching hot loop.
        line = json.dumps(rec, separators=(",", ":"), default=str) + "\n"
        with self._lock:
            self.sink.write(line)
            self.sink.flush()


# -- per-thread span record ---------------------------------------------------
#
# A caller that writes one trace line for a piece of work opens a record
# round the call; code further down the same thread's stack (which the
# caller reaches only through a plain callable, e.g. the verify service's
# ``backend(items) -> verdicts``) adds what it timed to ``current_span()``.
# Per thread, so two launches in flight never see each other's record.

_span = threading.local()


@contextlib.contextmanager
def open_span() -> Iterator[dict]:
    rec: dict = {}
    outer = current_span()
    _span.rec = rec
    try:
        yield rec
    finally:
        _span.rec = outer


def current_span() -> Optional[dict]:
    """The record the nearest ``open_span()`` on this thread yielded, or
    None when nobody up the stack asked for one."""
    return getattr(_span, "rec", None)

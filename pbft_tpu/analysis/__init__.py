"""Static-analysis passes over the C++ core and the Python round it
(ISSUE 8 tentpole).

One protocol is written down twice: the C++ core that serves, and the
Python reference (state machine, codec, WAL, handshake) with the gateway,
client and verify service that speak its wire — held together by
hand-mirrored constants and a shared metrics/trace manifest. Runtime fuzz
(test_wire_codec.py) guards the dynamic behavior; this package is the static complement:

    constants       C++ / Python constant conformance (wire magic,
                    message tags, protocol versions, config defaults,
                    RLC window, pad ladder, status handshake)
    async-blocking  no blocking calls inside ``async def`` in pbft_tpu/net
    metrics         every metric/trace emitter matches the manifest
                    (generalized successor of scripts/check_trace_schema)
    sockets         TCP_NODELAY / SO_REUSEADDR at every stream-socket
                    creation site, C++ and Python (ISSUE 10)

Entry point: ``scripts/pbft_lint.py`` (wired into tier-1 by
tests/test_lint.py). Every pass takes a ``root`` so the tests can run
them against shadow trees with deliberate violations.
"""

from __future__ import annotations

import pathlib
from typing import Callable, Dict, List

from . import async_blocking, constants, metrics_lint, sockets

REPO = pathlib.Path(__file__).resolve().parent.parent.parent

PASSES: Dict[str, Callable[[pathlib.Path], List[str]]] = {
    "constants": constants.check,
    "async-blocking": async_blocking.check,
    "metrics": metrics_lint.check,
    "sockets": sockets.check,
}


def run_all(root: pathlib.Path = REPO, passes=None) -> Dict[str, List[str]]:
    """pass name -> error list (empty = clean). Unknown names raise."""
    selected = list(PASSES) if passes is None else list(passes)
    unknown = [p for p in selected if p not in PASSES]
    if unknown:
        raise ValueError(f"unknown passes {unknown}; have {sorted(PASSES)}")
    return {name: PASSES[name](root) for name in selected}


def scanned_files(root: pathlib.Path = REPO) -> List[pathlib.Path]:
    """Every file any pass reads, absolute, deduplicated — the set a
    shadow tree (tests/test_lint.py) must copy for all passes to run."""
    paths = [root / rel for rel in constants.files_scanned()]
    paths += async_blocking.files_scanned(root)
    paths += metrics_lint.files_scanned(root)
    paths += sockets.files_scanned(root)
    out, seen = [], set()
    for p in paths:
        if p not in seen:
            seen.add(p)
            out.append(p)
    return out

"""Closed loop: a fixed population of outstanding requests. Every identity
starts with ``outstanding_per_identity`` requests and sends its next one
the instant one completes, so a slow system is offered less load."""

from __future__ import annotations


def build(params: dict, rng, horizon_s: float):
    """-> (arrivals [(offset_s, identity)], resend_on_complete)."""
    del rng, horizon_s
    idents = int(params["identities"])
    per = int(params["outstanding_per_identity"])
    return [(0.0, i) for _ in range(per) for i in range(idents)], True

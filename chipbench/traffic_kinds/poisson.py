"""Open loop: Poisson arrivals at a fixed rate, independent of replies.

Every seed gets the SAME multiset of inter-arrival gaps (the exponential
distribution's mid-quantiles) in another order, and the same number of
requests per identity in another assignment, so that the seed moves the
order of the work and not its amount."""

from __future__ import annotations

import math


def build(params: dict, rng, horizon_s: float):
    """-> (arrivals [(offset_s, identity)], resend_on_complete)."""
    rate = float(params["rate_per_s"])
    idents = int(params["identities"])
    count = int(round(rate * horizon_s))
    gaps = [-math.log(1.0 - (k + 0.5) / count) / rate for k in range(count)]
    rng.shuffle(gaps)
    who = [k % idents for k in range(count)]
    rng.shuffle(who)
    arrivals, t = [], 0.0
    for gap, ident in zip(gaps, who):
        t += gap
        arrivals.append((t, ident))
    return arrivals, False

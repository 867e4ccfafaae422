"""A percentile (``q``) of the time from the instant a request was DUE to
the reply that completed its quorum, over the requests due inside the
window, in milliseconds. A request that never completed counts with the
whole time to the end of the drain: it is missing, not left out."""

import stats
from reducers._window import due_in_window


def reduce(run: dict, args: dict):
    gen = run["gen"]
    end = run["t1"] + float(run["traffic"]["drain_s"])
    lat = [
        (gen["done"][i] if gen["done"][i] is not None else end) - gen["due"][i]
        for i in due_in_window(run)
    ]
    return 1e3 * stats.percentile(lat, args["q"]) if lat else None

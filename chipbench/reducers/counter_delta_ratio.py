"""What a /metrics counter (``counter``) gained between the window's edges,
on the primary (``replicas``: "primary") or pooled over every replica
("all"), over the requests committed in the window; over the gain of another
counter instead where ``over`` names one, and alone where ``over`` is
"nothing". Nothing where a scrape lacks a counter (a program older than the
counter), or where the divisor is 0."""

import stats
from reducers._window import completed_in_window


def reduce(run: dict, args: dict):
    a, b = run["edge_a"]["metrics"], run["edge_b"]["metrics"]
    if args.get("replicas", "primary") == "primary":
        status = run["edge_b"]["status"]
        which = [status[0]["view"] % len(status)]
    else:
        which = range(len(b))

    def gain(name: str):
        if any((name, "") not in b[i] for i in which):
            return None
        return sum(stats.counter_delta(a[i], b[i], name) for i in which)

    over = args.get("over", "requests")
    top = gain(args["counter"])
    if over == "nothing":
        return top
    bottom = completed_in_window(run) if over == "requests" else gain(over)
    return top / bottom if top is not None and bottom else None

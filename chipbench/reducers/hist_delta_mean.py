"""The mean of what a /metrics histogram (``histogram``) observed between
the window's two edges, times ``scale``; on the primary (``replicas``:
"primary") or pooled over every replica ("all")."""

import stats


def reduce(run: dict, args: dict):
    a, b = run["edge_a"], run["edge_b"]
    if args.get("replicas", "primary") == "primary":
        primary = b["status"][0]["view"] % len(b["status"])
        which = [primary]
    else:
        which = range(len(b["metrics"]))
    total = count = 0.0
    for i in which:
        d_sum, d_cnt = stats.hist_delta(a["metrics"][i], b["metrics"][i], args["histogram"])
        total += d_sum
        count += d_cnt
    return args.get("scale", 1.0) * total / count if count else None

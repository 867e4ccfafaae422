"""What the /metrics counters of ``top`` gained between the window's edges,
summed, over what those of ``bottom`` gained, summed: a share of a whole
that the program exports as its parts (a thread's busy stages over all its
stages). On the primary (``replicas``: "primary") or pooled over every
replica ("all"). Nothing where a scrape lacks one of the counters (a program
older than it), or where the divisor is 0."""

import stats


def reduce(run: dict, args: dict):
    a, b = run["edge_a"]["metrics"], run["edge_b"]["metrics"]
    if args.get("replicas", "primary") == "primary":
        status = run["edge_b"]["status"]
        which = [status[0]["view"] % len(status)]
    else:
        which = range(len(b))

    def gain(names):
        if any((name, "") not in b[i] for name in names for i in which):
            return None
        return sum(stats.counter_delta(a[i], b[i], name) for name in names for i in which)

    top, bottom = gain(args["top"]), gain(args["bottom"])
    return top / bottom if top is not None and bottom else None

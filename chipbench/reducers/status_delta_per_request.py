"""What a /status counter (``field``) on the primary gained between the
window's edges, over the requests committed in the window."""

from reducers._window import completed_in_window


def reduce(run: dict, args: dict):
    a, b = run["edge_a"]["status"], run["edge_b"]["status"]
    primary = b[0]["view"] % len(b)
    done = completed_in_window(run)
    return (b[primary][args["field"]] - a[primary][args["field"]]) / done if done else None

"""A /status field (``field``) as the replicas report it at the window's
closing edge, over every replica: the least (``stat``: "min"), the most
("max") or the sum ("sum"). Nothing where a replica's document lacks the
field (a program older than the field)."""

_STATS = {"min": min, "max": max, "sum": sum}


def reduce(run: dict, args: dict):
    values = [d.get(args["field"]) for d in run["edge_b"]["status"]]
    if any(not isinstance(v, (int, float)) or isinstance(v, bool) for v in values):
        return None
    return float(_STATS[args["stat"]](values))

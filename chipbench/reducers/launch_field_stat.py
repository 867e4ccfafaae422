"""One statistic (``stat``: mean, max or p50) over the window's launches of
the sum of the named ``fields`` of verifyd's per-launch records (the stages
the program times itself: ``queue_s``, ``slot_s``, ``pad_s``, ...), times
``scale``. Launches that lack a field are left out; nothing where every
launch does (a program from before the spans, a backend that is not the
sharded engine)."""

import statistics

import stats

_STATS = {
    "mean": statistics.fmean,
    "max": max,
    "p50": lambda values: stats.percentile(values, 50),
}


def reduce(run: dict, args: dict):
    fields = args["fields"]
    sums = [sum(e[f] for f in fields) for e in run["launches"] if all(f in e for f in fields)]
    return args.get("scale", 1.0) * _STATS[args["stat"]](sums) if sums else None

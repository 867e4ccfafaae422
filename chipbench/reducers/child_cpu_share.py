"""The CPU seconds one of the run's child processes (``child``: "gateway",
"verifyd", "pbftd-0", "loadgen-0", ... as the harness names them) used
between the window's edges, user and system, over the window's length: its
share of one core (a process with several threads may pass 1). Nothing where
the run kept no such reading (a harness from before it, a child that had
ended by the closing edge)."""

import math


def reduce(run: dict, args: dict):
    used = run.get("cpu_window", {}).get(args["child"])
    if not isinstance(used, (int, float)) or math.isnan(used) or not run.get("seconds"):
        return None
    return used / run["seconds"]

"""What the two kernel readers share: the device time of a launch at each
padded shape (rung), from the traced slice, set against the rungs that ALL
the window's launches ran at, from ``verifyd --trace``'s lines.

The slice holds a dozen launches, the window some thousands, and an
executable's device time hardly moves from launch to launch while the mix of
rungs does from slice to slice. So the time per rung comes from the slice
and the mix from the whole window; a rung the slice never saw is left out on
both sides.
"""

import statistics

import stats
import xplane


def weighted(run: dict, module: str) -> list:
    """-> [(rung, launches in the window, mean device seconds)]"""
    if not run["trace"]:
        return []
    seen = xplane.device_seconds_by_rung(run["trace"], module, run["ladder"])
    window: dict = {}
    for e in run["launches"]:
        rung = stats.rung_of(e["size"], run["ladder"])
        window[rung] = window.get(rung, 0) + 1
    return [(r, n, statistics.fmean(seen[r])) for r, n in sorted(window.items()) if r in seen]

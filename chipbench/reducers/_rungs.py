"""What the two kernel readers share: the device time of a launch at each
padded shape, from the traced slice, set against the shapes that ALL the
window's launches ran at, from ``verifyd --trace``'s lines.

A launch here is one executable on the device: a window that ran as chunks
is as many launches, each at its own shape (``xplane.shapes_run`` reads them
off the line's ``rung`` and ``chunks``; ``xplane.reduce_trace`` gives every
launch of the slice the shape its executable ran at). The slice holds a dozen
launches, the window some thousands, and an executable's device time hardly
moves from launch to launch while the mix of shapes does from slice to slice.
So the time per shape comes from the slice and the mix from the whole window;
a shape the slice never saw is left out on both sides.
"""

import statistics
from collections import Counter

import xplane


def weighted(run: dict, module: str) -> list:
    """-> [(slots, launches in the window, mean device seconds)]"""
    if not run["trace"]:
        return []
    seen = xplane.launches_by_shape(run["trace"], module)
    window = Counter(
        slots for e in run["launches"] for slots in xplane.shapes_run(e, run["ladder"])
    )
    return [(s, window[s], statistics.fmean(seen[s])) for s in sorted(seen) if window[s]]

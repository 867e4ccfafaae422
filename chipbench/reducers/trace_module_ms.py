"""Device time of one launch of a compiled executable (``module``), in
milliseconds: the mean over the window's launches (a chunk of a split window
is a launch), each taken at the device time its padded shape showed in the
traced slice (``_rungs.py``). Nothing without a trace."""

from reducers import _rungs


def reduce(run: dict, args: dict):
    rows = _rungs.weighted(run, args["module"])
    if not rows:
        return None
    return 1e3 * sum(n * sec for _, n, sec in rows) / sum(n for _, n, _ in rows)

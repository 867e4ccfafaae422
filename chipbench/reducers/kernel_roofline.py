"""A kernel's share of its roofline, per chip: the least time ONE chip could
take for its part of the window's launches (operations over the peak rate,
or bytes over the peak bandwidth, whichever is longer) over the device time
the executable (``module``) takes for them, each launch at the device time its
padded shape showed in the traced slice (``_rungs.py``). A launch sharded over
N chips is ``slots / N`` rows a chip (the trace says on how many planes a
launch ran) and its device time the longest of its planes, so the operations
and bytes are ``kernel_ops.<ops>`` of ``slots / N`` rows, against the peaks
of one chip from ``peaks.json`` by device kind. Nothing without a trace."""

import kernel_ops
from reducers import _rungs


def reduce(run: dict, args: dict):
    peaks = run["peaks"]
    rows = _rungs.weighted(run, args["module"])
    if not rows or not peaks:
        return None
    count = getattr(kernel_ops, args["ops"])
    chips = run["trace"]["planes"]
    need = [(n, count(-(-slots // chips))) for slots, n, _ in rows]
    least = max(
        sum(n * c["ops"] for n, c in need) / peaks[args["ops_peak"]],
        sum(n * c["bytes"] for n, c in need) / peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / sum(n * sec for _, n, sec in rows)

"""The share of the WHOLE timed window in which no launch was between its
dispatch and the arrival of its verdicts: 1 - the union of [``start``,
``start`` + the sum of ``lengths``] over the window's launches, cut to the
window, over the window. With nothing in flight the device has nothing to
run, so this is the host's floor under the device's idle share (inside the
union the device may still idle: staging, the read-back), from every launch
of the run, beside the traced slice's 0.2 s sample. A launch whose record
falls after the window's end is not among the window's launches, which can
overstate the figure by one or two launches' length."""

import xplane


def reduce(run: dict, args: dict):
    start, lengths = args["start"], args["lengths"]
    spans = [
        (e[start], e[start] + sum(e[f] for f in lengths))
        for e in run["launches"] if start in e and all(f in e for f in lengths)
    ]
    if not spans:
        return None
    busy = xplane.total(xplane.union(xplane._clip(spans, run["t0"], run["t1"])))
    return 100.0 * (1.0 - busy / (run["t1"] - run["t0"]))

"""The share of the traced slice in which no operation ran on the device;
the slice runs from edge to edge of its ``chipbench.slice`` span."""


def reduce(run: dict, args: dict):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])

"""Helpers the readers share: which requests belong to the window."""


def due_in_window(run: dict) -> list:
    """Indices of the requests that were due inside the timed window."""
    t0, t1 = run["t0"], run["t1"]
    return [i for i, due in enumerate(run["gen"]["due"]) if t0 <= due < t1]


def completed_in_window(run: dict) -> int:
    """Requests whose quorum completed inside the timed window."""
    t0, t1 = run["t0"], run["t1"]
    return sum(d is not None and t0 <= d < t1 for d in run["gen"]["done"])

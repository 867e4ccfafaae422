"""A percentile (``q``) of how late the generator sent: the instant the
request went onto the socket minus the instant it was due, milliseconds,
over the requests due inside the window."""

import stats
from reducers._window import due_in_window


def reduce(run: dict, args: dict):
    gen = run["gen"]
    late = [gen["sent"][i] - gen["due"][i] for i in due_in_window(run)]
    return 1e3 * stats.percentile(late, args["q"]) if late else None

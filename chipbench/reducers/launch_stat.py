"""One figure (``field``) of verifyd's per-launch records inside the
window: ``items_per_launch``, ``pad_fill`` (items over the padded slots each
launch RAN, the record's ``rung``), ``launch_ms_p50`` (host clock round the
launch, with staging and readback), ``window_max_items``."""

import stats


def reduce(run: dict, args: dict):
    return stats.launch_stats(run["launches"]).get(args["field"])

"""The device itself: the share of the traced window in which no
operation ran (``idle_pct``), or the peak of memory in use on the fullest
chip (``peak_hbm_gib``)."""


def read(args, view):
    if view.device["platform"] != "tpu":
        return None
    if args["stat"] == "peak_hbm_gib":
        return view.device["memory_peak_bytes"] / 2 ** 30
    if args["stat"] == "idle_pct" and view.trace is not None:
        return 100.0 * (1.0 - view.trace["busy_s"] / view.trace["window_s"])
    return None

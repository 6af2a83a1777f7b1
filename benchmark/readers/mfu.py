"""The whole step's share of the chip's peak: the operations the window's
work required (``required_flops``, from ``harness/flops.py``) over the
window, the chips and the published bf16 peak."""


def read(args, view):
    c = view.result["counters"]
    if view.peaks is None or not c.get("required_flops"):
        return None
    return 100.0 * c["required_flops"] / c["window_s"] / \
        (view.device["count"] * view.peaks["bf16_flops"])

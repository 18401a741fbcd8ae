"""Whole served window: the model operations that every prompt and token
served in the traced window required, remote and hedge alike, over the
window's length times the chips times each chip's bf16 peak."""
from chipbench import readings


def read(view):
    if view.trace is None:
        return None
    flops = readings.window_flops(view)
    peak = view.peak["bf16_flops_per_s"] * view.chips * view.trace.window_s
    return 100.0 * flops / peak

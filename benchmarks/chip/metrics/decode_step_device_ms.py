"""Model step, decode: device time per run of the remote model's decode
program, from the profiler trace of the traced window."""
import numpy as np

from chipbench import readings


def read(view):
    runs = readings.served_runs(view, "decode_fn")
    return 1e3 * float(np.mean(runs)) if runs else None

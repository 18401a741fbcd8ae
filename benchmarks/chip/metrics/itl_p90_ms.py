"""Continuous tier, decode: 90th percentile of every gap between two
consecutive streamed tokens of a request."""
import numpy as np

from chipbench import readings


def read(view):
    gaps = [np.diff(r.chunks_s) * 1e3 for r in view.clean if len(r.chunks_s) > 1]
    return readings.p90(np.concatenate(gaps)) if gaps else None

"""Continuous tier, prefill and graft: 90th percentile of the time from a
request's due time to its first streamed token."""
from chipbench import readings


def read(view):
    return readings.p90(
        [1e3 * (r.chunks_s[0] - r.due_s) for r in view.clean if r.chunks_s]
    )

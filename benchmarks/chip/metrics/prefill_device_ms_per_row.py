"""Model step, prefill: device time of the remote model's prefill runs in
the traced window, per prompt they prefilled."""
from chipbench import readings


def read(view):
    runs = readings.prefill_runs(view)
    rows = sum(r for _, r in runs)
    return 1e3 * sum(s for s, _ in runs) / rows if rows else None

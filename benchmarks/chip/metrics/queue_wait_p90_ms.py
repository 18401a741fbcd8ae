"""Admission and policy: 90th percentile of the wait from a request's due
time to the tick that scheduled it (the loop's clock is the wall clock
since the window opened, so the future's ``scheduled_ms`` minus its due
time is that wait)."""
from chipbench import readings


def read(view):
    waits = [
        r.scheduled_ms - 1e3 * (r.due_s - view.t0)
        for r in view.clean if r.scheduled_ms is not None
    ]
    return readings.p90(waits)

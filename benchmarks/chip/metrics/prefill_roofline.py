"""Step programs, prefill: the least time the chip could take for the
prefill runs of the traced window over the device time they took.  The
work covers the real prompt tokens only (``counts.prefill_flops``), the
weights read once per run and the keys and values written for real
positions (``counts.prefill_bytes``).  Work and time come from the same
runs: those in the trace, with the prompts the host stamped from each."""
from chipbench import counts, readings


def read(view):
    runs = readings.prefill_runs(view)
    if not runs:
        return None
    m, P = view.cfg.remote, int(view.mix["prompt_tokens"])
    least = sum(
        counts.least_seconds(rows * counts.prefill_flops(m, P),
                             counts.prefill_bytes(m, rows, P), view.peak)
        for _, rows in runs
    )
    return 100.0 * least / sum(s for s, _ in runs)

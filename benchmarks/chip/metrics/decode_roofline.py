"""Step programs, decode: the least time the chip could take for the decode
runs of the traced window over the device time they took.

The work is what the algorithm needs: every run reads all the weights, and
each token it decodes reads the keys and values of its real positions
(``counts.decode_bytes``) and does its operations
(``counts.decode_flops``).  A run's least time is the larger of operations
over peak and bytes over bandwidth.  Work and time come from the same runs:
those in the trace, with the tokens the host stamped from each."""
from chipbench import counts, readings


def read(view):
    runs = readings.decode_runs(view)
    if not runs:
        return None
    m = view.cfg.remote
    least = sum(
        counts.least_seconds(sum(counts.decode_flops(m, p) for p in ps),
                             counts.decode_bytes(m, ps), view.peak)
        for _, ps in runs
    )
    return 100.0 * least / sum(s for s, _ in runs)

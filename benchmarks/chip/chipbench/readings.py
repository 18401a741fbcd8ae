"""Helpers shared by the per-layer metric readers in ``metrics/``.

A reader returns ``None`` where it finds nothing to read, and the run
leaves that metric out of its line.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

import numpy as np

from chipbench import counts

# The host stamps a token just after the device run that made it ends: the
# continuous tier waits for each prefill and decode run's tokens before it
# stamps them.  A stamp belongs to the run whose end lies nearest, if that
# end lies within these bounds of it on the trace's clock tied to
# ``perf_counter`` (the remote model's runs last 9 ms or more, so two runs
# never compete for one stamp).
STAMP_BEFORE_END_S = 0.002
STAMP_AFTER_END_S = 0.008


def p90(values) -> Optional[float]:
    values = np.asarray(values, dtype=float)
    return float(np.percentile(values, 90)) if values.size else None


def _weights_floor_s(view) -> float:
    """A run of the remote model reads all of its weights, so a run shorter
    than their bytes over the chip's bandwidth is not the remote model's
    (the hedge's programs share its names and take microseconds)."""
    return counts.step_weight_bytes(view.cfg.remote) / view.peak["hbm_bytes_per_s"]


def served_runs(view, function: str) -> List[float]:
    """Device durations of the remote model's runs of ``function``
    (``prefill_fn``, ``decode_fn``, ...) in the traced window."""
    if view.trace is None:
        return []
    floor = _weights_floor_s(view)
    return [d for durs in view.trace.runs(function).values() for d in durs if d >= floor]


def matched_runs(view, function: str, stamps, lags=None) -> List[Tuple[float, list]]:
    """The remote model's runs of ``function`` in the traced window, each
    with what the host stamped from it.

    ``stamps`` is a list of ``(perf_counter seconds, payload)``.  Returns
    ``(device seconds, payloads)`` of each run that a stamp belongs to;
    a run without a stamp, and a stamp without a run in the trace (its run
    lay outside the traced window), are left out, so the work counted and
    the time it is set against come from the same runs.  ``lags``, where
    given, collects each matched stamp's time after its run's end."""
    if view.trace is None:
        return []
    floor = _weights_floor_s(view)
    spans = [(a, b) for a, b in view.trace.run_spans(function) if b - a >= floor]
    if not spans:
        return []
    ends = np.asarray([b for _, b in spans])
    got: dict = {}
    for t, payload in stamps:
        k = int(np.argmin(np.abs(ends - t)))
        if -STAMP_BEFORE_END_S <= t - ends[k] <= STAMP_AFTER_END_S:
            got.setdefault(k, []).append(payload)
            if lags is not None:
                lags.append(t - ends[k])
    return [(spans[k][1] - spans[k][0], got[k]) for k in sorted(got)]


def prefill_runs(view, lags=None) -> List[Tuple[float, int]]:
    """``(device seconds, prompts)`` of each remote prefill run in the
    traced window: a prompt's first token is stamped as its run ends."""
    stamps = [(r.chunks_s[0], None) for r in view.requests if r.chunks_s]
    return [(s, len(p)) for s, p in matched_runs(view, "prefill_fn", stamps, lags)]


def decode_runs(view, lags=None) -> List[Tuple[float, List[int]]]:
    """``(device seconds, query positions)`` of each remote decode run in
    the traced window: token j >= 1 of a request is decoded at P + j - 1."""
    P = int(view.mix["prompt_tokens"])
    stamps = [(t, P + j - 1) for r in view.requests
              for j, t in enumerate(r.chunks_s) if j >= 1]
    return matched_runs(view, "decode_fn", stamps, lags)


def _overlap(view, leg) -> float:
    """Share of a leg's ``(start, end)`` that lies in the traced window."""
    a, b = leg
    if b is None or b <= a:
        return 0.0
    lo, hi = max(a, view.traced[0]), min(b, view.traced[1])
    return max(hi - lo, 0.0) / (b - a)


def request_flops(m, prompt: int, tokens: int) -> int:
    """A whole request: its prompt, then ``tokens - 1`` decoded tokens."""
    return counts.prefill_flops(m, prompt) + sum(
        counts.decode_flops(m, prompt + j - 1) for j in range(1, tokens)
    )


def window_flops(view) -> float:
    """Model operations of all the work served in the traced window: the
    remote tier's prefill and decode runs in the trace (the prompts and
    tokens stamped from them) and the hedge's legs, each spread evenly
    over its length."""
    P, n = int(view.mix["prompt_tokens"]), int(view.mix["output_tokens"])
    remote, hedge = view.cfg.remote, view.cfg.hedge
    total = float(sum(rows * counts.prefill_flops(remote, P) for _, rows in prefill_runs(view)))
    total += sum(counts.decode_flops(remote, p) for _, ps in decode_runs(view) for p in ps)
    whole_hedge = request_flops(hedge, P, n)
    return total + sum(whole_hedge * _overlap(view, r.legs["ondevice"])
                       for r in view.requests if "ondevice" in r.legs)

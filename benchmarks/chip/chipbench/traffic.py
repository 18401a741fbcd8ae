"""The one general generator of traffic: a mix's parameters in, requests out.

A traffic file (``traffic/<name>.json``) gives the arrival process and its
rate, the prompt and answer lengths, the network the clients sit behind,
the SLA and the tick cadence.  Every seed gets the same *set* of
inter-arrival gaps and network times, in another order, and its own
prompt tokens: the count of requests in the window and the work they
carry do not depend on the seed, so runs with different seeds differ by
the order of the same work and not by its amount.

The arrival and network models are copies of ``serving/loadgen.py``
``PoissonArrivals``/``BurstyArrivals`` and of ``core/network.py``'s
body-plus-tail traces, kept here so that the yardstick does not move with
the program.
"""
from __future__ import annotations

import dataclasses

import numpy as np

_MIN_NW_MS = 0.1


@dataclasses.dataclass(frozen=True)
class Requests:
    """One run's requests, in order of arrival."""

    arrival_s: np.ndarray  # (N,) seconds after the window opens, in [0, T)
    network_ms: np.ndarray  # (N,) round-trip network time of each client
    prompts: np.ndarray  # (N, prompt_tokens) int32
    output_tokens: int
    sla_ms: float

    def __len__(self) -> int:
        return len(self.arrival_s)


def _poisson_gaps(rng, n: int, p: dict) -> list:
    """Exponential gaps, as one run of base-rate segments."""
    return [rng.exponential(1.0, size=n)]


def _bursty_gaps(rng, n: int, p: dict) -> list:
    """The two-state MMPP of ``loadgen.BurstyArrivals``, cut into segments
    of one state each, so that a permutation keeps every burst whole."""
    flips = rng.random(n)
    raw = rng.exponential(1.0, size=n)
    segments, current, in_burst = [], [], False
    for i in range(n):
        was = in_burst
        if in_burst:
            in_burst = not flips[i] < p["p_exit"]
        else:
            in_burst = flips[i] < p["p_enter"]
        if in_burst != was and current:
            segments.append(np.asarray(current))
            current = []
        current.append(raw[i] / (p["burst_factor"] if in_burst else 1.0))
    segments.append(np.asarray(current))
    return segments


_ARRIVALS = {"poisson": _poisson_gaps, "bursty": _bursty_gaps}


def network_trace(p: dict) -> np.ndarray:
    """The body-plus-tail network trace of ``core/network.py``: a gamma
    body (capped) with a uniform outage tail, from the mix's fixed seed."""
    rng = np.random.default_rng(p["trace_seed"])
    n = p["trace_n"]
    shape = 1.0 / p["base_cv"] ** 2
    body = rng.gamma(shape, p["base_mean_ms"] / shape, size=n)
    if p.get("cap_ms") is not None:
        body = np.minimum(body, p["cap_ms"])
    tail = rng.uniform(p["tail_lo_ms"], p["tail_hi_ms"], size=n)
    is_tail = rng.random(n) < p["tail_frac"]
    return np.maximum(np.where(is_tail, tail, body), _MIN_NW_MS)


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["arrivals"]["rate_rps"] * seconds)))


def generate(mix: dict, seconds: float, seed: int, vocab: int) -> Requests:
    """The requests of one run of ``seconds`` under ``mix``.

    ``vocab`` bounds the prompt ids (ids both tiers' vocabularies hold).
    """
    arr = mix["arrivals"]
    n = count(mix, seconds)
    shape_rng = np.random.default_rng(arr["shape_seed"])
    segments = _ARRIVALS[arr["process"]](shape_rng, n, arr)
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(segments))
    gaps = np.concatenate([segments[i] for i in order])
    # The n gaps fill the window exactly: the first request is due when it
    # opens, the last one gap before it closes.
    gaps = gaps * (seconds / gaps.sum())
    arrival_s = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    trace = network_trace(mix["network"])
    network_ms = np.quantile(trace, (np.arange(n) + 0.5) / n)
    network_ms = network_ms[rng.permutation(n)]
    prompts = rng.integers(0, vocab, (n, mix["prompt_tokens"])).astype(np.int32)
    return Requests(
        arrival_s=arrival_s,
        network_ms=network_ms,
        prompts=prompts,
        output_tokens=int(mix["output_tokens"]),
        sla_ms=float(mix["sla_ms"]),
    )

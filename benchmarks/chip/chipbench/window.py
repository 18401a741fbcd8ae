"""The open-loop generator: requests submitted when due, on the wall clock.

The serving loop is driven through its normal entry points: ``submit``
when a request is due, ``tick(now_ms, wait=False)`` every ``tick_ms`` of
wall time with ``now_ms`` the wall time since the window opened, and
``poll()`` in between.  Every request is timed from when it was due, so a
stall of the loop delays the requests behind it and shows in their
latency.  Each call is wrapped in a ``TraceAnnotation`` (``bench.submit``,
``bench.tick``, ``bench.poll``, ``bench.wait``), so a device trace can say
what the host was doing in each idle gap.  Each annotation carries the
``perf_counter`` reading at its start (``perf_us``), which ties the
trace's clock to the one the futures' stamps are taken on.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional

import jax
import numpy as np

# After the window closes the run waits this long for late answers.
DRAIN_S = 60.0
_IDLE_POLL_S = 0.0005  # a poll shorter than this did no work
_IDLE_SLEEP_S = 0.001


@dataclasses.dataclass
class Record:
    """One request as its client saw it."""

    due_s: float  # perf_counter seconds
    network_ms: float
    future: object = None
    submitted_s: Optional[float] = None
    resolved_s: Optional[float] = None


@dataclasses.dataclass
class Window:
    records: List[Record]
    t0: float  # perf_counter seconds at which the window opened
    seconds: float
    closed_s: float  # when the run stopped waiting for answers
    tick_late_s: List[float]
    compile_events: int
    traced: Optional[tuple] = None  # (start, stop) perf_counter seconds
    # (begin, end) of each profiler start and stop call: the host stalls
    # in them.
    profiler_calls: List[tuple] = dataclasses.field(default_factory=list)

    def submit_late_ms(self) -> np.ndarray:
        return np.asarray(
            [1e3 * (r.submitted_s - r.due_s) for r in self.records
             if r.submitted_s is not None]
        )


class CompileCounter:
    """Counts JAX trace, lowering and compile events while ``active``."""

    def __init__(self):
        self.active = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if self.active and event.startswith("/jax/core/compile/"):
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self)


def drive(loop, requests, make_request: Callable, *, seconds: float,
          tick_ms: float, trace_span: Optional[tuple] = None,
          profiler=None) -> Window:
    """Run one measured window of ``seconds`` over ``loop``.

    ``make_request(i, arrival_ms)`` builds request ``i``'s
    ``QueuedRequest``.  ``trace_span=(offset_s, length_s)`` and
    ``profiler=(start, stop)`` trace that part of the window.
    """
    n = len(requests)
    tick_s = tick_ms / 1e3
    with CompileCounter() as compiles:
        t0 = time.perf_counter()
        records = [
            Record(t0 + float(requests.arrival_s[i]), float(requests.network_ms[i]))
            for i in range(n)
        ]
        end = t0 + seconds
        next_tick = t0 + tick_s
        nxt = 0
        pending = []  # submitted, not yet resolved
        tick_late = []
        trace_at = trace_stop = None
        traced = None
        calls = []
        if trace_span is not None:
            trace_at = t0 + trace_span[0]
            trace_stop = trace_at + trace_span[1]
        compiles.active = True
        while True:
            now = time.perf_counter()
            if trace_at is not None and now >= trace_at:
                profiler[0]()
                trace_at, traced = None, [time.perf_counter(), None]
                calls.append((now, traced[0]))
            if traced is not None and traced[1] is None and now >= trace_stop:
                traced[1] = time.perf_counter()
                profiler[1]()
                calls.append((traced[1], time.perf_counter()))
            while nxt < n and records[nxt].due_s <= now:
                rec = records[nxt]
                with _annotate("bench.submit"):
                    rec.future = loop.submit(
                        make_request(nxt, 1e3 * (rec.due_s - t0))
                    )
                rec.submitted_s = time.perf_counter()
                pending.append(rec)
                nxt += 1
            now = time.perf_counter()
            if now >= next_tick:
                tick_late.append(now - next_tick)
                with _annotate("bench.tick"):
                    loop.tick(now_ms=1e3 * (now - t0), wait=False)
                while next_tick <= now:
                    next_tick += tick_s
                _stamp(pending)
            p0 = time.perf_counter()
            with _annotate("bench.poll"):
                loop.poll()
            p1 = time.perf_counter()
            _stamp(pending)
            pending = [r for r in pending if r.resolved_s is None]
            if nxt == n and not pending:
                break
            if p1 >= end + DRAIN_S:
                break
            if p1 - p0 < _IDLE_POLL_S:
                due = records[nxt].due_s if nxt < n else next_tick
                wake = min(due, next_tick, p1 + _IDLE_SLEEP_S)
                if traced is None and trace_at is not None:
                    wake = min(wake, trace_at)
                with _annotate("bench.wait"):
                    time.sleep(max(wake - time.perf_counter(), 0.0))
        closed = time.perf_counter()
        compiles.active = False
    if traced is not None and traced[1] is None:
        traced[1] = time.perf_counter()
        profiler[1]()
        calls.append((traced[1], time.perf_counter()))
    return Window(
        records=records, t0=t0, seconds=seconds, closed_s=closed,
        tick_late_s=tick_late, compile_events=compiles.count,
        traced=None if traced is None else tuple(traced),
        profiler_calls=calls if traced is not None else [],
    )


def _annotate(name: str):
    return jax.profiler.TraceAnnotation(name, perf_us=int(time.perf_counter() * 1e6))


def _stamp(pending: List[Record]) -> None:
    now = None
    for r in pending:
        if r.resolved_s is None and r.future.done():
            if now is None:
                now = time.perf_counter()
            r.resolved_s = now

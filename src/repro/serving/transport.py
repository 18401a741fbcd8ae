"""Replica transport: the message boundary under the cluster's pool.

PR 5's :class:`repro.serving.cluster.ClusterBackend` was horizontal in
name only — every replica an in-process object sharing the loop's fate.
:class:`ProcessTransportBackend` puts a replica behind a *real* boundary:
its backend runs in a spawned worker process and every batch crosses a
pipe as serialized submit/completion messages
(:mod:`repro.serving.transport_worker`).  The worker can genuinely die —
and the parent observes it as :class:`ReplicaDied` on every in-flight
batch, reconciling the replica's inflight/EWMA accounting on the way out
(the routing signals must not leak rows a dead worker will never
complete).

Two modes, one failure surface:

* ``mode="process"`` — the real boundary: spawned worker, pickled
  messages, a pump thread demultiplexing completions, worker-death and
  per-batch timeout detection, :meth:`kill` / :meth:`restart` for fault
  injection and rejoin.
* ``mode="inline"`` — the sync/CI fallback: the factory's backend runs
  in-process (zero new concurrency), but the *fault surface is
  preserved*: :meth:`kill` makes every subsequent batch raise
  :class:`ReplicaDied`, and :meth:`inject_failures` queues deterministic
  :class:`RemoteExecutionError` faults — so breaker/requeue tests run
  byte-deterministically under ``dispatch="sync"``.

Error taxonomy (all :class:`TransportError`):

* :class:`ReplicaDied` — the worker is gone (death, kill, timeout):
  *fatal* to the circuit breaker, trips immediately.
* :class:`RemoteExecutionError` — the worker survived but the batch
  raised: counts toward the breaker's consecutive-failure threshold.

Either way the batch's rows leave ``inflight_rows`` (``_note_done`` with
``wall_ms=None``) — the accounting-reconcile contract the routers depend
on.
"""
from __future__ import annotations

import itertools
import multiprocessing as mp
import threading
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import numpy as np

from repro.serving.backend import BatchHandle, ExecutionBackend, Variant
from repro.serving.transport_worker import worker_main

__all__ = [
    "PROCESS_ON_TPU",
    "TransportError",
    "ReplicaDied",
    "RemoteExecutionError",
    "FailedBatchHandle",
    "ProcessTransportBackend",
]


PROCESS_ON_TPU = (
    "process transport is CPU-only: a TPU chip belongs to the one process "
    "that opened it, so a spawned replica worker cannot reach it. Serve "
    "replicas in this process instead (transport 'none' or 'inline'), "
    "one per device."
)


class TransportError(RuntimeError):
    """A batch was lost to the transport layer (never produced tokens)."""


class ReplicaDied(TransportError):
    """The replica's worker is gone — death, kill, or timeout.  Fatal to
    the circuit breaker (trips immediately)."""


class RemoteExecutionError(TransportError):
    """The worker survived but the batch raised remotely.  Counts toward
    the breaker's consecutive-failure threshold."""


class FailedBatchHandle(BatchHandle):
    """A handle for a batch the transport already knows is lost.

    ``poll`` is immediately True (there is nothing to wait for) and
    ``wait`` raises the stored :class:`TransportError` — the serving
    loop's collection path turns that into requeue/hedge-failover instead
    of tokens.
    """

    def __init__(self, name: str, n_rows: int, error: TransportError):
        super().__init__(name, n_rows)
        self.error = error

    def poll(self) -> bool:
        return True

    def wait(self, timeout=None):
        raise self.error


class _PendingBatch:
    """Parent-side slot for one submitted batch awaiting its completion
    message (process mode)."""

    def __init__(self):
        self.event = threading.Event()
        self.result: Optional[Tuple[np.ndarray, float]] = None
        self.error: Optional[TransportError] = None
        # Tracing extras (populated only when the submit asked for them):
        # the worker's relative timings and the parent-side receive stamp.
        self.span_info: Optional[dict] = None
        self.recv_wall_ms: Optional[float] = None


class ProcessTransportBackend(ExecutionBackend):
    """One replica's backend behind a process (or inline) transport.

    ``factory`` builds the actual execution backend — in the worker for
    ``mode="process"`` (it must be picklable: a top-level callable), in
    this process for ``mode="inline"``.  Registration is mirrored: the
    parent keeps the variant metadata (so placement/routing see
    ``variants``) and forwards each registration across the boundary.
    """

    def __init__(
        self,
        factory: Callable[[], ExecutionBackend],
        *,
        mode: str = "process",
        timeout_s: Optional[float] = 60.0,
        max_len: Optional[int] = None,
    ):
        if mode not in ("process", "inline"):
            raise ValueError(f"mode must be 'process' or 'inline', got {mode!r}")
        if mode == "process" and jax.default_backend() == "tpu":
            raise RuntimeError(PROCESS_ON_TPU)
        super().__init__()
        self.factory = factory
        self.mode = mode
        self.timeout_s = timeout_s
        self._dead: Optional[str] = None  # death reason, None while alive
        self._seq = itertools.count()
        self._inner: Optional[ExecutionBackend] = None
        self._fail_queue: list = []  # inline-mode injected faults
        self._conn = None
        self._proc: Optional[mp.process.BaseProcess] = None
        self._pending: Dict[int, _PendingBatch] = {}
        self._send_lock = threading.Lock()
        self._pump_thread: Optional[threading.Thread] = None
        if mode == "inline":
            self._inner = factory()
            self.max_len = (
                max_len if max_len is not None
                else getattr(self._inner, "max_len", None)
            )
        else:
            self.max_len = max_len
            self._spawn()

    # -- lifecycle ------------------------------------------------------------
    @property
    def alive(self) -> bool:
        return self._dead is None

    def _spawn(self) -> None:
        ctx = mp.get_context("spawn")
        self._conn, child_conn = ctx.Pipe(duplex=True)
        self._proc = ctx.Process(
            target=worker_main, args=(child_conn, self.factory), daemon=True
        )
        self._proc.start()
        child_conn.close()  # the parent keeps only its end
        self._dead = None
        self._pump_thread = threading.Thread(
            target=self._pump, name="transport-pump", daemon=True
        )
        self._pump_thread.start()

    def _pump(self) -> None:
        """Demultiplex completion messages to their pending slots; a
        broken pipe means the worker died — fail everything in flight."""
        conn = self._conn
        try:
            while True:
                msg = conn.recv()
                kind, seq = msg[0], msg[1]
                slot = self._pending.pop(seq, None)
                if slot is None:
                    continue  # a timed-out batch already gave up on it
                if kind == "result":
                    slot.result = (msg[2], msg[3])
                    if len(msg) > 4:  # traced submit: worker-side timings
                        slot.span_info = msg[4]
                        slot.recv_wall_ms = time.perf_counter() * 1e3
                else:
                    slot.error = RemoteExecutionError(
                        f"batch failed in worker: {msg[2]}"
                    )
                slot.event.set()
        except (EOFError, OSError):
            self._fail_all_pending("worker process died")

    def _fail_all_pending(self, reason: str) -> None:
        self._dead = reason
        while self._pending:
            _, slot = self._pending.popitem()
            slot.error = ReplicaDied(reason)
            slot.event.set()

    def kill(self, reason: str = "killed") -> None:
        """Hard-kill the replica (fault injection / operator action).

        Process mode terminates the worker; either mode fails every
        in-flight batch with :class:`ReplicaDied` and makes every future
        submit raise it too, until :meth:`restart`.
        """
        if self._proc is not None and self._proc.is_alive():
            self._proc.terminate()
        self._fail_all_pending(reason)

    def restart(self) -> None:
        """Bring a dead replica back (the rejoin path).

        Process mode respawns the worker and replays registration from
        the parent's variant mirror; inline mode just clears the death
        flag.  Load accounting is already reconciled (failures drained
        inflight), so the recovered replica re-enters routing at zero.
        """
        if self._proc is not None:
            self._proc.join(timeout=5.0)
        self._dead = None
        self._fail_queue = []
        if self.mode == "process":
            self._spawn()
            for v in self.variants.values():
                self._conn.send(("register", v))

    def close(self) -> None:
        """Shut the worker down cleanly (tests / bench teardown)."""
        if self.mode == "process" and self.alive and self._proc is not None:
            try:
                self._conn.send(("stop",))
            except (BrokenPipeError, OSError):
                pass
            self._proc.join(timeout=5.0)
            if self._proc.is_alive():  # pragma: no cover - stuck worker
                self._proc.terminate()
        self._dead = "closed"

    # -- fault injection (inline mode) ----------------------------------------
    def inject_failures(self, n: int, reason: str = "injected fault") -> None:
        """Queue ``n`` deterministic batch failures (inline mode only) —
        the sync/CI stand-in for a worker that errors without dying."""
        if self.mode != "inline":
            raise ValueError(
                "inject_failures is the inline-mode fault hook; kill() the "
                "process worker instead"
            )
        self._fail_queue.extend([reason] * n)

    # -- the execution protocol, across the boundary --------------------------
    def register(self, v: Variant) -> None:
        self.variants[v.name] = v
        if self.mode == "inline":
            self._inner.register(v)
        elif self.alive:
            self._conn.send(("register", v))

    def run_batch(self, name, batch, n_steps):
        if self._dead is not None:
            raise ReplicaDied(f"replica is down: {self._dead}")
        if self.mode == "inline":
            if self._fail_queue:
                if self._obs is not None:
                    self._obs.counter(
                        "transport_batches_total", outcome="error"
                    ).inc()
                raise RemoteExecutionError(self._fail_queue.pop(0))
            if self._obs is None:
                return self._inner.run_batch(name, batch, n_steps)
            return self._run_inline_traced(name, batch, n_steps)
        return self._roundtrip(name, np.asarray(batch), int(n_steps))

    def _run_inline_traced(self, name, batch, n_steps):
        """Inline execution with the same span shape as process mode:
        a ``transport.roundtrip`` wrapping a ``worker.execute`` (here
        the 'worker' is this process — the boundary is logical only)."""
        tracer = self._obs.tracer
        span = tracer.start(
            "transport.roundtrip",
            parent=tracer.ambient_id(),
            cat="transport",
            track=self._obs_track,
            variant=name,
            rows=int(np.asarray(batch).shape[0]),
            mode="inline",
        )
        exec_span = tracer.start(
            "worker.execute",
            parent=span,
            cat="transport",
            track=self._obs_track,
            variant=name,
        )
        try:
            out = self._inner.run_batch(name, batch, n_steps)
        except BaseException as e:
            span.args["error"] = repr(e)
            self._obs.counter(
                "transport_batches_total", outcome="error"
            ).inc()
            raise
        finally:
            tracer.end(exec_span)
            tracer.end(span)
        self._obs.counter("transport_batches_total", outcome="ok").inc()
        self._obs.histogram("transport_roundtrip_ms").record(
            span.duration_ms
        )
        return out

    def generate(self, name, tokens, n_steps):
        if self.mode == "inline":
            if self._dead is not None:
                raise ReplicaDied(f"replica is down: {self._dead}")
            return self._inner.generate(name, tokens, n_steps)
        return self.run_batch(name, tokens, n_steps)

    def _roundtrip(self, name, batch, n_steps) -> Tuple[np.ndarray, float]:
        if self._obs is None:
            return self._roundtrip_raw(name, batch, n_steps, traced=False)[0]
        # Traced path: one transport.roundtrip span around the pipe trip,
        # with a worker.execute child reconstructed from the worker's
        # *relative* timings (perf_counter epochs differ across processes,
        # so the child is anchored to end at the parent-side receive
        # stamp and extend backwards by the reported duration).
        tracer = self._obs.tracer
        span = tracer.start(
            "transport.roundtrip",
            parent=tracer.ambient_id(),
            cat="transport",
            track=self._obs_track,
            variant=name,
            rows=int(batch.shape[0]),
            mode="process",
        )
        try:
            result, slot = self._roundtrip_raw(
                name, batch, n_steps, traced=True
            )
        except TransportError as e:
            span.args["error"] = str(e)
            tracer.end(span)
            self._obs.counter(
                "transport_batches_total", outcome="error"
            ).inc()
            raise
        if slot.span_info is not None and slot.recv_wall_ms is not None:
            info = slot.span_info
            exec_span = tracer.start(
                "worker.execute",
                parent=span,
                cat="transport",
                track=self._obs_track,
                variant=name,
                worker_wall_ms=info.get("wall_ms"),
                t0_ms=slot.recv_wall_ms - float(info.get("handle_ms", 0.0)),
            )
            tracer.end(exec_span, slot.recv_wall_ms)
        tracer.end(span)
        self._obs.counter("transport_batches_total", outcome="ok").inc()
        self._obs.histogram("transport_roundtrip_ms").record(
            span.duration_ms
        )
        return result

    def _roundtrip_raw(
        self, name, batch, n_steps, *, traced: bool
    ) -> Tuple[Tuple[np.ndarray, float], _PendingBatch]:
        slot = _PendingBatch()
        with self._send_lock:
            if self._dead is not None:
                raise ReplicaDied(f"replica is down: {self._dead}")
            seq = next(self._seq)
            self._pending[seq] = slot
            # Backward-compatible protocol extension: the 6th element asks
            # the worker to report its relative timings alongside the
            # result (old 5-tuples keep the old 4-tuple reply).
            msg = (
                ("submit", seq, name, batch, n_steps, True)
                if traced
                else ("submit", seq, name, batch, n_steps)
            )
            try:
                self._conn.send(msg)
            except (BrokenPipeError, OSError):
                self._pending.pop(seq, None)
                self._fail_all_pending("worker process died")
                raise ReplicaDied("worker process died") from None
        if not slot.event.wait(self.timeout_s):
            # A wedged worker is indistinguishable from a dead one; the
            # timeout converts the ambiguity into a definite death — kill
            # so no later batch waits on it too.
            self._pending.pop(seq, None)
            self.kill(f"batch timeout after {self.timeout_s}s")
            raise ReplicaDied(f"batch timeout after {self.timeout_s}s")
        if slot.error is not None:
            raise slot.error
        return slot.result, slot

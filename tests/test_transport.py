"""Transport-layer tests: inline fault surface + real process workers.

Process-mode tests spawn genuine worker processes; the backend factory
(``transport_stubs``) imports only numpy, so the children stay jax-free
and the spawns are cheap enough for tier-1 CI.
"""
import threading
import time

import jax
import numpy as np
import pytest

from repro.serving.transport import (
    FailedBatchHandle,
    ProcessTransportBackend,
    RemoteExecutionError,
    ReplicaDied,
    TransportError,
)
from transport_stubs import (
    ExplodingWorkerBackend,
    HangingWorkerBackend,
    SlowWorkerBackend,
    StubVariant,
    StubWorkerBackend,
)


def expected_tokens(batch, n_steps):
    base = np.asarray(batch)[:, :1].astype(np.int32)
    return base + np.arange(n_steps, dtype=np.int32)[None, :]


# -- FailedBatchHandle ---------------------------------------------------------


def test_failed_handle_polls_true_and_wait_raises():
    err = ReplicaDied("gone")
    h = FailedBatchHandle("m", 4, err)
    assert h.poll()
    assert h.n_rows == 4
    with pytest.raises(ReplicaDied, match="gone"):
        h.wait()


# -- inline mode ---------------------------------------------------------------


def test_inline_roundtrip_delegates_to_inner_backend():
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    assert "m" in t.variants  # the parent-side mirror
    batch = np.array([[3, 0], [7, 0]])
    out, wall_ms = t.run_batch("m", batch, 4)
    np.testing.assert_array_equal(out, expected_tokens(batch, 4))
    assert wall_ms >= 0.0


def test_inline_injected_failures_then_recovery():
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    t.inject_failures(2, reason="synthetic")
    batch = np.array([[1, 0]])
    for _ in range(2):
        with pytest.raises(RemoteExecutionError, match="synthetic"):
            t.run_batch("m", batch, 2)
    # The worker "survived": the next batch succeeds.
    out, _ = t.run_batch("m", batch, 2)
    np.testing.assert_array_equal(out, expected_tokens(batch, 2))


def test_inline_kill_then_restart():
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    t.kill("chaos test")
    assert not t.alive
    with pytest.raises(ReplicaDied, match="chaos test"):
        t.run_batch("m", np.array([[1, 0]]), 2)
    t.restart()
    assert t.alive
    out, _ = t.run_batch("m", np.array([[1, 0]]), 2)
    np.testing.assert_array_equal(out, expected_tokens(np.array([[1, 0]]), 2))


def test_process_mode_refuses_on_a_tpu(monkeypatch):
    # A chip belongs to the one process that opened it: a spawned worker
    # could never reach it.  Inline replicas stay in this process.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="CPU-only"):
        ProcessTransportBackend(StubWorkerBackend, mode="process")
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    out, _ = t.run_batch("m", np.array([[3], [7]]), 2)
    np.testing.assert_array_equal(out, expected_tokens([[3], [7]], 2))


def test_inject_failures_rejected_in_process_mode():
    t = ProcessTransportBackend(StubWorkerBackend, timeout_s=10.0)
    try:
        with pytest.raises(ValueError, match="inline-mode fault hook"):
            t.inject_failures(1)
    finally:
        t.close()


# -- accounting reconcile (satellite: inflight must not leak on failure) -------


def test_sync_submit_failure_reconciles_inflight():
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    t.inject_failures(1)
    with pytest.raises(RemoteExecutionError):
        t.submit_batch("m", np.array([[1, 0], [2, 0]]), 2, sync=True)
    assert t.inflight_rows == 0  # the failed rows drained out
    assert t.dispatched_rows == 2
    # EWMA untouched by the failure; a later success still seeds it.
    assert t.ewma_wall_ms is None
    t.submit_batch("m", np.array([[1, 0]]), 2, sync=True).wait()
    assert t.inflight_rows == 0
    assert t.ewma_wall_ms is not None


def test_threaded_submit_failure_reconciles_inflight():
    t = ProcessTransportBackend(StubWorkerBackend, mode="inline")
    t.register(StubVariant("m"))
    t.inject_failures(1)
    h = t.submit_batch("m", np.array([[1, 0]]), 2, sync=False)
    with pytest.raises(RemoteExecutionError):
        h.wait(timeout=5.0)
    assert t.inflight_rows == 0


# -- process mode --------------------------------------------------------------


def test_process_roundtrip_crosses_the_boundary():
    t = ProcessTransportBackend(StubWorkerBackend, timeout_s=30.0)
    try:
        t.register(StubVariant("m"))
        batch = np.array([[5, 0], [9, 0], [2, 0]])
        out, wall_ms = t.run_batch("m", batch, 3)
        np.testing.assert_array_equal(out, expected_tokens(batch, 3))
        assert wall_ms >= 0.0
        # Several sequential batches demultiplex correctly.
        for k in range(3):
            b = np.array([[k, 0]])
            out, _ = t.run_batch("m", b, 2)
            np.testing.assert_array_equal(out, expected_tokens(b, 2))
    finally:
        t.close()


def test_process_remote_error_counts_but_worker_survives():
    t = ProcessTransportBackend(ExplodingWorkerBackend, timeout_s=30.0)
    try:
        t.register(StubVariant("boom"))
        t.register(StubVariant("ok"))
        with pytest.raises(RemoteExecutionError, match="synthetic execution"):
            t.run_batch("boom", np.array([[1, 0]]), 2)
        assert t.alive  # the worker outlived the batch failure
        out, _ = t.run_batch("ok", np.array([[4, 0]]), 2)
        np.testing.assert_array_equal(out, expected_tokens(np.array([[4, 0]]), 2))
    finally:
        t.close()


def test_process_kill_fails_inflight_and_restart_reregisters():
    t = ProcessTransportBackend(SlowWorkerBackend, timeout_s=30.0)
    try:
        t.register(StubVariant("m"))
        # Warm the worker so the in-flight batch below is mid-execution
        # (not stuck behind child start-up) when the kill lands.
        t.run_batch("m", np.array([[0, 0]]), 1)
        h = t.submit_batch("m", np.array([[1, 0], [2, 0]]), 2, sync=False)
        time.sleep(0.05)  # let the submit reach the worker
        t.kill("fault injection")
        with pytest.raises(ReplicaDied):
            h.wait(timeout=10.0)
        assert not t.alive
        assert t.inflight_rows == 0  # accounting reconciled on the way out
        with pytest.raises(ReplicaDied, match="replica is down"):
            t.run_batch("m", np.array([[1, 0]]), 2)

        t.restart()  # respawns and replays registration from the mirror
        assert t.alive
        out, _ = t.run_batch("m", np.array([[6, 0]]), 2)
        np.testing.assert_array_equal(out, expected_tokens(np.array([[6, 0]]), 2))
        assert t.inflight_rows == 0
    finally:
        t.close()


def test_process_worker_death_surfaces_as_replica_died():
    t = ProcessTransportBackend(SlowWorkerBackend, timeout_s=30.0)
    try:
        t.register(StubVariant("m"))
        t.run_batch("m", np.array([[0, 0]]), 1)  # worker is up and serving
        errors = []

        def submit():
            try:
                t.run_batch("m", np.array([[1, 0]]), 2)
            except TransportError as e:
                errors.append(e)

        th = threading.Thread(target=submit)
        th.start()
        time.sleep(0.05)
        t._proc.terminate()  # the worker dies out from under the batch
        th.join(timeout=10.0)
        assert not th.is_alive()
        assert len(errors) == 1 and isinstance(errors[0], ReplicaDied)
        assert not t.alive
    finally:
        t.close()


def test_process_batch_timeout_kills_the_worker():
    t = ProcessTransportBackend(HangingWorkerBackend, timeout_s=0.5)
    try:
        t.register(StubVariant("m"))
        with pytest.raises(ReplicaDied, match="timeout"):
            t.run_batch("m", np.array([[1, 0]]), 2)
        assert not t.alive  # a wedged worker is treated as dead
    finally:
        t.close()

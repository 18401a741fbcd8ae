"""Observability layer (PR 10 tentpole): tracer, metrics, exporters, and
the regression pin — with the handle detached (the default) the stack is
byte-identical to the pre-observability loop; attached, it records span
trees that conserve requests and metrics that match the loop's counters.
"""
import json
import math
import types

import numpy as np
import pytest

from repro.observability import (
    Observability,
    Tracer,
    chrome_trace,
    prometheus_text,
    quantile,
    request_conservation,
)
from repro.observability.metrics import (
    BUCKET_LO_MS,
    N_BUCKETS,
    Histogram,
    MetricsRegistry,
    bucket_index,
    bucket_lower_ms,
    bucket_upper_ms,
)
from repro.observability.quantile import percentiles
from repro.serving.admission import AdmissionConfig, AdmissionQueue
from repro.serving.controller import AdmissionController, ControllerConfig
from repro.serving.health import BreakerConfig
from repro.serving.lifecycle import QueuedRequest, RequestState
from repro.serving.loop import ServingLoop

from loop_stubs import (
    StubHedgeBackend,
    StubRemoteBackend,
    stub_fault_cluster,
    stub_scheduler,
)

GEN = 2


def _request(rid, arrival_ms=0.0, nw=10.0, tenant=None):
    return QueuedRequest(
        rid=rid, tokens=np.zeros(4, np.int32), n_steps=GEN,
        t_nw_est_ms=nw, t_nw_actual_ms=nw, arrival_ms=arrival_ms,
        tenant=tenant,
    )


def _stub_loop(obs=None, *, hedge=False, admission=None, **kw):
    backend = StubRemoteBackend(0.0)
    from repro.serving.backend import Variant

    for name, quality in (("stub-a", 40.0), ("stub-b", 80.0)):
        backend.register(Variant(name, None, None, quality))
    return ServingLoop(
        stub_scheduler(t_sla_ms=1_000.0),
        backend,
        StubHedgeBackend(0.0) if hedge else None,
        dispatch="sync",
        admission=admission,
        observability=obs,
        **kw,
    )


# ---------------------------------------------------------------------------
# quantile helper (the one shared percentile convention)
# ---------------------------------------------------------------------------
def test_quantile_matches_numpy_and_is_empty_safe():
    vals = [3.0, 1.0, 4.0, 1.0, 5.0, 9.0, 2.0, 6.0]
    for q in (0, 25, 50, 90, 99, 100):
        assert quantile(vals, q) == pytest.approx(np.percentile(vals, q))
    assert math.isnan(quantile([], 99))
    assert quantile([], 99, default=0.0) == 0.0
    assert percentiles(vals, [50, 99]) == pytest.approx(
        list(np.percentile(vals, [50, 99]))
    )
    assert percentiles([], [50, 99], default=-1.0) == [-1.0, -1.0]


# ---------------------------------------------------------------------------
# histogram: fixed grid, O(1) recording, merge, percentile
# ---------------------------------------------------------------------------
def test_bucket_layout_is_fixed_and_monotone():
    assert N_BUCKETS == 97  # ~O(100), shared by every histogram
    uppers = [bucket_upper_ms(i) for i in range(N_BUCKETS)]
    assert all(a < b for a, b in zip(uppers, uppers[1:]))
    assert math.isinf(uppers[-1])
    # Every value lands in the bucket whose (lower, upper] covers it.
    for v in (0.02, 0.5, 1.0, 3.7, 42.0, 999.0, 1e5):
        i = bucket_index(v)
        assert bucket_lower_ms(i) <= v <= bucket_upper_ms(i) * (1 + 1e-12)


def test_histogram_records_zero_and_underflow_into_bucket_zero():
    h = Histogram()
    h.record(0.0)  # loop_tick_wall_ms can legitimately be 0 on stub ticks
    h.record(-1.0)
    h.record(BUCKET_LO_MS / 2)
    assert h.counts[0] == 3 and h.count == 3


def test_histogram_percentile_within_bucket_resolution():
    h = Histogram()
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=3.0, sigma=1.0, size=5_000)  # ~20ms median
    for s in samples:
        h.record(float(s))
    for q in (50, 90, 99):
        exact = float(np.percentile(samples, q))
        approx = h.percentile(q)
        # Bucket resolution is one 1/12-decade step (~21% width).
        assert abs(approx - exact) / exact < 0.25
    assert h.mean == pytest.approx(float(np.mean(samples)))


def test_histogram_snapshots_merge_like_a_single_histogram():
    a, b, both = Histogram(), Histogram(), Histogram()
    rng = np.random.default_rng(1)
    for i, v in enumerate(rng.uniform(0.1, 500.0, 400)):
        (a if i % 2 else b).record(float(v))
        both.record(float(v))
    merged = a.snapshot().merge(b.snapshot())
    assert merged.counts == both.snapshot().counts
    assert merged.count == both.count
    assert merged.sum == pytest.approx(both.sum)
    assert merged.percentile(99) == pytest.approx(both.percentile(99))


def test_registry_keys_by_name_and_labels():
    reg = MetricsRegistry()
    reg.counter("x", tenant="ui").inc()
    reg.counter("x", tenant="batch").inc(3)
    reg.counter("x", tenant="ui").inc()  # same handle, not a new metric
    assert reg.get_value("counter", "x", tenant="ui") == 2.0
    assert reg.get_value("counter", "x", tenant="batch") == 3.0
    assert reg.get_value("counter", "x", tenant="nope") is None
    reg.gauge("g").set(7)
    reg.histogram("h").record(5.0)
    snap = reg.snapshot()
    assert {c["name"] for c in snap["counters"]} == {"x"}
    assert len(snap["counters"]) == 2  # one row per label set
    assert snap["histograms"][0]["count"] == 1
    assert len(snap["histograms"][0]["counts"]) == N_BUCKETS


# ---------------------------------------------------------------------------
# tracer: parentage, instants, ambient binding
# ---------------------------------------------------------------------------
def test_tracer_parent_links_and_instants():
    tr = Tracer()
    root = tr.start("request", cat="request", rid=1)
    child = tr.start("queued", parent=root)
    mark = tr.instant("resolve", parent=root, t_ms=123.0)
    tr.end(child)
    tr.end(root)
    assert child.parent_id == root.span_id
    assert mark.is_instant and mark.start_ms == 123.0
    assert not root.is_instant and root.end_ms >= root.start_ms
    assert [s.span_id for s in tr.children_of(root)] == [
        child.span_id, mark.span_id
    ]
    # End is idempotent: the first close wins.
    end0 = child.end_ms
    tr.end(child, t1_ms=end0 + 999.0)
    assert child.end_ms == end0
    # Ids are assigned in creation order (deterministic trees).
    assert [s.span_id for s in tr.spans] == [0, 1, 2]


def test_tracer_ambient_binding_is_per_thread_and_nested():
    tr = Tracer()
    outer = tr.start("tick")
    assert tr.ambient_id() is None
    with tr.bind(outer):
        assert tr.ambient_id() == outer.span_id
        inner = tr.start("batch:stub", parent=tr.ambient_id())
        with tr.bind(inner):
            assert tr.ambient_id() == inner.span_id
        assert tr.ambient_id() == outer.span_id

        import threading

        seen = []
        t = threading.Thread(target=lambda: seen.append(tr.ambient_id()))
        t.start()
        t.join()
        assert seen == [None]  # ambient state never leaks across threads
    assert tr.ambient_id() is None


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_chrome_trace_shape_tracks_and_units():
    tr = Tracer()
    a = tr.start("request", track="tenant:ui", t0_ms=10.0)
    tr.instant("resolve", parent=a, track="tenant:ui", t_ms=14.0)
    tr.end(a, t1_ms=14.0)
    tr.start("tick", track="loop", t0_ms=10.0)  # left open on purpose
    doc = chrome_trace(tr)
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    assert meta[0]["name"] == "process_name"
    tracks = {
        e["args"]["name"]: e["tid"]
        for e in meta
        if e["name"] == "thread_name"
    }
    assert set(tracks) == {"tenant:ui", "loop"}
    request = next(e for e in events if e["name"] == "request")
    assert request["ph"] == "X"
    assert request["ts"] == pytest.approx(10.0 * 1e3)  # µs
    assert request["dur"] == pytest.approx(4.0 * 1e3)
    assert request["args"]["span_id"] == a.span_id
    instant = next(e for e in events if e["name"] == "resolve")
    assert instant["ph"] == "i" and instant["s"] == "t"
    open_tick = next(e for e in events if e["name"] == "tick")
    assert open_tick["ph"] == "X" and open_tick["dur"] == 0.0
    json.dumps(doc)  # must be serializable as-is


def test_prometheus_text_counters_and_cumulative_buckets():
    reg = MetricsRegistry()
    reg.counter("loop_shed_total").inc(5)
    reg.gauge("loop_inflight_ticks", lane="x").set(2)
    h = reg.histogram("wait_ms")
    for v in (0.5, 0.5, 50.0):
        h.record(v)
    text = prometheus_text(reg)
    assert "# TYPE loop_shed_total counter" in text
    assert "loop_shed_total 5.0" in text
    assert 'loop_inflight_ticks{lane="x"} 2.0' in text
    assert "# TYPE wait_ms histogram" in text
    assert 'wait_ms_bucket{le="+Inf"} 3' in text
    assert "wait_ms_count 3" in text
    assert "wait_ms_sum 51.0" in text
    # Bucket series are cumulative: the 50ms bucket's line reads 3.
    lines = [ln for ln in text.splitlines() if ln.startswith("wait_ms_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in lines]
    assert counts == sorted(counts) and counts[-1] == 3


# ---------------------------------------------------------------------------
# loop integration: regression pin, span trees, conservation
# ---------------------------------------------------------------------------
def test_detached_default_keeps_futures_untraced():
    loop = _stub_loop(obs=None)
    f = loop.submit(_request(0))
    loop.tick(now_ms=0.0)
    assert loop.observability is None
    assert f.span is None and f._tracer is None
    assert f.state is RequestState.RESOLVED


def test_attached_run_is_a_decision_identical_twin():
    """The instrumentation observes, never steers: same completions, same
    model choices, same waits as the detached run on one seeded stream."""
    results = []
    for obs in (None, Observability()):
        loop = _stub_loop(obs)
        futures = [loop.submit(_request(i, arrival_ms=i * 5.0)) for i in range(12)]
        res = loop.tick(now_ms=100.0)
        results.append(
            [
                (c.rid, c.model_index, c.queue_wait_ms)
                for c in res.completions
            ]
        )
        assert all(f.state is RequestState.RESOLVED for f in futures)
    assert results[0] == results[1]


def test_request_span_tree_and_conservation_on_resolve():
    obs = Observability()
    loop = _stub_loop(obs, hedge=True)
    n = 6
    futures = [loop.submit(_request(i, tenant="ui")) for i in range(n)]
    loop.tick(now_ms=50.0)

    roots = obs.tracer.find("request")
    assert len(roots) == n
    assert all(r.track == "tenant:ui" for r in roots)
    for f, root in zip(futures, roots):
        names = [s.name for s in obs.tracer.children_of(root)]
        assert names.count("queued") == 1
        assert "scheduled" in names and "resolve" in names
        assert "remote" in names  # the tier leg replayed from wall stamps
        queued = next(
            s for s in obs.tracer.children_of(root) if s.name == "queued"
        )
        assert queued.end_ms is not None  # closed when the tick claimed it
        assert f.span is root

    audit = request_conservation(obs.tracer)
    assert audit["submitted"] == n and audit["resolved"] == n
    assert audit["open"] == 0 and audit["extra_terminals"] == 0

    # Tick + dispatch-group spans on the loop track.
    (tick_span,) = obs.tracer.find("tick")
    assert tick_span.track == "loop" and tick_span.end_ms is not None
    batch_spans = [
        s for s in obs.tracer.spans if s.name.startswith("batch:")
    ]
    assert batch_spans and all(
        s.parent_id == tick_span.span_id for s in batch_spans
    )
    assert any(s.name == "batch:hedge" for s in batch_spans)

    # Loop metric families line up with the trace.
    m = obs.metrics
    assert m.get_value("counter", "loop_submitted_total") == n
    assert m.get_value("counter", "loop_completions_total") == n
    assert m.get_value("histogram", "loop_tick_wall_ms") == 1
    assert m.get_value("counter", "loop_hedged_total") == n


def test_shed_requests_terminate_with_shed_and_close_queued_span():
    obs = Observability()
    loop = _stub_loop(
        obs,
        admission=AdmissionConfig(policy="shed", max_pending=2, max_chunk=2),
    )
    futures = [loop.submit(_request(i)) for i in range(6)]
    n_rejected = sum(1 for f in futures if f.state is RequestState.REJECTED)
    assert n_rejected == 4  # capacity 2: the rest shed at offer
    loop.tick(now_ms=0.0)
    audit = request_conservation(obs.tracer)
    assert audit["submitted"] == 6
    assert audit["rejected"] == n_rejected
    assert audit["resolved"] == 2
    assert audit["open"] == 0 and audit["extra_terminals"] == 0
    for s in obs.tracer.find("queued"):
        assert s.end_ms is not None
    assert obs.metrics.get_value(
        "counter", "admission_offers_total", disposition="rejected"
    ) == 4


def test_cancel_terminates_span_tree():
    obs = Observability()
    loop = _stub_loop(obs)
    f = loop.submit(_request(0))
    assert f.cancel()
    loop.tick(now_ms=0.0)
    audit = request_conservation(obs.tracer)
    assert audit["cancelled"] == 1 and audit["open"] == 0


def test_lost_batch_reopens_queued_span_and_conserves():
    """A replica failure requeues its rows: the request span gets a
    ``requeue`` instant plus a *second* queued span, and still ends in
    exactly one terminal once the survivor serves it."""
    obs = Observability()
    cluster = stub_fault_cluster(
        2, router="least_inflight",
        breaker=BreakerConfig(failure_threshold=1, cooldown_ms=1e6),
    )
    cluster.replicas[0].backend.inject_failures(50)
    loop = ServingLoop(
        stub_scheduler(t_sla_ms=1_000.0), cluster, dispatch="sync",
        observability=obs,
    )
    futures = [loop.submit(_request(i)) for i in range(8)]
    r1 = loop.tick(now_ms=0.0)
    assert r1.stats.n_lost > 0 and r1.stats.n_requeued == r1.stats.n_lost
    r2 = loop.tick(now_ms=100.0)
    assert r2.stats.n_lost == 0
    assert all(f.state is RequestState.RESOLVED for f in futures)

    requeued = [f for f in futures if f.requeues]
    assert len(requeued) == r1.stats.n_requeued
    for f in requeued:
        children = obs.tracer.children_of(f.span)
        names = [s.name for s in children]
        assert names.count("requeue") == 1
        assert names.count("queued") == 2  # original + reopened
        assert all(
            s.end_ms is not None for s in children if s.name == "queued"
        )

    audit = request_conservation(obs.tracer)
    assert audit["submitted"] == 8 and audit["resolved"] == 8
    assert audit["open"] == 0 and audit["extra_terminals"] == 0

    m = obs.metrics
    assert m.get_value("counter", "loop_lost_rows_total") == r1.stats.n_lost
    assert (
        m.get_value("counter", "loop_requeued_total") == r1.stats.n_requeued
    )
    assert m.get_value("counter", "loop_batches_lost_total") >= 1
    # The breaker trip left its mark on the control plane.
    assert obs.tracer.find("breaker.trip")
    trips = sum(
        obj.value
        for kind, name, labels, obj in m.items()
        if kind == "counter" and name == "breaker_trips_total"
    )
    assert trips >= 1


def test_transport_spans_nest_under_the_dispatch_group():
    obs = Observability()
    cluster = stub_fault_cluster(1)
    loop = ServingLoop(
        stub_scheduler(t_sla_ms=1_000.0), cluster, dispatch="sync",
        observability=obs,
    )
    loop.submit(_request(0))
    loop.tick(now_ms=0.0)
    roundtrips = obs.tracer.find("transport.roundtrip")
    assert roundtrips
    batch_ids = {
        s.span_id for s in obs.tracer.spans if s.name.startswith("batch:")
    }
    assert all(s.parent_id in batch_ids for s in roundtrips)
    for rt in roundtrips:
        execs = [
            s for s in obs.tracer.children_of(rt) if s.name == "worker.execute"
        ]
        assert len(execs) == 1
        ex = execs[0]
        # The worker leg sits inside the roundtrip envelope.
        assert rt.start_ms <= ex.start_ms and ex.end_ms <= rt.end_ms + 1e-6


# ---------------------------------------------------------------------------
# controller retunes as spans + metrics
# ---------------------------------------------------------------------------
def test_controller_retune_emits_instant_and_counters():
    obs = Observability()
    ctl = AdmissionController(
        ControllerConfig(target_wait_frac=0.1, hysteresis=1)
    )
    ctl.observability = obs
    queue = AdmissionQueue(
        AdmissionConfig(policy="shed", max_pending=16, max_chunk=16)
    )
    sched = types.SimpleNamespace(
        cfg=types.SimpleNamespace(t_sla_ms=100.0),
        mu=np.array([5.0]),
        join_ttft_mu=0.0,
    )
    comp = types.SimpleNamespace(queue_wait_ms=90.0)  # way over target
    result = types.SimpleNamespace(
        completions=[comp], stats=types.SimpleNamespace(n_shed=1)
    )
    ctl.observe(result, scheduler=sched, now_ms=123.0)
    assert ctl.apply(queue)
    retunes = obs.tracer.find("controller.retune")
    assert len(retunes) == 1 and retunes[0].is_instant
    assert retunes[0].args["direction"] == "tighten"
    assert retunes[0].args["max_pending"] == queue.cfg.max_pending
    m = obs.metrics
    assert m.get_value(
        "counter", "controller_retunes_total", direction="tighten"
    ) == 1
    assert m.get_value("gauge", "controller_max_pending") == (
        queue.cfg.max_pending
    )
    assert m.get_value("histogram", "controller_wait_ewma_ms") == 1
    assert len(ctl.log) == 1  # the serve --controller summary's source


# ---------------------------------------------------------------------------
# satellite: InferenceFuture.stream() chunk stamps + TickStats fields
# ---------------------------------------------------------------------------
def test_stream_chunks_carry_wall_stamps_and_token_instants():
    obs = Observability()
    loop = _stub_loop(obs)
    f = loop.submit(_request(0))
    # Backend-side pushes while EXECUTING: indexed in decode order with
    # the emission wall stamp (what TTFT accounting reads).
    f._push_chunk(7, 100.0)
    f._push_chunk(9, 105.0)
    assert [c.index for c in f.chunks] == [0, 1]
    assert [c.token for c in f.chunks] == [7, 9]
    assert [c.wall_ms for c in f.chunks] == [100.0, 105.0]
    # Per-token stamps ride the chunks only: no per-token span.
    assert obs.tracer.find("stream.token") == []
    loop.tick(now_ms=0.0)
    assert obs.tracer.find("stream.token") == []
    # The consumer sees the pushed chunks first, in order.
    streamed = list(f.stream())
    assert [c.token for c in streamed[:2]] == [7, 9]


def test_stream_degrades_to_burst_on_tokenless_tier():
    loop = _stub_loop()
    f = loop.submit(_request(0))
    loop.tick(now_ms=0.0)
    assert f.done() and not f.chunks  # stub tier has no token channel
    chunks = list(f.stream())
    comp = f.result(timeout=0)
    assert [c.token for c in chunks] == [
        int(t) for t in np.asarray(comp.tokens).ravel()
    ]
    assert [c.index for c in chunks] == list(range(len(chunks)))
    # Burst chunks share one consumption-time stamp.
    assert len({c.wall_ms for c in chunks}) == 1


def test_tickstats_defaults_and_loss_accounting():
    from repro.serving.loop import TickStats

    stats = TickStats(
        n_requests=0, n_hedged=0, remote_wall_ms=0.0, hedge_wall_ms=None,
        span_wall_ms=0.0, dispatch_spread_wall_ms=0.0,
        hedge_dispatched_before_remote_done=False,
    )
    assert stats.n_lost == 0 and stats.n_requeued == 0

    cluster = stub_fault_cluster(
        1, breaker=BreakerConfig(failure_threshold=1, cooldown_ms=1e6)
    )
    cluster.replicas[0].backend.inject_failures(10)
    hedge = StubHedgeBackend(0.0)
    loop = ServingLoop(stub_scheduler(t_sla_ms=1_000.0), cluster, hedge,
                       dispatch="sync")
    loop.submit(_request(0))
    loop.submit(_request(1))
    res = loop.tick(now_ms=0.0)
    # With a measured hedge duplicate, lost rows fail over instead of
    # requeueing: n_lost counts them, n_requeued stays 0.
    assert res.stats.n_lost == 2 and res.stats.n_requeued == 0
    assert len(res.completions) == 2
    assert all(c.race_resolution == "remote_failed" for c in res.completions)

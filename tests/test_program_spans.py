"""Execution spans of the serving stack at toy widths, on the CPU.

A stepped loop over a real continuous-batching tier and a real on-device
hedge records, per tick, ``admission.take``, ``policy.decide``, the
continuous tier's ``continuous.submit``/``prefill``/``graft``, the hedge's
``hedge.run`` and ``loop.collect``, and per stepping poll a
``decode.step`` split into ``decode.prepare``/``run``/``emit``.  With no
handle the same run decides the same and records nothing.  The hedge's
programs carry their own names, so a device trace tells them apart.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.configs.mdinference_zoo import ServingGeometry
from repro.models import transformer as T
from repro.observability import Observability
from repro.serving.backend import OnDeviceBackend, Variant
from repro.serving.engine import QueuedRequest, ServingEngine
from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

PROMPT, GEN = 8, 4
GEO = ServingGeometry(
    max_len=32, prompt_width=PROMPT, bs_ladder=(1, 2, 4), n_slots=8,
    page_size=8, max_steps=8,
)
N_REQ = 3  # ladder chunks 2 + 1


@pytest.fixture(scope="module")
def served():
    cfg = reduced(
        "gemma-2b", d_model=64, n_layers=2, n_heads=2, n_kv_heads=1,
        head_dim=32,
    )
    variant = Variant("m", cfg, T.init_params(cfg, jax.random.key(0)), 80.0)
    hedge = OnDeviceBackend.from_zoo(max_len=GEO.max_len)
    engine = ServingEngine(hedge_backend=hedge, continuous=True, geometry=GEO)
    engine.register(variant)
    registry = engine.measure_profiles(prompt_len=PROMPT, gen_tokens=GEN, trials=1)
    ondevice = hedge.measure_profile(prompt_len=PROMPT, gen_tokens=GEN, trials=1)
    hedge.run_batch(hedge.hedge_name, np.zeros((4, PROMPT), np.int32), GEN)
    engine.backend.warmup()
    runs = {}
    # Untraced first: attaching a handle wires the backends for good.
    for key, obs in (("plain", None), ("traced", Observability())):
        sched = MDInferenceScheduler(
            registry, ondevice, SchedulerConfig(t_sla_ms=60_000.0, seed=0)
        )
        loop = engine.make_loop(sched, observability=obs)
        runs[key] = _drive(engine, loop, obs)
    return engine, runs


def _drive(engine, loop, obs):
    """One tick of N_REQ requests, polled to completion, then one idle
    poll; records what each poll stepped."""
    toks = np.random.default_rng(21).integers(0, 64, (N_REQ, PROMPT))
    futures = [
        loop.submit(QueuedRequest(rid=i, tokens=toks[i], n_steps=GEN,
                                  t_nw_est_ms=50.0, t_nw_actual_ms=50.0))
        for i in range(N_REQ)
    ]
    assert loop.tick(now_ms=100.0, wait=False) is None
    eng = engine.backend._engines["m"]
    stepped, results = [], []
    while not results:
        stepped.append([(s, eng.slot_rt[s].pos) for s in sorted(eng.slot_rt)])
        results = loop.poll()
    n_spans = len(obs.tracer.spans) if obs is not None else 0
    assert not eng.slot_rt and loop.poll() == []
    idle_spans = (len(obs.tracer.spans) if obs is not None else 0) - n_spans
    return {"futures": futures, "completions": results[0].completions,
            "stepped": [s for s in stepped if s], "idle_spans": idle_spans,
            "tracer": None if obs is None else obs.tracer}


def _one(tracer, name):
    (span,) = tracer.find(name)
    return span


def test_tick_spans_parents_and_args(served):
    _, runs = served
    tr = runs["traced"]["tracer"]
    tick = _one(tr, "tick")
    take = _one(tr, "admission.take")
    assert take.parent_id is None and take.track == "loop"
    assert take.args == {"n_taken": N_REQ, "n_shed": 0}
    assert take.end_ms <= tick.start_ms  # the tick opens after the take
    decide = _one(tr, "policy.decide")
    assert decide.parent_id == tick.span_id and decide.args == {"rows": N_REQ}
    assert tick.start_ms <= decide.start_ms <= decide.end_ms
    collect = _one(tr, "loop.collect")
    assert collect.parent_id is None and collect.track == "loop"
    assert collect.args == {"ticks": 1}
    assert collect.end_ms >= tick.end_ms  # the collection closed the tick


def test_continuous_submit_prefill_and_graft(served):
    _, runs = served
    tr = runs["traced"]["tracer"]
    group = _one(tr, "batch:m")
    submit = _one(tr, "continuous.submit")
    assert submit.parent_id == group.span_id and submit.track == "remote"
    assert submit.args == {"variant": "m", "rows": N_REQ}
    prefills, grafts = tr.find("continuous.prefill"), tr.find("continuous.graft")
    assert [s.args for s in prefills] == [{"rows": 2, "padded": 2},
                                          {"rows": 1, "padded": 1}]
    assert [s.args for s in grafts] == [{"rows": 2}, {"rows": 1}]
    for s in prefills + grafts:
        assert s.parent_id == submit.span_id
        assert submit.start_ms <= s.start_ms <= s.end_ms <= submit.end_ms
    ordered = sorted(prefills + grafts, key=lambda s: s.start_ms)
    assert [s.name.split(".")[1] for s in ordered] == ["prefill", "graft"] * 2
    assert all(a.end_ms <= b.start_ms for a, b in zip(ordered, ordered[1:]))


def test_hedge_run_under_the_hedge_batch(served):
    _, runs = served
    tr = runs["traced"]["tracer"]
    batch = _one(tr, "batch:hedge")
    run = _one(tr, "hedge.run")
    assert run.parent_id == batch.span_id and run.track == "ondevice"
    assert run.args == {"rows": 4, "steps": GEN}  # N_REQ rows padded to 4
    assert batch.start_ms <= run.start_ms <= run.end_ms <= batch.end_ms


def test_decode_step_names_what_it_stepped(served):
    _, runs = served
    tr = runs["traced"]["tracer"]
    steps = tr.find("decode.step")
    stepped = runs["traced"]["stepped"]
    assert len(steps) == len(stepped) == GEN - 1  # the first token is prefill's
    for span, slots in zip(steps, stepped):
        assert span.parent_id is None and span.track == "remote"
        assert span.args == {"variant": "m", "active": len(slots),
                             "positions": [p for _, p in slots]}
        phases = sorted(tr.children_of(span), key=lambda s: s.start_ms)
        assert [s.name for s in phases] == ["decode.prepare", "decode.run",
                                            "decode.emit"]
        assert span.start_ms <= phases[0].start_ms
        assert phases[-1].end_ms <= span.end_ms
        assert all(a.end_ms <= b.start_ms for a, b in zip(phases, phases[1:]))
    assert stepped[0] == [(s, PROMPT) for s, _ in stepped[0]]


def test_idle_poll_records_nothing(served):
    _, runs = served
    assert runs["traced"]["idle_spans"] == 0


def test_detached_run_is_decision_identical_and_records_nothing(served):
    engine, runs = served
    plain, traced = runs["plain"], runs["traced"]

    def decided(run):
        return [(c.rid, c.model_index, c.hedged, c.used_remote,
                 c.race_resolution, tuple(np.asarray(c.tokens)))
                for c in sorted(run["completions"], key=lambda c: c.rid)]

    assert decided(plain) == decided(traced)
    assert plain["stepped"] == traced["stepped"]
    assert all(f.span is None and f._tracer is None for f in plain["futures"])
    assert plain["tracer"] is None


def test_hedge_programs_are_named_apart(served):
    engine, _ = served
    hedge = engine.hedge_backend
    v = hedge.variants[hedge.hedge_name]
    tokens = jnp.zeros((1, PROMPT), jnp.int32)
    cache, logits = hedge._prefill[v.name](v.params, tokens)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((1,), PROMPT, jnp.int32)
    lowered = hedge._decode[v.name].lower(v.params, cache, tok, pos)
    assert lowered.as_text().startswith("module @jit_hedge_decode_fn")
    assert hedge._prefill[v.name].lower(v.params, tokens).as_text().startswith(
        "module @jit_hedge_prefill_fn")

    eng = engine.backend._engines["m"]
    g = GEO
    tables = jnp.zeros((g.n_slots, g.pages_per_slot), jnp.int32)
    slots = jnp.zeros((g.n_slots,), jnp.int32)
    text = eng.decode_fn.lower(eng.variant.params, eng.pool, tables, slots,
                               slots).as_text()
    assert text.startswith("module @jit_decode_fn")
    chunk = jnp.zeros((1, g.prompt_width), jnp.int32)
    lens = jnp.full((1,), g.prompt_width, jnp.int32)
    assert eng.prefill_fn.lower(eng.variant.params, chunk, lens).as_text().startswith(
        "module @jit_prefill_fn")
    pcache, _ = eng.prefill_fn(eng.variant.params, chunk, lens)
    trash = jnp.zeros((1, g.pages_per_slot), jnp.int32)
    assert eng.graft_fn.lower(eng.pool, pcache, trash).as_text().startswith(
        "module @jit_graft_fn")

"""Cross-tick continuous batching: fixed-shape entries + block-paged slots.

The PR's correctness contract, bottom-up:

* The block-paged slot cache conserves pages and slots — every graft is
  matched by exactly one release, ``freed == resolved + hedge_win + cancel``.
* ``ContinuousBatchingBackend.generate`` is token-exact with ``JitBackend``
  at every ladder batch size *and* every padded partial size (masked ladder
  rows and trash-page writes never leak into real rows).
* A request joining the persistent decode batch mid-flight produces the
  same tokens as whole-batch execution, with TTFT stamped at graft.
* After ``warmup`` the jit caches never grow: zero post-warmup recompiles,
  counter-asserted across all traffic shapes.
* The stepped serving loop surfaces the tier's accounting: per-tick
  ``n_joined``/``n_recycled``/``compile_count`` in ``TickStats``, per-row
  ``ttft_ms`` on completions, and the scheduler's mid-flight-join EWMA.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced
from repro.configs.mdinference_zoo import SERVING_GEOMETRY, ServingGeometry
from repro.models import transformer as T
from repro.serving.backend import (
    ContinuousBatchingBackend,
    JitBackend,
    OnDeviceBackend,
    Variant,
    continuous_step_programs,
)
from repro.serving.block_cache import BlockPagedSlotCache, NoFreeSlot
from repro.serving.engine import QueuedRequest, ServingEngine

PROMPT, GEN = 8, 4
GEO = ServingGeometry(
    max_len=32, prompt_width=PROMPT, bs_ladder=(1, 2, 4), n_slots=8,
    page_size=8, max_steps=8,
)


def _variant(name="m", width=64, quality=80.0, seed=0):
    cfg = reduced(
        "gemma-2b", d_model=width, n_layers=2,
        n_heads=2, n_kv_heads=1, head_dim=width // 2,
    )
    return Variant(name, cfg, T.init_params(cfg, jax.random.key(seed)), quality)


@pytest.fixture(scope="module")
def variant():
    return _variant()


@pytest.fixture(scope="module")
def backend(variant):
    be = ContinuousBatchingBackend(GEO)
    be.register(variant)
    be.warmup()
    be.compiles_after_warmup = be.compile_count
    return be


@pytest.fixture(scope="module")
def jit_backend(variant):
    jb = JitBackend(max_len=GEO.max_len)
    jb.register(variant)
    return jb


def _prompts(n, seed=3):
    return np.random.default_rng(seed).integers(
        0, 64, (n, PROMPT)
    ).astype(np.int32)


# ---------------------------------------------------------------------------
# Block-paged slot cache.
# ---------------------------------------------------------------------------
def test_block_cache_lifecycle_and_conservation():
    cache = BlockPagedSlotCache(
        n_slots=2, n_pages=5, page_size=4, pages_per_slot=2
    )
    a = cache.begin_prefill(prompt_len=4, n_steps=4)
    b = cache.begin_prefill(prompt_len=4, n_steps=4)
    with pytest.raises(NoFreeSlot):
        cache.begin_prefill(prompt_len=4, n_steps=4)
    cache.commit_graft(a.index)
    cache.commit_graft(b.index)
    # Trash-padded tables: every entry is a real page id or the trash page.
    table = cache.page_table(a.index)
    assert table.dtype == np.int32 and table.shape == (2,)
    assert (table > 0).sum() == cache.pages_needed(4, 4)
    cache.release(a.index, "resolved")
    cache.release(b.index, "hedge_win")
    c = cache.begin_prefill(prompt_len=4, n_steps=4)  # slot recycles
    cache.commit_graft(c.index)
    cache.release(c.index, "cancel")
    stats = cache.stats()
    assert stats["grafted"] == 3 and stats["freed"] == 3
    assert stats["freed_resolved"] == 1
    assert stats["freed_hedge_win"] == 1
    assert stats["freed_cancel"] == 1
    cache.check_conservation()
    assert len(cache.free_slots) == 2


def test_block_cache_never_hands_out_trash_page():
    cache = BlockPagedSlotCache(
        n_slots=4, n_pages=9, page_size=4, pages_per_slot=2
    )
    seen = set()
    for _ in range(4):
        s = cache.begin_prefill(prompt_len=4, n_steps=4)
        pages = set(int(p) for p in cache.page_table(s.index) if p != 0)
        assert 0 not in pages
        assert not (pages & seen)  # disjoint reservations
        seen |= pages


# ---------------------------------------------------------------------------
# Generate equivalence: every ladder size + padded partials.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("B", [1, 2, 3, 4, 5, 6])
def test_generate_matches_jit_backend(backend, jit_backend, B):
    """Ladder sizes (1, 2, 4) and partial chunks (3 -> 2+1, 5 -> 4+1,
    6 -> 4+2) — padded rows and trash writes never touch real outputs."""
    toks = _prompts(B, seed=B)
    out_c, _ = backend.generate("m", toks, GEN)
    out_j, _ = jit_backend.generate("m", toks, GEN)
    np.testing.assert_array_equal(out_c, out_j)


def test_single_step_and_zero_step(backend, jit_backend):
    toks = _prompts(2)
    out_c, _ = backend.generate("m", toks, 1)  # retires at graft
    out_j, _ = jit_backend.generate("m", toks, 1)
    np.testing.assert_array_equal(out_c, out_j)
    h = backend.submit_batch("m", toks, 0)
    assert h.poll() and h.result().shape == (2, 0)


def test_shape_validation(backend):
    wide = np.zeros((1, GEO.prompt_width + 1), np.int32)
    with pytest.raises(ValueError):
        backend.submit_batch("m", wide, GEN)
    with pytest.raises(ValueError):
        backend.submit_batch("m", _prompts(1), GEO.max_steps + 1)


# ---------------------------------------------------------------------------
# Mid-flight join.
# ---------------------------------------------------------------------------
def test_midflight_join_token_exact(backend, jit_backend):
    toks = _prompts(5, seed=9)
    h1 = backend.submit_batch("m", toks[:3], GEN, sync=False)
    backend.pump()
    backend.pump()  # h1 is mid-decode...
    h2 = backend.submit_batch("m", toks[3:], GEN, sync=False)  # ...h2 joins
    assert all(t is not None for t in h2.ttft_wall_ms)
    out1, _ = h1.wait()
    out2, _ = h2.wait()
    ref, _ = jit_backend.generate("m", toks, GEN)
    np.testing.assert_array_equal(np.vstack([out1, out2]), ref)


def test_early_release_recycles_slots(backend, jit_backend):
    toks = _prompts(4, seed=11)
    free_before = len(backend._engines["m"].cache_mgr.free_slots)
    h = backend.submit_batch("m", toks, GEN, sync=False)
    backend.pump()
    h.release_rows([0], "hedge_win")
    h.release_rows([2], "cancel")
    assert h.released_rows == {0: "hedge_win", 2: "cancel"}
    out, _ = h.wait()
    assert len(backend._engines["m"].cache_mgr.free_slots) == free_before
    # Surviving rows still decode to the whole-batch reference.
    ref, _ = jit_backend.generate("m", toks, GEN)
    np.testing.assert_array_equal(out[[1, 3]], ref[[1, 3]])
    # Released rows keep their tokens up to the release point, zero after.
    assert np.array_equal(out[0, :2], ref[0, :2]) and (out[0, 2:] == 0).all()
    backend.check_conservation()


# ---------------------------------------------------------------------------
# The two counter invariants.
# ---------------------------------------------------------------------------
def test_zero_recompiles_after_warmup(backend):
    """Runs after the traffic above (module order): every shape the tier
    has seen — all ladder sizes, partials, joins, releases — and the jit
    caches hold exactly the warmup executables."""
    for B in (1, 3, 5):
        backend.generate("m", _prompts(B), GEN)
    assert backend.compile_count == backend.compiles_after_warmup


def test_slot_recycle_conservation(backend):
    """freed == hedge wins + cancels + resolutions, pool fully drained."""
    stats = backend.slot_stats("m")
    assert stats["freed"] == (
        stats["freed_resolved"]
        + stats["freed_hedge_win"]
        + stats["freed_cancel"]
    )
    assert stats["grafted"] == stats["freed"]  # nothing in flight leaks
    assert stats["freed_hedge_win"] >= 1 and stats["freed_cancel"] >= 1
    assert stats["free_slots"] == GEO.n_slots
    backend.check_conservation()
    assert backend.joined_total == backend.recycled_total == stats["grafted"]


# ---------------------------------------------------------------------------
# Satellite: the max_len knob comes from the zoo geometry.
# ---------------------------------------------------------------------------
def test_backend_max_len_defaults_to_geometry():
    assert JitBackend().max_len == SERVING_GEOMETRY.max_len
    assert JitBackend(max_len=48).max_len == 48
    assert OnDeviceBackend.from_zoo().max_len == SERVING_GEOMETRY.max_len


# ---------------------------------------------------------------------------
# The paged decode loop against the dense ring-cache decode.
# ---------------------------------------------------------------------------
STEPS = 16


def _period_of_two_cfg():
    """Two periods of (``local``, ``moe``) and a ``local`` epilogue block:
    GQA, qk-norm, and a 20-position window, which the decode steps pass
    (the graft takes a window no narrower than the prompt).  Capacity for
    every routed token, so a row's experts do not depend on the rows
    beside it."""
    return reduced(
        "olmoe-1b-7b", pattern=("local", "moe"), n_layers=5, window=20,
        n_kv_heads=2, capacity_factor=2.0,
    )


@pytest.mark.parametrize("which", ["attn", "local-moe-epilogue"])
def test_paged_decode_matches_dense_decode(variant, which):
    """Rows at independent positions, beside an inactive trash-table row,
    decode through the paged layer loop token for token as each row does
    alone on the dense ring cache, with logits within the decode tests'
    tolerance, over ``STEPS`` steps.  Rows 0 and 3 are grafted by
    ``graft_prefill_batch``, row 2 by ``graft_prefill``."""
    if which == "attn":
        cfg, params = variant.cfg, variant.params
    else:
        cfg = _period_of_two_cfg()
        params = T.init_params(cfg, jax.random.key(1))
    page, width = 4, 16
    lengths = np.array([5, 0, 16, 9], np.int32)  # row 1 inactive
    active = np.flatnonzero(lengths)
    prompts = np.random.default_rng(5).integers(
        1, cfg.vocab_size, (len(lengths), width)
    ).astype(np.int32)
    prompts[np.arange(width)[None] >= lengths[:, None]] = 0
    tol = dict(rtol=3e-4, atol=3e-4)

    pages = (width + STEPS) // page
    tables = np.zeros((len(lengths), pages), np.int32)
    for i, r in enumerate(active):
        tables[r] = 1 + i * pages + np.arange(pages)
    pcache, logits = T.prefill_ragged(
        cfg, params, {"tokens": jnp.asarray(prompts)},
        jnp.asarray(lengths), max_len=width,
    )
    pool = T.init_paged_cache(cfg, 1 + len(active) * pages, page)
    batch_tables = tables.copy()
    batch_tables[2] = 0  # row 2 goes through graft_prefill instead
    pool = T.graft_prefill_batch(
        cfg, pool, pcache, jnp.asarray(batch_tables), page
    )
    pool = T.graft_prefill(cfg, pool, pcache, 2, jnp.asarray(tables[2]), page)
    step = jax.jit(
        lambda p, pool, tb, tok, pos: T.paged_decode_step(
            cfg, p, pool, tb, tok, pos, page
        ),
        donate_argnums=1,
    )

    dense_step = jax.jit(
        lambda p, cache, tok, pos: T.decode_step(cfg, p, cache, tok, pos)
    )
    dense = {}
    for r in active:
        n = int(lengths[r])
        cache, dl = T.prefill(
            cfg, params, {"tokens": jnp.asarray(prompts[r : r + 1, :n])},
            max_len=n + STEPS,
        )
        np.testing.assert_allclose(logits[r], dl[0], **tol)
        dense[r] = (cache, dl)

    for i in range(STEPS):
        tok = np.where(lengths > 0, np.argmax(logits, -1), 0).astype(np.int32)
        pos = np.where(lengths > 0, lengths + i, 0).astype(np.int32)
        logits, pool = step(
            params, pool, jnp.asarray(tables), jnp.asarray(tok),
            jnp.asarray(pos),
        )
        for r in active:
            cache, dl = dense[r]
            dtok = jnp.argmax(dl, -1).astype(jnp.int32)
            assert int(dtok[0]) == tok[r], f"row {r} step {i}"
            dl, cache = dense_step(
                params, cache, dtok, jnp.asarray([pos[r]], jnp.int32)
            )
            np.testing.assert_allclose(
                logits[r], dl[0], err_msg=f"row {r} step {i}", **tol
            )
            dense[r] = (cache, dl)


def test_decode_fn_donates_the_pool(variant):
    """The served decode program updates the pool in place: the pool it
    was given is gone after the call, and the one it returns is live."""
    _, _, decode_fn = continuous_step_programs(variant.cfg, GEO)
    pool = T.init_paged_cache(variant.cfg, GEO.total_pages, GEO.page_size)
    tables = jnp.zeros((GEO.n_slots, GEO.pages_per_slot), jnp.int32)
    slots = jnp.zeros((GEO.n_slots,), jnp.int32)
    _, new_pool = decode_fn(variant.params, pool, tables, slots, slots)
    assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pool))
    assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(new_pool))


# ---------------------------------------------------------------------------
# The stepped serving loop.
# ---------------------------------------------------------------------------
def test_loop_stepped_tick_accounting(variant):
    from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

    hedge = OnDeviceBackend.from_zoo(max_len=GEO.max_len)
    engine = ServingEngine(
        hedge_backend=hedge, continuous=True, geometry=GEO
    )
    engine.register(variant)
    assert engine.dispatch == "stepped"
    registry = engine.measure_profiles(
        prompt_len=PROMPT, gen_tokens=GEN, trials=2
    )
    ondevice = hedge.measure_profile(
        prompt_len=PROMPT, gen_tokens=GEN, trials=2
    )
    # Pre-warm the hedge at the tick's pow2 batch shape so its inline
    # compile cannot burn the SLA budget mid-race.
    for N in (2, 4):
        hedge.run_batch(hedge.hedge_name, np.zeros((N, PROMPT), np.int32), GEN)
    engine.backend.warmup()
    compiles = engine.backend.compile_count
    joined_before = engine.backend.joined_total

    sched = MDInferenceScheduler(
        registry, ondevice, SchedulerConfig(t_sla_ms=2000.0, seed=0)
    )
    loop = engine.make_loop(sched)
    toks = _prompts(4, seed=21)
    for i in range(4):
        loop.submit(
            QueuedRequest(
                rid=i, tokens=toks[i], n_steps=GEN,
                t_nw_est_ms=50.0, t_nw_actual_ms=50.0,
            )
        )
    assert loop.tick(now_ms=100.0, wait=False) is None
    results = []
    for _ in range(200):
        results = loop.poll()
        if results:
            break
    assert len(results) == 1
    res = results[0]
    assert len(res.completions) == 4
    assert res.stats.n_joined == 4
    assert res.stats.n_recycled == 4
    assert res.stats.compile_count == compiles  # no tick-time recompiles
    assert engine.backend.joined_total - joined_before == 4
    for c in res.completions:
        assert c.ttft_ms is not None and 0.0 < c.ttft_ms < 1e4
    # Mid-flight-join EWMA observed every joined row.
    assert int(sched.join_count.sum()) == 4
    mu = sched.join_ttft_mu[~np.isnan(sched.join_ttft_mu)]
    assert mu.size >= 1 and (mu > 0).all()
    engine.backend.check_conservation()


# ---------------------------------------------------------------------------
# Streaming: every decode token pushed before resolution, TTFT-stamped.
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dispatch", ["stepped", "sync"])
def test_stream_yields_every_decode_token_before_resolution(variant, dispatch):
    from repro.core.duplication import HedgePolicy
    from repro.serving.client import InferenceClient
    from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

    hedge = OnDeviceBackend.from_zoo(max_len=GEO.max_len)
    engine = ServingEngine(
        hedge_backend=hedge, continuous=True, geometry=GEO, dispatch=dispatch
    )
    engine.register(variant)
    registry = engine.measure_profiles(
        prompt_len=PROMPT, gen_tokens=GEN, trials=2
    )
    ondevice = hedge.measure_profile(
        prompt_len=PROMPT, gen_tokens=GEN, trials=2
    )
    engine.backend.warmup()
    # Selective hedging with a huge SLA: the duplicate never engages, so
    # the remote decode stream deterministically runs to completion.
    sched = MDInferenceScheduler(
        registry, ondevice,
        SchedulerConfig(
            t_sla_ms=60_000.0, seed=0,
            hedge=HedgePolicy(always=False, deadline_headroom_ms=0.0),
        ),
    )
    loop = engine.make_loop(sched)
    fut = InferenceClient(loop).submit(
        _prompts(1, seed=9)[0], n_steps=GEN, t_nw_est_ms=10.0
    )
    chunks, done_at_yield = [], []
    for chunk in fut.stream():
        chunks.append(chunk)
        done_at_yield.append(fut.done())
    c = fut.result(timeout=0)  # already resolved when the stream ends
    assert c.used_remote and not c.hedged
    # Every decode token streamed, in order, with monotone emission stamps
    # (distinct pushes, not the no-channel one-burst fallback).
    assert [ch.index for ch in chunks] == list(range(GEN))
    np.testing.assert_array_equal([ch.token for ch in chunks], c.tokens)
    assert all(a.wall_ms <= b.wall_ms for a, b in zip(chunks, chunks[1:]))
    # The first chunk shares the backend's TTFT stamp exactly.
    assert c.ttft_ms is not None
    assert chunks[0].wall_ms - fut.tier_dispatch_wall_ms["remote"] == (
        pytest.approx(c.ttft_ms, abs=1e-6)
    )
    if dispatch == "stepped":
        # Stepped polling surfaces tokens incrementally: the early tokens
        # arrive while the request is still in flight.
        assert not done_at_yield[0]
    engine.backend.check_conservation()


# ---------------------------------------------------------------------------
# Adaptive stack: the slot ledger stays conserved while the admission
# queue's max_pending is retuned mid-run (PR 9's controller surface).
# ---------------------------------------------------------------------------
def test_slot_ledger_conserved_under_midrun_retunes(variant):
    from repro.serving.admission import AdmissionConfig
    from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

    engine = ServingEngine(continuous=True, geometry=GEO, dispatch="sync")
    engine.register(variant)
    registry = engine.measure_profiles(
        prompt_len=PROMPT, gen_tokens=GEN, trials=2
    )
    ondevice = registry.profiles[0]
    sched = MDInferenceScheduler(
        registry, ondevice, SchedulerConfig(t_sla_ms=60_000.0, seed=0)
    )
    loop = engine.make_loop(
        sched,
        admission=AdmissionConfig(max_pending=4, max_chunk=4, policy="shed"),
    )
    joined_before = engine.backend.joined_total  # profiling grafts rows too
    toks = _prompts(10, seed=33)
    futures, t = [], 0.0
    # Three waves, with the capacity knobs moving between every tick —
    # including a shrink below the current backlog.
    for wave, (mp, headroom) in enumerate(
        [(4, 0.0), (2, 50.0), (6, 0.0)]
    ):
        loop.admission.retune(max_pending=mp, shed_headroom_ms=headroom)
        for i in range(3):
            rid = wave * 3 + i
            futures.append(
                loop.submit(
                    QueuedRequest(
                        rid=rid, tokens=toks[rid], n_steps=GEN,
                        t_nw_est_ms=10.0, t_nw_actual_ms=10.0, arrival_ms=t,
                    )
                )
            )
        t += 100.0
        loop.tick(now_ms=t)
    while loop.backlog:
        t += 100.0
        if loop.tick(now_ms=t) is None:
            break
    # Request conservation across the retuned run...
    from repro.serving.lifecycle import RequestState

    resolved = sum(f.state is RequestState.RESOLVED for f in futures)
    assert resolved + loop.admission.n_rejected == len(futures)
    # ...and the block-paged slot ledger balances: every graft recycled.
    engine.backend.check_conservation()
    assert engine.backend.joined_total == engine.backend.recycled_total
    assert engine.backend.joined_total - joined_before == resolved

"""Smoke test of the serving path at published widths on a TPU.

Run from the checkout root, on a machine with a TPU:

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --chips 4  # the replicated cluster across four chips

One chip: phi3-mini-3.8b at its published widths and depth (seeded random
bf16 weights) is the remote tier, served by the continuous-batching backend,
next to the real on-device hedge tier.  Sixteen 512-token requests go
through ``build_engine`` -> ``make_loop`` -> ``drain_trace``; then the
served decode step is timed, and the logits of the continuous tier's step
functions are compared with a cache-free float32 forward pass over the
same weights.

``--chips 4``: a four-replica ``ClusterBackend`` of full-width phi3-mini
``JitBackend``s, one per chip, routed round robin, serves 16 requests; its
tokens must equal those of a one-replica run of the same prompts.

Each phase checks its results and the script exits non-zero on the first
failure.  It also exits non-zero, printing no result, when JAX runs on no
TPU: it never falls back to the CPU.  On success the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.  The
figures printed before it are smoke figures, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.metadata
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.configs.mdinference_zoo import ONDEVICE_HEDGE, ServingGeometry  # noqa: E402
from repro.core.network import LognormalNetwork  # noqa: E402
from repro.launch.serve import (  # noqa: E402
    build_engine,
    prewarm_hedge,
    use_compile_cache,
)
from repro.models import transformer as T  # noqa: E402
from repro.serving.loadgen import LoadTrace, PoissonArrivals, make_trace  # noqa: E402
from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig  # noqa: E402

ARCH = "phi3-mini-3.8b"
# The selection policy's accuracy proxy: phi-3-mini's MMLU (5-shot) from
# the Phi-3 technical report.
QUALITY = 68.8
SEED = 0
# Large enough that the remote tier always answers inside it.
SLA_MS = 60_000.0
# The continuous tier's shapes: 8 decode slots, 512-token prompts.
GEOMETRY = ServingGeometry(
    max_len=584, prompt_width=512, max_steps=128, n_slots=8, page_size=8,
    bs_ladder=(1, 2, 4, 8),
)

# Logit tolerances of the bf16 served path against the float32 reference,
# in units of the reference logits' RMS over the vocabulary.  The served
# path rounds every activation to bf16 (relative step 2**-8), and 32 layers
# of rounded residual updates carry that into the logits: at 32 layers and
# widths 256 to 1024 (CPU, same code) it measured a max error of 0.09 and a
# mean error of 0.012 RMS.  A wrong cache entry, mask, position or page
# table instead moves the logits by the order of their own RMS.  The limits
# sit about three times above the rounding and far below a fault.
MAX_ERR_RMS = 0.25
MEAN_ERR_RMS = 0.05


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def _params_bytes(params) -> int:
    return sum(leaf.nbytes for leaf in jax.tree.leaves(params))


def _peak_bytes():
    """Device 0's high-water mark so far (None where JAX keeps none)."""
    stats = jax.devices()[0].memory_stats()
    return None if stats is None else stats["peak_bytes_in_use"]


# ---------------------------------------------------------------------------
# One chip: the continuous tier through the normal serving path.
# ---------------------------------------------------------------------------
def serve_continuous(cfg, geometry: ServingGeometry, *, prompt_len: int,
                     gen: int, n_requests: int, rate_rps: float,
                     window_ms: float, seed: int = SEED):
    """Serve ``n_requests`` on the continuous tier; check and report.

    Returns ``(engine, report)``; raises :class:`SmokeFailure` when a
    request was not answered by the remote tier, a batch was lost, a
    program compiled after warmup, or the slot ledger does not balance.
    """
    t0 = time.perf_counter()
    engine = build_engine(
        max_len=geometry.max_len, seed=seed, dispatch="stepped",
        geometry=geometry, tiers=((cfg.name, cfg, QUALITY),),
    )
    peak_built = _peak_bytes()
    registry = engine.measure_profiles(
        prompt_len=prompt_len, gen_tokens=gen, trials=2, seed=seed
    )
    ondevice = engine.hedge_backend.measure_profile(
        prompt_len=prompt_len, gen_tokens=gen, trials=2, seed=seed
    )
    prewarm_hedge(engine, geometry.n_slots, prompt_len, gen)
    compiles = engine.backend.compile_count  # measure_profiles warmed it
    setup_s = time.perf_counter() - t0

    sched = MDInferenceScheduler(
        registry, ondevice, SchedulerConfig(t_sla_ms=SLA_MS, seed=seed)
    )
    trace = make_trace(
        n_requests, PoissonArrivals(rate_rps), LognormalNetwork(300.0, 0.6),
        seed=seed,
    )
    # Token ids that both tiers' vocabularies hold.
    vocab = min(cfg.vocab_size, ONDEVICE_HEDGE.config().vocab_size)
    prompts = np.random.default_rng(seed).integers(
        0, vocab, (n_requests, prompt_len)
    )
    lost = []
    t1 = time.perf_counter()
    completions, metrics = engine.make_loop(sched).drain_trace(
        trace, window_ms, tokens_for=lambda i: prompts[i], n_steps=gen,
        on_tick=lambda _t, res: lost.append(res.stats.n_lost),
    )
    serve_s = time.perf_counter() - t1

    require(
        sorted(c.rid for c in completions) == list(range(n_requests)),
        f"{len(completions)} of {n_requests} requests resolved",
    )
    races = sorted({c.race_resolution for c in completions})
    require(
        all(c.used_remote for c in completions)
        and set(races) <= {"remote_won", "unhedged"},
        f"a request was not answered by the remote tier: {races}",
    )
    require(sum(lost) == 0, f"{sum(lost)} rows lost to failed batches")
    growth = engine.backend.compile_count - compiles
    require(growth == 0, f"{growth} programs compiled after warmup")
    engine.backend.check_conservation()
    for c in completions:
        toks = np.asarray(c.tokens)
        require(
            toks.shape == (gen,) and 0 <= toks.min() and toks.max() < cfg.vocab_size,
            f"request {c.rid}: tokens {toks.shape} outside the vocabulary",
        )
    report = {
        "setup_s": setup_s,
        "serve_s": serve_s,
        "peak_built": peak_built,
        "peak_served": _peak_bytes(),
        "requests": len(completions),
        "race_resolution": dict(metrics.race_resolution),
        "compiled_programs": compiles,
        "post_warmup_recompiles": growth,
    }
    return engine, report


def decode_step_ms(engine, name: str, *, pos: int, steps: int = 16):
    """Wall time of the served ``n_slots``-row decode program, per step.

    Calls the continuous engine's own compiled ``decode_fn`` on its pool
    with all-trash page tables (the writes land in the reserved trash page)
    and waits for each step's tokens, as the serving loop does.  One call
    first, untimed.  Returns the ``steps`` times in milliseconds.
    """
    eng = engine.backend._engines[name]
    g = eng.geometry
    params = eng.variant.params
    tables = jnp.zeros((g.n_slots, g.pages_per_slot), jnp.int32)
    token = jnp.zeros((g.n_slots,), jnp.int32)
    at = jnp.full((g.n_slots,), pos, jnp.int32)
    before = eng.compile_count
    jax.block_until_ready(eng.decode_fn(params, eng.pool, tables, token, at))
    times = []
    for _ in range(steps):
        t0 = time.perf_counter()
        jax.block_until_ready(eng.decode_fn(params, eng.pool, tables, token, at))
        times.append((time.perf_counter() - t0) * 1e3)
    require(eng.compile_count == before, "timing the decode step recompiled it")
    return times


def logit_check(cfg, params, *, prompt_width: int, lengths, steps: int = 2,
                page_size: int = 8, seed: int = SEED):
    """Logits of the continuous tier's step functions against a reference.

    Prefills ``len(lengths)`` right-padded rows with
    :func:`~repro.models.transformer.prefill_ragged`, grafts them into a
    page pool with ``graft_prefill_batch`` and runs ``steps`` of
    ``paged_decode_step`` — the functions the continuous engine jits, here
    returning logits.  The reference is one cache-free forward pass over
    the same bf16 weights with float32 activations at the highest matmul
    precision; weights are upcast inside the jitted function, one layer at
    a time, so no float32 copy of the model is held.

    Returns one ``(max_err, mean_err, ref_rms)`` triple per compared
    position (the prefill's last token, then each decode step).
    """
    lengths = np.asarray(lengths, np.int32)
    B = len(lengths)
    rng = np.random.default_rng(seed + 1)
    tokens = rng.integers(0, cfg.vocab_size, (B, prompt_width)).astype(np.int32)
    for r, n in enumerate(lengths):
        tokens[r, n:] = 0  # right padding, as the continuous tier pads

    pages = -(-(prompt_width + steps) // page_size)
    tables = (1 + np.arange(B * pages, dtype=np.int32)).reshape(B, pages)
    prefill = jax.jit(
        lambda p, t, n: T.prefill_ragged(
            cfg, p, {"tokens": t}, n, max_len=prompt_width
        )
    )
    graft = jax.jit(
        lambda pool, c, tb: T.graft_prefill_batch(cfg, pool, c, tb, page_size),
        donate_argnums=0,
    )
    decode = jax.jit(
        lambda p, pool, tb, tok, pos: T.paged_decode_step(
            cfg, p, pool, tb, tok, pos, page_size
        ),
        donate_argnums=1,
    )
    cache, logits = prefill(params, jnp.asarray(tokens), jnp.asarray(lengths))
    pool = graft(
        T.init_paged_cache(cfg, 1 + B * pages, page_size), cache,
        jnp.asarray(tables),
    )
    del cache
    served = [np.asarray(logits)]
    fed = []
    for i in range(steps):
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        fed.append(np.asarray(tok))
        logits, pool = decode(
            params, pool, jnp.asarray(tables), tok, jnp.asarray(lengths + i)
        )
        served.append(np.asarray(logits))
    del pool

    # The reference sees each row's prompt followed by the fed tokens.
    seq = np.zeros((B, prompt_width + steps), np.int32)
    for r, n in enumerate(lengths):
        seq[r, :n] = tokens[r, :n]
        seq[r, n : n + steps] = [f[r] for f in fed]
    rows = np.repeat(np.arange(B), steps + 1)
    cols = (lengths[:, None] - 1 + np.arange(steps + 1)[None, :]).reshape(-1)
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    @jax.jit
    def reference(p, s, rows, cols):
        x, _, _ = T.forward_hidden(cfg32, p, {"tokens": s})
        return T._unembed(cfg32, p, x[rows, cols][:, None])[:, 0]

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(
            reference(params, jnp.asarray(seq), jnp.asarray(rows),
                      jnp.asarray(cols))
        ).reshape(B, steps + 1, -1)
    out = []
    for j in range(steps + 1):
        err = np.abs(served[j] - ref[:, j])
        rms = float(np.sqrt(np.mean(np.square(ref[:, j]))))
        out.append((float(err.max()), float(err.mean()), rms))
    return out


def check_logits(errors) -> None:
    for j, (max_err, mean_err, rms) in enumerate(errors):
        what = "prefill" if j == 0 else f"decode step {j}"
        require(
            max_err <= MAX_ERR_RMS * rms and mean_err <= MEAN_ERR_RMS * rms,
            f"{what} logits: max err {max_err:.4g}, mean err {mean_err:.4g} "
            f"against a reference RMS of {rms:.4g}",
        )


# ---------------------------------------------------------------------------
# Four chips: one JitBackend replica per chip behind the cluster router.
# ---------------------------------------------------------------------------
def _grouped_trace(n_groups: int, group: int) -> LoadTrace:
    """``n_groups`` bursts of ``group`` arrivals 1 ms apart, 1 s between
    bursts, at a fixed 50 ms network time."""
    arrival = np.asarray(
        [g * 1000.0 + j for g in range(n_groups) for j in range(group)]
    )
    nw = np.full(arrival.shape, 50.0)
    return LoadTrace(arrival_ms=arrival, t_nw_ms=nw, t_nw_est_ms=nw)


def serve_replicated(cfg, replicas: int, trace: LoadTrace, window_ms: float,
                     prompts: np.ndarray, gen: int, seed: int = SEED):
    """Serve ``trace`` on ``replicas`` JitBackend replicas (1: no cluster).

    Returns ``(engine, completions)``."""
    prompt_len = prompts.shape[1]
    engine = build_engine(
        max_len=prompt_len + gen + 8, seed=seed, replicas=replicas,
        router="round_robin", tiers=((cfg.name, cfg, QUALITY),),
    )
    registry = engine.measure_profiles(
        prompt_len=prompt_len, gen_tokens=gen, trials=1, seed=seed
    )
    ondevice = engine.hedge_backend.measure_profile(
        prompt_len=prompt_len, gen_tokens=gen, trials=1, seed=seed
    )
    sched = MDInferenceScheduler(
        registry, ondevice, SchedulerConfig(t_sla_ms=SLA_MS, seed=seed)
    )
    completions, _ = engine.make_loop(sched).drain_trace(
        trace, window_ms, tokens_for=lambda i: prompts[i], n_steps=gen
    )
    require(
        sorted(c.rid for c in completions) == list(range(len(trace)))
        and all(c.used_remote for c in completions),
        f"{replicas}-replica run: not every request answered remotely",
    )
    return engine, completions


def replicas_check(cfg, n_replicas: int, *, prompt_len: int, gen: int,
                   seed: int = SEED):
    """The cluster across ``n_replicas`` devices against one replica.

    Bursts of ``n_replicas`` requests make one tick each, which the loop
    fans out one row per replica; the one-replica run serves every
    request in a tick of its own.  Every batch thus has one row in both
    runs, so both run the same programs and the tokens must be equal.
    """
    devices = jax.devices()[:n_replicas]
    require(
        len(devices) == n_replicas,
        f"{n_replicas} replicas need {n_replicas} devices, JAX has "
        f"{len(jax.devices())}",
    )
    trace = _grouped_trace(4, n_replicas)
    vocab = min(cfg.vocab_size, ONDEVICE_HEDGE.config().vocab_size)
    prompts = np.random.default_rng(seed).integers(
        0, vocab, (len(trace), prompt_len)
    )
    t0 = time.perf_counter()
    engine, single = serve_replicated(cfg, 1, trace, 0.5, prompts, gen, seed)
    del engine
    gc.collect()
    t1 = time.perf_counter()
    engine, pooled = serve_replicated(
        cfg, n_replicas, trace, 100.0, prompts, gen, seed
    )
    t2 = time.perf_counter()

    want = {c.rid: np.asarray(c.tokens) for c in single}
    mismatched = [
        c.rid for c in pooled if not np.array_equal(np.asarray(c.tokens), want[c.rid])
    ]
    require(not mismatched, f"tokens differ from the 1-replica run: {mismatched}")
    rows = {i: 0 for i in range(n_replicas)}
    for c in pooled:
        rows[c.replica] += 1
    require(all(rows.values()), f"a replica served no rows: {rows}")

    for i, replica in enumerate(engine.backend.pool.replicas):
        params = replica.backend.variants[cfg.name].params
        held = {d for leaf in jax.tree.leaves(params) for d in leaf.devices()}
        require(held == {devices[i]}, f"replica {i} params on {held}")
    report = {
        "single_s": t1 - t0,
        "pooled_s": t2 - t1,
        "rows_per_replica": rows,
        "tokens_match": len(pooled),
        "weight_bytes": _params_bytes(params),
    }
    return engine, report


def device_bytes_in_use(devices, weight_bytes: int):
    """``bytes_in_use`` of each device; one weight copy on each, no more."""
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    require(
        all(weight_bytes <= b < 2 * weight_bytes for b in in_use),
        f"expected one weight copy ({weight_bytes} B) per device, in use: "
        f"{in_use}",
    )
    return in_use


# ---------------------------------------------------------------------------
def _versions() -> str:
    libtpu = "absent"
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        pass
    jaxlib = importlib.metadata.version("jaxlib")
    return f"jax {jax.__version__}, jaxlib {jaxlib}, libtpu {libtpu}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the continuous tier on one chip; 4: only the "
                    "replicated cluster across four chips")
    args = ap.parse_args(argv)
    cache_dir = use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: JAX runs on {devices[0].platform!r}, not a TPU; "
            "this smoke test runs on a TPU only",
            file=sys.stderr,
        )
        return 1
    print(f"versions      : {_versions()}")
    for d in devices:
        print(f"device        : {d.id} platform={d.platform} kind={d.device_kind}")
    print(f"device count  : {len(devices)}")
    print(f"compile cache : {cache_dir}")
    cfg = get_config(ARCH)
    print(
        f"model         : {cfg.name} {cfg.n_layers}L d={cfg.d_model} "
        f"{cfg.n_heads}x{cfg.head_dim} heads d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size} {cfg.dtype}, seeded random weights"
    )
    try:
        if args.chips == 4:
            engine, rep = replicas_check(cfg, 4, prompt_len=128, gen=32)
            in_use = device_bytes_in_use(devices[:4], rep["weight_bytes"])
            print(f"1-replica run : {rep['single_s']:.1f} s (compile included)")
            print(f"4-replica run : {rep['pooled_s']:.1f} s (compile included)")
            print(f"rows/replica  : {rep['rows_per_replica']}")
            print(
                f"weights       : {rep['weight_bytes']} B per replica; "
                f"bytes_in_use per device {in_use}"
            )
            print(f"tokens        : {rep['tokens_match']} requests equal "
                  "to the 1-replica run")
        else:
            engine, rep = serve_continuous(
                cfg, GEOMETRY, prompt_len=512, gen=64, n_requests=16,
                rate_rps=8.0, window_ms=250.0,
            )
            print(f"compile       : {rep['setup_s']:.1f} s (build, profile, "
                  f"warm {rep['compiled_programs']} programs)")
            print(f"serve         : {rep['serve_s']:.1f} s for "
                  f"{rep['requests']} requests")
            print(f"race          : {rep['race_resolution']}")
            print(f"recompiles    : {rep['post_warmup_recompiles']} after "
                  "warmup; conservation ok")
            step_ms = decode_step_ms(engine, cfg.name, pos=512)
            print(
                f"decode step   : median {np.median(step_ms):.3f} ms, min "
                f"{min(step_ms):.3f} ms, max {max(step_ms):.3f} ms over "
                f"{len(step_ms)} steps of {GEOMETRY.n_slots} slots "
                "(host clock, tokens waited for)"
            )
            params = engine.backend.variants[cfg.name].params
            errors = logit_check(
                cfg, params, prompt_width=GEOMETRY.prompt_width,
                lengths=(512, 301),
            )
            for j, (max_err, mean_err, rms) in enumerate(errors):
                what = "prefill" if j == 0 else f"decode {j}"
                print(
                    f"logits {what:9s}: max err {max_err:.4g} mean err "
                    f"{mean_err:.4g} ref rms {rms:.4g} "
                    f"(limits {MAX_ERR_RMS} / {MEAN_ERR_RMS} rms)"
                )
            check_logits(errors)
            print(
                f"peak memory   : {rep['peak_built']} B after building, "
                f"{rep['peak_served']} B after serving, {_peak_bytes()} B "
                "after the logit check"
            )
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        },
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""One run of one cell: set-up, the measured window, metrics, the check.

The program under test is built through its normal entry
(``launch/serve.build_engine`` and ``ServingEngine.make_loop``) from what
the configuration file names: the model, the serving geometry, replicas,
router and admission.  Everything the program computes reaches this
module through the serving loop's futures; the reference that decides
``correct`` imports nothing of the program.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import shutil
import tempfile
import time
from typing import List, Optional

import jax
import numpy as np

from chipbench import check, counts, devtrace, spec, traffic, window
from chipbench.spec import Model

# The traced run traces the last TRACE_S seconds of its window.
TRACE_S = 10.0


def _pow2_upto(n: int) -> List[int]:
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    if out[-1] < n:
        out.append(b)
    return out


def _same_model(program_cfg, m: Model) -> List[str]:
    """Fields in which the program's model differs from ``m``."""
    want = spec.family(m.kind).program_model(m)
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab_size", "pattern", "mlp_type", "rope_theta",
            "norm_eps", "tie_embeddings", "emb_scale", "norm_offset", "dtype",
            "window", "qk_norm", "causal")
    return [k for k in keys if getattr(program_cfg, k) != getattr(want, k)]


@dataclasses.dataclass
class Served:
    engine: object
    loop: object
    setup_s: float
    max_chunk: int
    programs_warmed: int


def build(cfg: spec.Config, mix: dict, seed: int, t_start: float) -> Served:
    """Build, profile and warm the program for ``cfg``; returns it ready."""
    from repro.configs.mdinference_zoo import ServingGeometry
    from repro.launch.serve import build_engine, prewarm_hedge
    from repro.serving.admission import AdmissionConfig
    from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

    sv = cfg.serving
    P, n = int(mix["prompt_tokens"]), int(mix["output_tokens"])
    geometry = ServingGeometry(**{
        k: tuple(v) if isinstance(v, list) else v
        for k, v in sv["geometry"].items()
    })
    if P + n > geometry.max_len:
        raise ValueError(f"prompt {P} + answer {n} exceed max_len {geometry.max_len}")
    window_limit = cfg.remote.sliding_window
    if window_limit and geometry.max_len > window_limit:
        raise ValueError("served lengths reach the sliding window")
    engine = build_engine(
        max_len=geometry.max_len, seed=seed, dispatch=sv["dispatch"],
        replicas=int(sv.get("replicas", 1)), router=sv.get("router", "round_robin"),
        geometry=geometry,
        tiers=((cfg.name, spec.family(cfg.remote.kind).program_model(cfg.remote),
                cfg.remote_quality),),
    )
    hedge = engine.hedge_backend
    hv = hedge.variants[hedge.hedge_name]
    diff = _same_model(hv.cfg, cfg.hedge)
    if diff or hv.quality != cfg.hedge_quality:
        raise ValueError(f"the program's hedge differs from the config file: {diff}")
    trials = int(sv["profile_trials"])
    registry = engine.measure_profiles(prompt_len=P, gen_tokens=n, trials=trials, seed=seed)
    ondevice = hedge.measure_profile(prompt_len=P, gen_tokens=n, trials=trials, seed=seed)
    max_chunk = int(sv["admission"]["max_chunk"])
    # Warm every shape the window can see: the hedge at each power-of-two
    # row count up to a tick's chunk (the continuous tier warmed its
    # prefill and graft at each ladder size and its decode step while
    # profiling), and the selection policy at each padded chunk length.
    prewarm_hedge(engine, max_chunk, P, n)
    warmed = len(_pow2_upto(max_chunk)) + len(geometry.bs_ladder) * 2 + 1
    sched_cfg = SchedulerConfig(t_sla_ms=float(mix["sla_ms"]), seed=seed)
    probe = MDInferenceScheduler(registry, ondevice, sched_cfg)
    for rows in _pow2_upto(max_chunk):
        probe.decide_batch(np.full(rows, 50.0))
    sched = MDInferenceScheduler(registry, ondevice, sched_cfg)
    loop = engine.make_loop(sched, admission=AdmissionConfig(max_chunk=max_chunk))
    jax.block_until_ready(jax.numpy.zeros(()))
    return Served(engine, loop, time.perf_counter() - t_start, max_chunk, warmed)


@dataclasses.dataclass
class Request:
    """What one client got, in plain values (no program objects)."""

    due_s: float
    network_ms: float
    answered: bool
    resolved_s: Optional[float] = None
    used_remote: bool = False
    race: str = ""
    tokens: Optional[np.ndarray] = None
    scheduled_ms: Optional[float] = None  # loop clock: ms since the window opened
    chunks_s: List[float] = dataclasses.field(default_factory=list)
    legs: dict = dataclasses.field(default_factory=dict)  # tier -> (start_s, end_s)


def _requests(win: window.Window) -> List[Request]:
    out = []
    for r in win.records:
        f = r.future
        req = Request(r.due_s, r.network_ms, False)
        if f is not None:
            req.scheduled_ms = f.scheduled_ms
            req.chunks_s = [c.wall_ms / 1e3 for c in f.chunks]
            for tier, t in f.tier_dispatch_wall_ms.items():
                done = f.tier_done_wall_ms.get(tier)
                req.legs[tier] = (t / 1e3, None if done is None else done / 1e3)
            if r.resolved_s is not None and f.done() and not f.cancelled():
                c = f.result(timeout=0)
                req.answered = True
                req.resolved_s = r.resolved_s
                req.used_remote = bool(c.used_remote)
                req.race = c.race_resolution
                req.tokens = np.asarray(c.tokens)
        out.append(req)
        r.future = None  # futures hold the loop, and through it the weights
    return out


@dataclasses.dataclass
class RunView:
    """Everything a per-layer metric reader may read."""

    cfg: spec.Config
    mix: dict
    requests: List[Request]
    t0: float
    seconds: float
    chips: int
    traced: Optional[tuple]  # (start, stop) perf_counter seconds
    trace: Optional[devtrace.Trace]
    peak: dict
    # The requests whose life (due to resolution) holds no profiler start
    # or stop: the host-side per-layer metrics read these.
    clean: List[Request] = dataclasses.field(default_factory=list)


def end_to_end(cfg: spec.Config, mix: dict, reqs: List[Request], t0: float,
               seconds: float, closed_s: float) -> dict:
    sla = float(mix["sla_ms"])
    lat, acc, tokens = [], [], 0
    end = t0 + seconds
    for r in reqs:
        # Tokens delivered inside the window: the chunks streamed before
        # the close, or, where the answer came whole (from the hedge or a
        # tier that does not stream) before the close, the answer.
        streamed = sum(t < end for t in r.chunks_s)
        whole = len(r.tokens) if r.answered and r.resolved_s < end else 0
        tokens += max(streamed, whole)
        if not r.answered:
            lat.append(1e3 * (closed_s - r.due_s))
            acc.append(0.0)
            continue
        lat.append(1e3 * (r.resolved_s - r.due_s) + (r.network_ms if r.used_remote else 0.0))
        acc.append(cfg.remote_quality if r.used_remote else cfg.hedge_quality)
    lat = np.asarray(lat)
    return {
        "latency_p90_ms": (float(np.percentile(lat, 90)), "ms"),
        "sla_attainment": (100.0 * float(np.mean(lat <= sla)), "%"),
        "aggregate_accuracy": (float(np.mean(acc)), "%"),
        "tokens_per_s": (tokens / seconds, "tokens/s"),
    }


def per_layer(bench: dict, cell: str, view: RunView) -> dict:
    out = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        path = spec.metric_file(m["name"])
        modspec = importlib.util.spec_from_file_location(f"metric_{m['name']}", path)
        mod = importlib.util.module_from_spec(modspec)
        modspec.loader.exec_module(mod)
        value = mod.read(view)
        if value is not None:
            out[m["name"]] = (float(value), m["unit"])
    return out


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats is not None:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def run(bench: dict, cell: str, seed: int, seconds: float, trace: bool,
        t_start: float, log, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line's object.  With
    ``control`` the comparison reads the control in the program's place
    (see :func:`compare`), and ``correct`` is the control's verdict."""
    w = spec.workload(bench, cell)
    cfg = spec.config(w["config"], spec.config_file(bench, w["config"]))
    mix = spec.load_json(spec.traffic_file(w["traffic"]))
    devices = jax.devices()[: int(w["chips"])]
    peak = counts.peaks(devices[0].device_kind) if trace else None
    vocab = min(cfg.remote.vocab_size, cfg.hedge.vocab_size)
    requests = traffic.generate(mix, seconds, seed, vocab)
    served = build(cfg, mix, seed, t_start)
    log(f"set-up        : {served.setup_s:.3f} s, {served.programs_warmed} program "
        "shapes warmed")
    from repro.serving.lifecycle import QueuedRequest

    def make_request(i, arrival_ms):
        return QueuedRequest(
            rid=i, tokens=requests.prompts[i], n_steps=requests.output_tokens,
            t_nw_est_ms=float(requests.network_ms[i]),
            t_nw_actual_ms=float(requests.network_ms[i]), arrival_ms=arrival_ms,
        )

    trace_dir = profiler = span = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        profiler = (lambda: jax.profiler.start_trace(trace_dir, profiler_options=opts),
                    jax.profiler.stop_trace)
        length = min(TRACE_S, seconds)
        span = (seconds - length, length)
    win = window.drive(
        served.loop, requests, make_request, seconds=seconds,
        tick_ms=float(cfg.serving["tick_ms"]), trace_span=span, profiler=profiler,
    )
    late = win.submit_late_ms()
    log(f"generator     : {len(late)} of {len(requests)} submitted, late by "
        f"p50 {np.percentile(late, 50):.3f} ms, p99 {np.percentile(late, 99):.3f} ms, "
        f"max {late.max():.3f} ms; ticks late p99 "
        f"{1e3 * np.percentile(win.tick_late_s, 99):.3f} ms")
    log(f"window        : {win.compile_events} compile events inside the window")
    memory_peak = _peak_bytes(devices)
    reqs = _requests(win)
    metrics = end_to_end(cfg, mix, reqs, win.t0, seconds, win.closed_s)
    metrics["setup_s"] = (served.setup_s, "s")
    failed = sum(not r.answered for r in reqs)
    races = {}
    for r in reqs:
        races[r.race or "unanswered"] = races.get(r.race or "unanswered", 0) + 1
    log(f"answers       : {races}")
    lat = [1e3 * (r.resolved_s - r.due_s) + (r.network_ms if r.used_remote else 0.0)
           for r in reqs if r.answered]
    if lat:
        log(f"latency       : p50 {np.percentile(lat, 50):.1f} ms, p90 "
            f"{np.percentile(lat, 90):.1f} ms, max {max(lat):.1f} ms over the answered")
    result_trace = None
    if trace:
        table = devtrace.extract(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        result_trace = devtrace.reduce(table, win.traced[1] - win.traced[0])
        clean = [r for r in reqs if not any(
            a < (r.resolved_s or win.closed_s) and b > r.due_s for a, b in win.profiler_calls)]
        log(f"profiler      : start/stop took "
            f"{[round(b - a, 3) for a, b in win.profiler_calls]} s; {len(clean)} of "
            f"{len(reqs)} requests lived clear of them")
        view = RunView(cfg, mix, reqs, win.t0, seconds, len(devices), win.traced,
                       result_trace, peak, clean)
        metrics = per_layer(bench, cell, view)
        _log_matches(view, log)
    del served
    gc.collect()
    compared = compare(cfg, requests, reqs, seed, log, control)
    out = {
        "correct": verdict(compared),
        "attempted": len(reqs),
        "failed": failed,
        "metrics": {k: {"value": v, "unit": u} for k, (v, u) in metrics.items()},
        "device": {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(jax.devices()),
            "memory_peak_bytes": memory_peak,
        },
    }
    if result_trace is not None:
        out["device"]["busy_s"] = result_trace.busy_s
        out["device"]["window_s"] = result_trace.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in result_trace.top_ops],
            "idle_gaps": [[k, v] for k, v in result_trace.idle_by_host],
        }
    out["compared"] = compared
    return out


def _log_matches(view: RunView, log) -> None:
    """How many of the remote model's runs in the trace found the host
    stamps they produced, and how long after each run's end they came."""
    from chipbench import readings

    for fn, match in (("prefill_fn", readings.prefill_runs),
                      ("decode_fn", readings.decode_runs)):
        lags: list = []
        n = len(match(view, lags))
        if lags:
            log(f"runs matched  : {fn} {n} of {len(readings.served_runs(view, fn))} in the "
                f"trace, {len(lags)} stamps, lag after the run's end min "
                f"{1e3 * min(lags):.3f} p50 {1e3 * np.median(lags):.3f} max "
                f"{1e3 * max(lags):.3f} ms")


def sample_groups(cfg: spec.Config, requests: traffic.Requests, reqs: List[Request],
                  seed: int) -> dict:
    """The sampled requests by the tier that answered them:
    ``{tier: (prompts, served tokens)}``."""
    lim = cfg.correct
    answered = [(i, r.used_remote) for i, r in enumerate(reqs) if r.answered]
    pick = check.sample(answered, seed, int(lim["sample_remote"]), int(lim["sample_hedge"]))
    groups = {}
    for tier, remote in (("remote", True), ("hedge", False)):
        idx = [i for i in pick if reqs[i].used_remote == remote]
        if idx:
            groups[tier] = (requests.prompts[idx], np.stack([reqs[i].tokens for i in idx]))
    return groups


def verdict(compared: dict) -> bool:
    """``correct``: every number compared within its limit."""
    return all(v["value"] <= v["limit"] for v in compared.values())


def compare(cfg: spec.Config, requests: traffic.Requests, reqs: List[Request],
            seed: int, log, control: bool = False) -> dict:
    """The numbers that decide ``correct``, each beside its limit.

    With ``control`` the tokens judged at each position of the sampled
    requests are not the served ones but those that the reference one
    precision step down ranks first there (the control of the
    comparison), read against the same float32 reference and limit."""
    groups = sample_groups(cfg, requests, reqs, seed)
    t = time.perf_counter()
    widest = check.widest_gap({"remote": cfg.remote, "hedge": cfg.hedge}, seed, groups,
                              control=control)
    log(f"reference     : {sum(len(g[0]) for g in groups.values())} requests compared in "
        f"{time.perf_counter() - t:.3f} s; widest gap by tier {widest}"
        + (" (the control)" if control else ""))
    return {
        "logit_gap_max": {"value": max(widest.values()) if widest else check.WRONG,
                          "limit": float(cfg.correct["logit_gap_max"]["limit"])},
        "unanswered": {"value": sum(not r.answered for r in reqs), "limit": 0},
    }

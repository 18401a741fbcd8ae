"""The serving launcher's entry points: tier list, compile cache, transport."""
import pathlib

import jax
import numpy as np
import pytest

from repro.core.network import LognormalNetwork
from repro.launch import serve
from repro.launch.serve import TIERS, build_engine, use_compile_cache
from repro.serving.loadgen import PoissonArrivals, make_trace
from repro.serving.scheduler import MDInferenceScheduler, SchedulerConfig

PROMPT, GEN = 8, 3
CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def test_explicit_one_tier_list_serves_like_the_default_path():
    max_len = PROMPT + GEN + 8
    default = build_engine(max_len=max_len, measured_hedge=False)
    name = TIERS[1][0]
    one = build_engine(max_len=max_len, measured_hedge=False, tiers=TIERS[1:2])
    assert list(one.variants) == [name]
    assert list(default.variants) == [t[0] for t in TIERS]

    registry = one.measure_profiles(prompt_len=PROMPT, gen_tokens=GEN, trials=1)
    sched = MDInferenceScheduler(
        registry, registry[0], SchedulerConfig(t_sla_ms=5_000.0, seed=0)
    )
    n = 6
    trace = make_trace(
        n, PoissonArrivals(5.0), LognormalNetwork(300.0, 0.6), seed=0
    )
    prompts = np.random.default_rng(0).integers(0, 256, (n, PROMPT))
    completions, _ = one.make_loop(sched, dispatch="sync").drain_trace(
        trace, 1.0, tokens_for=lambda i: prompts[i], n_steps=GEN
    )
    assert sorted(c.rid for c in completions) == list(range(n))
    for c in completions:
        assert c.model_name == name
        want, _ = default.generate(name, prompts[c.rid][None], GEN)
        np.testing.assert_array_equal(c.tokens, want[0])


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = use_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert use_compile_cache() == path  # fixed, not per run
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_process_transport_refused_on_a_tpu(monkeypatch, capsys):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit) as exit_info:
        serve.main(["--transport", "process", "--replicas", "2"])
    assert exit_info.value.code == 2
    assert "CPU-only" in capsys.readouterr().err

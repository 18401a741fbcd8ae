"""``chip_smoke.py`` at toy widths on the CPU.

The script's phases run here on a reduced phi3-mini and a small serving
geometry; its ``main`` must refuse to run anywhere but on a TPU.
"""
import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.configs import reduced
from repro.configs.mdinference_zoo import ServingGeometry
from repro.models import transformer as T

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", CHECKOUT / "chip_smoke.py"
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def chip_smoke():
    return _load_chip_smoke()


def _tiny_phi3():
    return reduced(
        "phi3-mini-3.8b", d_model=64, n_layers=2, n_heads=4, n_kv_heads=4,
        head_dim=16, d_ff=128, vocab_size=512, dtype="bfloat16",
    )


def test_refuses_to_run_without_a_tpu(chip_smoke, monkeypatch, tmp_path,
                                      capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "not a TPU" in out.err


def test_continuous_tier_serves_and_its_logits_agree(chip_smoke):
    cfg = _tiny_phi3()
    geometry = ServingGeometry(
        max_len=48, prompt_width=32, max_steps=8, n_slots=4, page_size=8,
        bs_ladder=(1, 2, 4),
    )
    engine, report = chip_smoke.serve_continuous(
        cfg, geometry, prompt_len=32, gen=8, n_requests=6, rate_rps=8.0,
        window_ms=250.0,
    )
    assert report["requests"] == 6
    assert report["post_warmup_recompiles"] == 0
    step_ms = chip_smoke.decode_step_ms(engine, cfg.name, pos=32, steps=3)
    assert len(step_ms) == 3 and all(t > 0 for t in step_ms)
    errors = chip_smoke.logit_check(
        cfg, engine.backend.variants[cfg.name].params, prompt_width=32,
        lengths=(32, 17),
    )
    assert len(errors) == 3
    chip_smoke.check_logits(errors)


def test_logit_check_catches_a_wrong_position(chip_smoke, monkeypatch):
    cfg = _tiny_phi3()
    params = T.init_params(cfg, jax.random.key(0))
    chip_smoke.check_logits(
        chip_smoke.logit_check(cfg, params, prompt_width=32, lengths=(32, 17))
    )
    # A decode step one position behind overwrites the last prompt entry
    # and rotates by the wrong angle: the check must refuse it.
    step = T.paged_decode_step
    monkeypatch.setattr(
        T, "paged_decode_step",
        lambda cfg, p, pool, tb, tok, pos, page: step(
            cfg, p, pool, tb, tok, pos - 1, page
        ),
    )
    errors = chip_smoke.logit_check(
        cfg, params, prompt_width=32, lengths=(32, 17)
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="decode step 1"):
        chip_smoke.check_logits(errors)


def test_replicas_across_four_devices_match_one_replica():
    # Four virtual CPU devices exist only in a fresh process.
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
    )
    script = (
        "import chip_smoke, test_chip_smoke as t\n"
        "engine, rep = chip_smoke.replicas_check("
        "t._tiny_phi3(), 4, prompt_len=16, gen=4)\n"
        "assert rep['rows_per_replica'] == {0: 4, 1: 4, 2: 4, 3: 4}, rep\n"
        "print('tokens match', rep['tokens_match'])\n"
    )
    env["PYTHONPATH"] = os.pathsep.join(
        [str(CHECKOUT), str(CHECKOUT / "tests"), str(CHECKOUT / "src")]
    )
    done = subprocess.run(
        [sys.executable, "-c", script], cwd=CHECKOUT, env=env,
        capture_output=True, text=True, timeout=600,
    )
    assert done.returncode == 0, done.stderr[-4000:]
    assert "tokens match 16" in done.stdout

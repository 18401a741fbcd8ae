"""A CPU rehearsal of a whole run at toy widths, through the code the
command uses, and the command's refusals."""
import json
import os
import pathlib
import shutil
import subprocess
import sys
import time

import pytest

import toy
from chipbench import counts, harness, spec

CHIP = pathlib.Path(__file__).resolve().parents[1]
ROOT = CHIP.parents[1]
FAKE_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}


@pytest.fixture
def toy_bench(tmp_path, monkeypatch):
    bench = toy.write(tmp_path)
    monkeypatch.setattr(spec, "traffic_file", lambda name: tmp_path / f"{name}.json")
    monkeypatch.setattr(counts, "peaks", lambda kind: FAKE_PEAK)
    return bench


def run_toy(bench, trace: bool, lines=None, control: bool = False):
    log = (lines.append if lines is not None else lambda s: None)
    out = harness.run(bench, "toy.chat", 2**31 + 11, 2.0, trace, time.perf_counter(), log,
                      control=control)
    return json.loads(json.dumps(out))  # the result line is plain JSON


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
def test_rehearsal_prints_the_result_line(toy_bench, trace):
    lines = []
    out = run_toy(toy_bench, trace, lines)
    assert list(out) == ["correct", "attempted", "failed", "metrics", "device"] + (
        ["breakdown"] if trace else []) + ["compared"]
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] == 12
    names = [m["name"] for m in toy_bench["end_to_end" if not trace else "per_layer"]]
    assert sorted(out["metrics"]) == sorted(names)
    assert all(v["value"] > 0 or k == "hedge_win_share" for k, v in out["metrics"].items())
    assert set(out["compared"]) == {"logit_gap_max", "unanswered"}
    assert any("0 compile events inside the window" in s for s in lines)
    if trace:
        assert 0 < out["device"]["busy_s"] < out["device"]["window_s"]
        assert out["breakdown"]["device_ops"] and out["breakdown"]["idle_gaps"]


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "phi3-mini.chat-university", "--seed", str(2**31 + 5), "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_command_refuses_without_a_tpu():
    res = _command(ROOT)
    assert res.returncode != 0 and res.stdout.strip() == ""
    assert "not a TPU" in res.stderr


def test_command_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    res = _command(tmp_path)
    assert res.returncode != 0 and res.stdout.strip() == ""


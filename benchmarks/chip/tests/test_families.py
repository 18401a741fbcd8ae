"""The family modules: the weights, reference and counts that moved into
them read exactly what they read before, and a configuration of a new
``model_type`` needs only new files."""
import hashlib
import json
import pathlib
from types import SimpleNamespace

import numpy as np
import pytest

import toy
from chipbench import check, counts, readings, reference, spec, weights
from test_reference import GEMMA, PHI3
from test_rehearsal import run_toy, toy_bench  # noqa: F401

HERE = pathlib.Path(__file__).resolve().parent
CFG_RAW = spec.load_json(spec.BENCH_DIR / "configs" / "phi3-mini.json")
CFG = spec.config("phi3-mini", spec.BENCH_DIR / "configs" / "phi3-mini.json")

# What each quantity read at phi3-mini's published widths and at the
# hedge's, before the architecture moved out of the harness.
QUANTITIES = {
    "token_flops": lambda m: spec.family(m.kind).token_flops(m),
    "step_weight_bytes": counts.step_weight_bytes,
    "least_weight_bytes": counts.least_weight_bytes,
    "kv_bytes_per_token": counts.kv_bytes_per_token,
    "weights_nbytes": weights.nbytes,
    "prefill_flops_512": lambda m: counts.prefill_flops(m, 512),
    "decode_flops_575": lambda m: counts.decode_flops(m, 575),
    "decode_bytes_512_519": lambda m: counts.decode_bytes(m, range(512, 520)),
    "prefill_bytes_3x512": lambda m: counts.prefill_bytes(m, 3, 512),
}
PINNED = {
    "remote": {
        "token_flops": 7_444_758_528, "step_weight_bytes": 7_445_557_248,
        "least_weight_bytes": 7_445_557_248, "kv_bytes_per_token": 393_216,
        "weights_nbytes": 7_642_558_464, "prefill_flops_512": 3_863_356_637_184,
        "decode_flops_575": 7_671_250_944, "decode_bytes_512_519": 9_070_325_760,
        "prefill_bytes_3x512": 8_049_537_024,
    },
    "hedge": {
        "token_flops": 47_104, "step_weight_bytes": 94_592, "least_weight_bytes": 94_592,
        "kv_bytes_per_token": 128, "weights_nbytes": 94_592,
        "prefill_flops_512": 40_927_232, "decode_flops_575": 120_832,
        "decode_bytes_512_519": 623_488, "prefill_bytes_3x512": 291_200,
    },
}

# SHA-256 of ``weights.make(m, 5)`` at the toy widths of ``test_reference``
# (name, dtype, shape and bytes of each weight, in name order), and the
# reference logits in ``data/reference_toy_logits.npz``: both as the
# harness gave them before the move, on the CPU.
WEIGHTS_SHA256 = {
    "phi3": "86375c4c66f43b21814e5c4b0cdd313af6cd91e62bd3424315797dc206905aa3",
    "gemma": "8f4e9e9579dde8848e319e3c56e931d80f478628b509590e1d550b6c8363443a",
}


def _digest(w: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(w):
        a = np.asarray(w[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("quantity", list(QUANTITIES))
@pytest.mark.parametrize("tier", ["remote", "hedge"])
def test_counts_read_as_before_the_move(tier, quantity):
    m = CFG.remote if tier == "remote" else CFG.hedge
    assert QUANTITIES[quantity](m) == PINNED[tier][quantity]


@pytest.mark.parametrize("m", [PHI3, GEMMA], ids=lambda m: m.kind)
def test_weights_are_the_same_bytes(m):
    assert _digest(weights.make(m, 5)) == WEIGHTS_SHA256[m.kind]


@pytest.mark.parametrize("control", [False, True], ids=["float32", "control"])
@pytest.mark.parametrize("m", [PHI3, GEMMA], ids=lambda m: m.kind)
def test_reference_logits_are_the_same_bits(m, control):
    pinned = np.load(HERE / "data" / "reference_toy_logits.npz")
    quant = check.control_quant(m) if control else "none"
    tokens = pinned[f"{m.kind}_tokens"]
    rows, cols = np.repeat([0, 1], 24), np.tile(np.arange(24), 2)
    got = np.asarray(reference.logits(m, weights.make(m, 5), tokens, rows, cols, quant))
    np.testing.assert_array_equal(got, pinned[f"{m.kind}_{quant}"])


def _new_family(tmp_path, monkeypatch, source: str) -> None:
    """A family file for the ``model_type`` ``phi3x`` outside the harness,
    with the lookup pointed at it, and the toy cell's remote model naming
    that ``model_type``."""
    path = tmp_path / "families" / "phi3x.py"
    path.parent.mkdir()
    path.write_text(source)
    original = spec.family_file
    monkeypatch.setattr(spec, "family_file",
                        lambda mt: path if mt == "phi3x" else original(mt))
    raw = spec.load_json(tmp_path / "toy.json")
    raw["config"]["model_type"] = "phi3x"
    (tmp_path / "toy.json").write_text(json.dumps(raw))


def test_a_new_model_type_runs_from_new_files_only(toy_bench, tmp_path,  # noqa: F811
                                                   monkeypatch):
    _new_family(tmp_path, monkeypatch, "from chipbench.families.phi3 import *  # noqa\n")
    out = run_toy(toy_bench, False)
    assert spec.config("toy", tmp_path / "toy.json").remote.kind == "phi3x"
    assert spec.family("phi3x").__file__ == str(tmp_path / "families" / "phi3x.py")
    assert out["correct"] is True and out["failed"] == 0


def test_the_check_goes_through_the_family_reference(toy_bench, tmp_path,  # noqa: F811
                                                      monkeypatch):
    """A family whose reference leaves out the last layer: the served
    tokens no longer agree with it."""
    _new_family(tmp_path, monkeypatch, """\
from chipbench.families import phi3
from chipbench.families.phi3 import *  # noqa


def logits(m, w, tokens, rows, cols, quant):
    w = {k: v[:-1] if k.startswith("periods/") else v for k, v in w.items()}
    return phi3.logits(m, w, tokens, rows, cols, quant)
""")
    out = run_toy(toy_bench, False)
    assert out["correct"] is False
    c = out["compared"]["logit_gap_max"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("model_type", ["nope", "../spec"])
def test_an_unknown_model_type_is_refused(model_type):
    hf = dict(CFG_RAW["config"], model_type=model_type)
    with pytest.raises(ValueError, match=r"families/(nope|\.\./spec)\.py would define it"):
        spec.model("x", hf)


@pytest.mark.parametrize("key,value", [("rope_scaling", {"type": "longrope"}),
                                       ("attention_bias", True),
                                       ("hidden_act", "gelu_pytorch_tanh")])
def test_a_key_without_a_reference_is_refused(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        spec.model("x", dict(CFG_RAW["config"], **{key: value}))


def test_the_weights_floor_keeps_a_remote_run_at_it_and_drops_a_hedge_run(tmp_path):
    """At toy widths: a run that lasts exactly the remote model's least
    weight bytes over the bandwidth is the remote model's; one of the
    hedge's length is not."""
    toy.write(tmp_path)
    cfg = spec.config("toy", tmp_path / "toy.json")
    peak = counts.peaks("TPU v5 lite")
    floor = counts.least_weight_bytes(cfg.remote) / peak["hbm_bytes_per_s"]
    hedge = counts.least_weight_bytes(cfg.hedge) / peak["hbm_bytes_per_s"]
    assert counts.least_weight_bytes(cfg.remote) == counts.step_weight_bytes(cfg.remote)
    assert 0 < hedge < floor
    trace = SimpleNamespace(runs=lambda fn: {f"{fn}#1": [floor, hedge]},
                            run_spans=lambda fn: [(0.0, floor), (1.0, 1.0 + hedge)])
    view = SimpleNamespace(cfg=cfg, peak=peak, trace=trace)
    assert readings.served_runs(view, "decode_fn") == [floor]
    stamps = [(floor, "remote"), (1.0 + hedge, "hedge")]
    assert readings.matched_runs(view, "decode_fn", stamps) == [(floor, ["remote"])]

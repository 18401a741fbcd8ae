"""Find a cell's files by name and read them.

``BENCHMARK.json`` names each cell's configuration and traffic mix;
``configs/<name>.json`` and ``traffic/<name>.json`` hold them, and
``metrics/<name>.py`` reads each per-layer metric.  Nothing here imports
the program under test.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config_file(bench: dict, name: str) -> pathlib.Path:
    for c in bench["configs"]:
        if c["name"] == name:
            return ROOT / c["file"]
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "traffic" / f"{name}.json"


def metric_file(name: str) -> pathlib.Path:
    return BENCH_DIR / "metrics" / f"{name}.py"


@dataclasses.dataclass(frozen=True)
class Model:
    """The shapes of one served model, read from its published config.

    Only what the reference, the weight recipe and the operation counts
    need.  ``kind`` is the block family: ``phi3`` (pre-norm attention plus
    a SwiGLU MLP) or ``gemma`` (the same with embeddings scaled by
    sqrt(hidden), RMSNorm weights taken as 1 + w, a tanh-GELU gated MLP and
    a head tied to the embeddings).
    """

    name: str
    kind: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab_size: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: str
    sliding_window: Optional[int] = None

    @property
    def emb_scale(self) -> bool:
        return self.kind == "gemma"

    @property
    def norm_offset(self) -> bool:
        return self.kind == "gemma"

    @property
    def mlp(self) -> str:
        return "geglu" if self.kind == "gemma" else "swiglu"

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float32": 4}[self.dtype]


# The block families the reference knows, by model_type, with their MLP
# activation.
_ACTS = {"phi3": "silu", "gemma": "gelu_pytorch_tanh"}


def model(name: str, hf: dict) -> Model:
    """A :class:`Model` from the keys of a published ``config.json``."""
    mt = hf["model_type"]
    if mt not in _ACTS:
        raise ValueError(f"model_type {mt!r} has no reference here")
    act = hf.get("hidden_act", hf.get("hidden_activation"))
    if act != _ACTS[mt]:
        raise ValueError(f"{name}: hidden_act {act!r}, expected {_ACTS[mt]!r}")
    if hf.get("rope_scaling") or hf.get("attention_bias"):
        raise ValueError(f"{name}: rope scaling and attention biases have no reference here")
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return Model(
        name=name,
        kind=mt,
        n_layers=hf["num_hidden_layers"],
        d_model=d,
        n_heads=h,
        n_kv_heads=hf.get("num_key_value_heads", h),
        head_dim=hf.get("head_dim") or d // h,
        d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", mt == "gemma")),
        dtype=hf["torch_dtype"],
        sliding_window=hf.get("sliding_window"),
    )


@dataclasses.dataclass(frozen=True)
class Config:
    """One served configuration: the remote model, the hedge and how they
    are served (``serving`` is handed to ``build_engine``)."""

    name: str
    remote: Model
    remote_quality: float
    hedge: Model
    hedge_quality: float
    serving: dict
    correct: dict


def config(name: str, path) -> Config:
    raw = load_json(path)
    if raw["name"] != name:
        raise ValueError(f"{path}: names {raw['name']!r}, expected {name!r}")
    return Config(
        name=name,
        remote=model(name, raw["config"]),
        remote_quality=float(raw["quality"]["score"]),
        hedge=model(raw["hedge"]["name"], raw["hedge"]["config"]),
        hedge_quality=float(raw["hedge"]["quality"]["score"]),
        serving=raw["serving"],
        correct=raw["correct"],
    )

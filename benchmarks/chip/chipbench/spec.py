"""Find a cell's files by name and read them.

``BENCHMARK.json`` names each cell's configuration and traffic mix;
``configs/<name>.json`` and ``traffic/<name>.json`` hold them, and
``metrics/<name>.py`` reads each per-layer metric; ``chipbench/families/
<model_type>.py`` holds all that depends on a model's architecture.
Nothing here imports the program under test.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import re
import sys
from types import ModuleType
from typing import ClassVar, Optional

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

    ``kind`` is the published ``model_type``; it names the family module,
    ``chipbench/families/<kind>.py``, that parses the config and holds the
    model's weight layout, plain reference, operation counts and the
    program's ``ModelConfig``.  A family may subclass this with fields of
    its own; it stays frozen and hashable, since the reference is jitted
    with the model as a static argument.
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

    # RMSNorm weights taken as 1 + w, so that the weight recipe draws them
    # as zeros; a family sets it for all of its models.
    norm_offset: ClassVar[bool] = False

    @property
    def dtype_bytes(self) -> int:
        return {"bfloat16": 2, "float32": 4}[self.dtype]


def family_file(model_type: str) -> pathlib.Path:
    return BENCH_DIR / "chipbench" / "families" / f"{model_type}.py"


def family(model_type: str) -> ModuleType:
    """The family module of a published ``model_type``, loaded once from
    :func:`family_file`; an unknown ``model_type`` is refused."""
    path = family_file(model_type)
    if not re.fullmatch(r"[a-z0-9][a-z0-9_-]*", model_type) or not path.is_file():
        raise ValueError(f"model_type {model_type!r} has no reference here: "
                         f"{path} would define it")
    name = f"chipbench.families.{model_type}"
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != str(path):
        modspec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(modspec)
        sys.modules[name] = mod
        modspec.loader.exec_module(mod)
    return mod


def model(name: str, hf: dict) -> Model:
    """A :class:`Model` from the keys of a published ``config.json``,
    parsed and checked by the family its ``model_type`` names."""
    return family(hf["model_type"]).model(name, hf)


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

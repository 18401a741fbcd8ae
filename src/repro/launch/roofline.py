"""Roofline-term derivation from compiled artifacts.

Terms per (arch, shape, mesh), in seconds (v5e constants):

    compute    = HLO_FLOPs / (chips * 197e12)
    memory     = HLO_bytes / (chips * 819e9)
    collective = collective_bytes / (chips * 50e9)

Methodology notes (verified empirically in this repo):
  * ``compiled.cost_analysis()`` counts a ``while`` (scan) body ONCE, so the
    full scanned-step compile cannot give total FLOPs.  We therefore compile
    *cost components* — one block per distinct layer kind (fwd or fwd+bwd,
    attention unrolled), the 0-layer ends (embed + final norm + loss/logits),
    and the optimizer update — and combine them weighted by layer counts.
    The full-step compile remains the memory/sharding/collective-schedule
    proof artifact.
  * HLO_FLOPs/bytes from cost_analysis are *global* (all devices); dividing
    by the chip count gives per-chip work assuming perfect balance, which the
    sharding rules guarantee up to GSPMD padding (visible in the
    MODEL_FLOPS/HLO ratio).
  * collective_bytes sums the result-shape bytes of every all-gather /
    all-reduce / reduce-scatter / all-to-all / collective-permute in the
    post-SPMD HLO (per-device shapes), scaled by the same component weights.
    Dividing by link bandwidth approximates one-hop cost — a lower bound for
    multi-hop rings, stated as such in EXPERIMENTS.md.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

__all__ = [
    "HW",
    "collective_bytes",
    "cost_terms",
    "CellReport",
    "combine_components",
]

HW = {
    "peak_flops": 197e12,  # bf16/chip
    "hbm_bw": 819e9,
    "ici_bw": 50e9,
}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(\w[\w\d]*)\[([\d,]*)\]\{?[^}]*\}?\s*"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_TUPLE_COLL_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
)
_SHAPE_RE = re.compile(r"(\w[\w\d]*)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes per collective kind from post-SPMD HLO text."""
    out: Dict[str, int] = {}
    for m in _COLL_RE.finditer(hlo_text):
        dtype, dims, kind = m.groups()
        out[kind] = out.get(kind, 0) + _shape_bytes(dtype, dims)
    for m in _TUPLE_COLL_RE.finditer(hlo_text):
        shapes, kind = m.groups()
        total = sum(
            _shape_bytes(d, s) for d, s in _SHAPE_RE.findall(shapes)
        )
        out[kind] = out.get(kind, 0) + total
    return out


@dataclasses.dataclass
class Component:
    """One compiled cost component with its multiplier."""

    name: str
    flops: float
    bytes_accessed: float
    coll_bytes: Dict[str, int]
    multiplier: float = 1.0


def component_from_compiled(name: str, compiled, multiplier: float = 1.0) -> Component:
    ca = compiled.cost_analysis()
    return Component(
        name=name,
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=collective_bytes(compiled.as_text()),
        multiplier=multiplier,
    )


def combine_components(components) -> Dict[str, float]:
    flops = sum(c.flops * c.multiplier for c in components)
    byts = sum(c.bytes_accessed * c.multiplier for c in components)
    coll = 0.0
    coll_by_kind: Dict[str, float] = {}
    for c in components:
        for k, v in c.coll_bytes.items():
            coll_by_kind[k] = coll_by_kind.get(k, 0.0) + v * c.multiplier
            coll += v * c.multiplier
    return {"flops": flops, "bytes": byts, "coll_bytes": coll,
            "coll_by_kind": coll_by_kind}


def cost_terms(totals: Dict[str, float], chips: int) -> Dict[str, float]:
    """The three roofline terms in seconds.

    cost_analysis flops/bytes are already per-device (post-SPMD module), but
    we treat them as the per-chip stream directly; collective bytes are
    per-device link traffic.
    """
    return {
        "compute_s": totals["flops"] / HW["peak_flops"],
        "memory_s": totals["bytes"] / HW["hbm_bw"],
        "collective_s": totals["coll_bytes"] / HW["ici_bw"],
    }


@dataclasses.dataclass
class CellReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    terms_s: Dict[str, float]
    totals: Dict[str, float]
    model_flops: float
    bytes_per_device: Optional[int]
    coll_census: Dict[str, int]  # full-step compile: op kind -> count
    status: str = "ok"
    note: str = ""

    @property
    def dominant(self) -> str:
        return max(self.terms_s, key=lambda k: self.terms_s[k])

    @property
    def useful_ratio(self) -> float:
        hlo = self.totals["flops"] * self.chips
        return self.model_flops / hlo if hlo else 0.0

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["dominant"] = self.dominant
        d["model_flops_over_hlo"] = self.useful_ratio
        return d

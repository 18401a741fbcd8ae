"""Operations and bytes that the served work needs, from the shapes alone.

These count what the algorithm requires, not what the program happens to
execute: matrix products at two operations per multiply-add, causal
attention over the real positions only, the weights that a program run's
tokens reach read once per run (an embedding table only as far as it is
looked up), and the keys and values of real positions only (no padded
rows, no inactive decode slots, no trash page).  Embedding lookups, norms
and activations are left out: they are below 1% of the operations and
bytes at the served widths.  What depends on the architecture comes from
the model's family (``chipbench/families/``).
"""
from __future__ import annotations

from chipbench import spec
from chipbench.spec import Model
from chipbench.weights import nbytes as weight_bytes  # noqa: F401


def peaks(device_kind: str) -> dict:
    """The table row of ``device_kind``; an unknown device is an error."""
    table = spec.load_json(spec.BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in peaks.json")
    return table[device_kind]


def _family(m: Model):
    return spec.family(m.kind)


def step_weight_bytes(m: Model) -> int:
    """Weight bytes of a forward run that reaches every weight: all of
    them, except that an untied embedding table gives only the rows of the
    tokens looked up (left out, like the other lookups)."""
    table = 0 if m.tie_embeddings else m.vocab_size * m.d_model * m.dtype_bytes
    return weight_bytes(m) - table


def least_weight_bytes(m: Model) -> int:
    """The least weight bytes any one run of ``m`` reads (all of
    :func:`step_weight_bytes` where every run reads every weight)."""
    return _family(m).least_weight_bytes(m)


def kv_bytes_per_token(m: Model) -> int:
    return _family(m).kv_bytes_per_token(m)


def prefill_flops(m: Model, tokens: int) -> int:
    """One causal prompt of ``tokens`` positions."""
    f = _family(m)
    return tokens * f.token_flops(m) + f.key_flops(m) * (tokens * (tokens + 1) // 2)


def decode_flops(m: Model, position: int) -> int:
    """One generated token whose query sits at ``position`` (0-based)."""
    f = _family(m)
    return f.token_flops(m) + f.key_flops(m) * (position + 1)


def prefill_bytes(m: Model, rows: int, tokens: int) -> int:
    """A prefill run of ``rows`` real prompts: the weights those tokens
    reach and the keys and values written for every real position."""
    return (_family(m).run_weight_bytes(m, rows * tokens)
            + rows * tokens * kv_bytes_per_token(m))


def decode_bytes(m: Model, positions) -> int:
    """A decode run whose active slots sit at ``positions``: the weights
    their tokens reach, each slot's keys and values read over its real
    positions and the new ones written."""
    positions = list(positions)
    kv = kv_bytes_per_token(m)
    return (_family(m).run_weight_bytes(m, len(positions))
            + sum((p + 1) * kv for p in positions))


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])

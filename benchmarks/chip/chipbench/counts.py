"""Operations and bytes that the served work needs, from the shapes alone.

These count what the algorithm requires, not what the program happens to
execute: matrix products at two operations per multiply-add, causal
attention over the real positions only, weights read once per program
run (an embedding table only as far as it is looked up), and the keys
and values of real positions only (no padded rows, no
inactive decode slots, no trash page).  Embedding lookups, norms and
activations are left out: they are below 1% of the operations and bytes
at the served widths.
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


def _dense_flops(m: Model) -> int:
    """Matrix-product operations per token, outside attention scores."""
    d, hd, nq, nkv, f = m.d_model, m.head_dim, m.n_heads, m.n_kv_heads, m.d_ff
    per_layer = 2 * d * (nq + 2 * nkv) * hd + 2 * nq * hd * d + 3 * 2 * d * f
    return m.n_layers * per_layer + 2 * d * m.vocab_size


def _attn_flops(m: Model, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` positions."""
    return m.n_layers * 4 * m.n_heads * m.head_dim * keys


def step_weight_bytes(m: Model) -> int:
    """Weight bytes one forward run reads: all of them, except that an
    untied embedding table gives only the rows of the tokens looked up
    (left out, like the other lookups)."""
    table = 0 if m.tie_embeddings else m.vocab_size * m.d_model * m.dtype_bytes
    return weight_bytes(m) - table


def kv_bytes_per_token(m: Model) -> int:
    return m.n_layers * 2 * m.n_kv_heads * m.head_dim * m.dtype_bytes


def prefill_flops(m: Model, tokens: int) -> int:
    """One causal prompt of ``tokens`` positions."""
    return tokens * _dense_flops(m) + _attn_flops(m, tokens * (tokens + 1) // 2)


def decode_flops(m: Model, position: int) -> int:
    """One generated token whose query sits at ``position`` (0-based)."""
    return _dense_flops(m) + _attn_flops(m, position + 1)


def prefill_bytes(m: Model, rows: int, tokens: int) -> int:
    """A prefill run of ``rows`` real prompts: the weights once and the
    keys and values written for every real position."""
    return step_weight_bytes(m) + rows * tokens * kv_bytes_per_token(m)


def decode_bytes(m: Model, positions) -> int:
    """A decode run whose active slots sit at ``positions``: the weights
    once, each slot's keys and values read over its real positions and
    the new ones written."""
    kv = kv_bytes_per_token(m)
    return step_weight_bytes(m) + sum((p + 1) * kv for p in positions)


def least_seconds(flops: float, bytes_: float, peak: dict) -> float:
    """The roofline: the larger of operations over peak and bytes over
    bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], bytes_ / peak["hbm_bytes_per_s"])

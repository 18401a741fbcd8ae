"""Shared layers: RMSNorm, rotary embeddings, dense MLPs, embedding tables."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.api import constrain

__all__ = [
    "rms_norm",
    "rope",
    "apply_rope",
    "mlp_init_spec",
    "mlp_apply",
    "dense_init",
    "truncated_normal_init",
]


def truncated_normal_init(key, shape, dtype, scale: float):
    """He-style truncated normal, stddev = scale / sqrt(fan_in).

    The fan-in is the second-to-last dim: a kernel is ``(..., in, out)``,
    whether it is one layer's ``(in, out)``, a per-expert ``(E, in, out)``
    or a scan-stacked ``(layers, in, out)``.
    """
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    std = scale / np.sqrt(max(fan_in, 1))
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape) * std).astype(dtype)


def dense_init(key, shape, dtype):
    return truncated_normal_init(key, shape, dtype, 1.0)


def rms_norm(x, weight, *, eps: float = 1e-6, offset: bool = False):
    """RMSNorm; ``offset=True`` uses the gemma (1 + w) parameterization."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    scale = (1.0 + weight.astype(jnp.float32)) if offset else weight.astype(jnp.float32)
    return (y * scale).astype(x.dtype)


def rope(positions, head_dim: int, theta: float):
    """Rotary position embedding tables.

    Args:
      positions: (..., S) int32 absolute positions.
      head_dim: must be even.
    Returns:
      (sin, cos) each (..., S, head_dim // 2) float32.
    """
    half = head_dim // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None] * freq  # (..., S, half)
    return jnp.sin(angle), jnp.cos(angle)


def apply_rope(x, sin, cos):
    """Rotate pairs. x: (B, S, N, HD); sin/cos: (B, S, half) or (S, half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if sin.ndim == x.ndim - 1:  # (B, S, half) -> broadcast over heads
        sin = sin[..., None, :]
        cos = cos[..., None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1
    )
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense MLP (SwiGLU / GeGLU / GELU).  Spec tables keep init + logical axes in
# one place so parameter trees and sharding specs cannot drift.
# ---------------------------------------------------------------------------
def mlp_init_spec(d_model: int, d_ff: int, mlp_type: str):
    """Returns {name: (shape, logical_axes)} for one MLP."""
    if mlp_type in ("swiglu", "geglu"):
        return {
            "wi": ((d_model, d_ff), ("embed", "ffn")),
            "wg": ((d_model, d_ff), ("embed", "ffn")),
            "wo": ((d_ff, d_model), ("ffn", "embed")),
        }
    if mlp_type == "gelu":
        return {
            "wi": ((d_model, d_ff), ("embed", "ffn")),
            "wo": ((d_ff, d_model), ("ffn", "embed")),
        }
    raise ValueError(f"unknown mlp_type {mlp_type!r}")


def mlp_apply(params, x, mlp_type: str):
    if mlp_type == "swiglu":
        h = jax.nn.silu(x @ params["wi"]) * (x @ params["wg"])
    elif mlp_type == "geglu":
        h = jax.nn.gelu(x @ params["wi"], approximate=True) * (x @ params["wg"])
    elif mlp_type == "gelu":
        h = jax.nn.gelu(x @ params["wi"], approximate=True)
    else:
        raise ValueError(mlp_type)
    h = constrain(h, "batch", "seq", "ffn")
    return h @ params["wo"]

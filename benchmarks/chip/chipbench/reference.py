"""The plain reference: the published forward pass in float32.

Straight ``jax.numpy``, no cache, no batching tricks and no kernels: each
layer normalizes, attends over the whole sequence under a causal mask and
runs its MLP, the way the model's paper and ``config.json`` describe it.
Each family module (``chipbench/families/<model_type>.py``) writes its
block from the helpers here; :func:`logits` runs it with matrix products
at ``highest`` precision, so float32 is float32 on a TPU too.  It imports
nothing of the program under test.

``quant`` gives the control: the same pass with both operands of every
matrix product rounded to the precision step below the served one:
``"fp8"`` (float8 e4m3, one scale per row of activations and per output
column of weights) below bfloat16, ``"bf16"`` below float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench import spec
from chipbench.spec import Model

_F8_MAX = 448.0  # largest finite float8_e4m3fn


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, quant):
    """``a @ b`` with ``a`` (..., K) activations and ``b`` (K, N) weights."""
    if quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, 0)
    elif quant == "bf16":
        a, b = _bf16(a), _bf16(b)
    return jnp.matmul(a, b, precision="highest")


def _rms(x, w, m: Model):
    w = (1.0 + w) if m.norm_offset else w
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + m.norm_eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * freq  # (B, T, half)
    sin, cos = jnp.sin(ang)[:, :, None], jnp.cos(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _causal_attention(q, k, v, m: Model, quant):
    """Softmax attention of ``q`` (B, T, nq, hd) over ``k`` and ``v``
    (B, T, nkv, hd), each query over the positions up to its own (and
    within ``m.sliding_window``, where the model has one), grouped heads
    sharing their key and value head.  Returns (B, T, nq * hd)."""
    B, T, nq, hd = q.shape
    k = jnp.repeat(k, nq // k.shape[2], axis=2)
    v = jnp.repeat(v, nq // v.shape[2], axis=2)
    if quant == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, 1)
    elif quant == "bf16":
        q, k, v = _bf16(q), _bf16(k), _bf16(v)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") * hd**-0.5
    qi, ki = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = ki <= qi
    if m.sliding_window:
        ok = ok & (qi - ki < m.sliding_window)
    s = jnp.where(ok, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    if quant == "fp8":
        p = _fp8(p, -1)
    elif quant == "bf16":
        p = _bf16(p)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")
    return o.reshape(B, T, nq * hd)


def logits(m: Model, w: dict, tokens, rows, cols, quant: str = "none"):
    """Reference logits of ``tokens`` (B, T) at positions ``(rows, cols)``,
    from the family of ``m``.

    Returns a (K, vocab) float32 array, one row per position."""
    with jax.default_matmul_precision("highest"):
        return spec.family(m.kind).logits(m, w, jnp.asarray(tokens), jnp.asarray(rows),
                                          jnp.asarray(cols), quant)

"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.attention import attention_reference as _attn_ref
from repro.models.attention import decode_attention as _decode_ref
from repro.models.attention import paged_decode_attention as _paged_decode_ref
from repro.models.layers import rms_norm as _rms_ref

__all__ = [
    "flash_attention_ref",
    "decode_attention_ref",
    "decode_attention_paged_ref",
    "rglru_scan_ref",
    "rms_norm_ref",
]


def flash_attention_ref(q, k, v, *, causal=True, window=0, scale=None):
    """q: (B, NQ, S, D); k, v: (B, NKV, S, D) — kernel layout."""
    out = _attn_ref(
        q.transpose(0, 2, 1, 3),
        k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=causal,
        window=window,
        scale=scale,
    )
    return out.transpose(0, 2, 1, 3)


def decode_attention_ref(q, k_cache, v_cache, slot_pos, pos, *, window=0, scale=None):
    """q: (B, NKV, G, D); caches: (B, NKV, S, D) — kernel layout."""
    B, NKV, G, D = q.shape
    out = _decode_ref(
        q.reshape(B, 1, NKV * G, D),
        k_cache.transpose(0, 2, 1, 3),
        v_cache.transpose(0, 2, 1, 3),
        slot_pos,
        pos,
        window=window,
        scale=scale,
    )
    return out.reshape(B, NKV, G, D)


def decode_attention_paged_ref(q, k_pool, v_pool, page_tables, pos, *,
                               window=0, scale=None):
    """q: (B, NKV, G, D); pools: (P, NKV, page, D) — kernel layout."""
    B, NKV, G, D = q.shape
    P, _, page, _ = k_pool.shape
    out = _paged_decode_ref(
        q.reshape(B, 1, NKV * G, D),
        k_pool.transpose(0, 2, 1, 3).reshape(P, page, NKV * D),
        v_pool.transpose(0, 2, 1, 3).reshape(P, page, NKV * D),
        page_tables,
        pos,
        window=window,
        scale=scale,
    )
    return out.reshape(B, NKV, G, D)


def rglru_scan_ref(a, b, h0):
    """h_t = a_t h_{t-1} + b_t via associative scan.  a, b: (B, S, W)."""
    af = a.astype(jnp.float32)
    bf = b.astype(jnp.float32)
    bf = bf.at[:, 0].add(af[:, 0] * h0.astype(jnp.float32))

    def combine(lhs, rhs):
        a1, b1 = lhs
        a2, b2 = rhs
        return a1 * a2, a2 * b1 + b2

    _, h = jax.lax.associative_scan(combine, (af, bf), axis=1)
    return h.astype(a.dtype)


def rms_norm_ref(x, w, *, eps=1e-6, offset=False):
    return _rms_ref(x, w, eps=eps, offset=offset)

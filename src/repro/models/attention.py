"""Chunked online-softmax ("flash") attention in pure JAX, with a custom VJP.

Why a custom VJP: the naive differentiation of an online-softmax scan saves
every per-step carry (the running (B,C,KV,G,HD) accumulator), which is
quadratic memory — we measured a 48-layer llama step ballooning to 157 GB of
temps.  The flash backward recomputes block probabilities from the saved
log-sum-exp instead, keeping attention memory O(S).

Layout: q is grouped as (B, S, KV, G, HD) so GQA never materializes repeated
K/V.  The outer loop over query chunks is a static Python loop (exact causal
FLOPs — no masked-out off-diagonal blocks are ever computed); the inner loop
over key chunks is a ``lax.scan``.

Masks: causal, sliding window (RecurrentGemma local attention), bidirectional
(HuBERT), and prefix-LM (PaliGemma — requires prefix length <= chunk so the
non-causal pairs stay inside the diagonal block; asserted).

This is also the reference algorithm for the Pallas TPU kernel in
``repro.kernels.flash_attention`` (same tiling, VMEM-resident accumulators).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.api import constrain

__all__ = [
    "flash_attention",
    "attention_reference",
    "decode_attention",
    "paged_decode_attention",
]

_NEG_INF = -1e30


def _block_mask(qpos, kpos, *, causal: bool, window: int, prefix_len):
    """(B?, C, C2) boolean mask of allowed attention pairs."""
    ok = jnp.ones((qpos.shape[0], kpos.shape[0]), bool)
    if causal:
        ok &= qpos[:, None] >= kpos[None, :]
    if window:
        ok &= (qpos[:, None] - kpos[None, :]) < window
    if prefix_len is not None:
        # Bidirectional visibility of/within the prefix region.
        ok = ok[None] | (kpos[None, None, :] < prefix_len[:, None, None])
    return ok  # (C, C2) or (B, C, C2)


def _expand_mask(ok):
    """-> broadcastable against scores (B, KV, G, C, C2)."""
    if ok.ndim == 2:
        return ok[None, None, None]
    return ok[:, None, None]  # batch-dependent (prefix-LM)


def _kv_chunk_range(qi: int, n_kv: int, chunk: int, *, causal: bool, window: int):
    """Static [start, end) of key chunks needed by query chunk ``qi``."""
    if not causal:
        return 0, n_kv
    end = qi + 1
    start = 0
    if window:
        start = max(0, (qi * chunk - window + 1) // chunk)
    return start, end


def _pick_chunk(s: int, chunk: int) -> int:
    if s <= chunk:
        return s
    if s % chunk == 0:
        return chunk
    # Largest divisor of s that is <= chunk (keeps odd lengths working).
    for c in range(chunk, 0, -1):
        if s % c == 0:
            return c
    return s


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------
def _flash_fwd_impl(q, k, v, prefix_len, causal, window, chunk, scale, unroll=False):
    B, Sq, KV, G, HD = q.shape
    Skv = k.shape[1]
    C = _pick_chunk(Sq, chunk)
    C2 = _pick_chunk(Skv, chunk)
    nq, nkv = Sq // C, Skv // C2

    outs, lses = [], []
    for qi in range(nq):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * C, C, axis=1)
        qpos_c = qi * C + jnp.arange(C)
        start, end = _kv_chunk_range(qi, nkv, C2, causal=causal, window=window)

        acc0 = jnp.zeros((B, C, KV, G, HD), jnp.float32)
        m0 = jnp.full((B, KV, G, C), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, C), jnp.float32)

        def body(carry, kj, qc=qc, qpos_c=qpos_c, C2=C2):
            acc, m, l = carry
            ks = jax.lax.dynamic_slice_in_dim(k, kj * C2, C2, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, kj * C2, C2, axis=1)
            kpos = kj * C2 + jnp.arange(C2)
            s = (
                jnp.einsum("bqkgd,bskd->bkgqs", qc, ks, preferred_element_type=jnp.float32)
                * scale
            )
            ok = _expand_mask(
                _block_mask(qpos_c, kpos, causal=causal, window=window, prefix_len=prefix_len)
            )
            s = jnp.where(ok, s, _NEG_INF)
            mn = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - mn[..., None])
            corr = jnp.exp(m - mn)
            l2 = l * corr + p.sum(-1)
            pv = jnp.einsum(
                "bkgqs,bskd->bqkgd", p.astype(v.dtype), vs, preferred_element_type=jnp.float32
            )
            acc2 = acc * jnp.moveaxis(corr, -1, 1)[..., None] + pv
            return (acc2, mn, l2), None

        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0), jnp.arange(start, end), unroll=unroll
        )
        out_c = acc / jnp.maximum(jnp.moveaxis(l, -1, 1), 1e-30)[..., None]
        outs.append(out_c.astype(q.dtype))
        lses.append(m + jnp.log(jnp.maximum(l, 1e-30)))  # (B, KV, G, C)

    out = jnp.concatenate(outs, axis=1)
    lse = jnp.concatenate(lses, axis=-1)  # (B, KV, G, Sq)
    return out, lse


# ---------------------------------------------------------------------------
# Backward (recompute-from-LSE, standard flash backward).
# ---------------------------------------------------------------------------
def _flash_bwd_impl(q, k, v, prefix_len, out, lse, dout, causal, window, chunk,
                    scale, unroll=False):
    B, Sq, KV, G, HD = q.shape
    Skv = k.shape[1]
    C = _pick_chunk(Sq, chunk)
    C2 = _pick_chunk(Skv, chunk)
    nq, nkv = Sq // C, Skv // C2

    # delta_i = sum_d dout_i * out_i  (per query position).
    delta = jnp.einsum(
        "bqkgd,bqkgd->bkgq", dout.astype(jnp.float32), out.astype(jnp.float32)
    )  # (B, KV, G, Sq)

    dk = jnp.zeros((B, Skv, KV, HD), jnp.float32)
    dv = jnp.zeros((B, Skv, KV, HD), jnp.float32)
    dqs = []
    for qi in range(nq):
        qc = jax.lax.dynamic_slice_in_dim(q, qi * C, C, axis=1)
        doc = jax.lax.dynamic_slice_in_dim(dout, qi * C, C, axis=1).astype(jnp.float32)
        lse_c = jax.lax.dynamic_slice_in_dim(lse, qi * C, C, axis=-1)
        delta_c = jax.lax.dynamic_slice_in_dim(delta, qi * C, C, axis=-1)
        qpos_c = qi * C + jnp.arange(C)
        start, end = _kv_chunk_range(qi, nkv, C2, causal=causal, window=window)

        dq0 = jnp.zeros((B, C, KV, G, HD), jnp.float32)

        def body(carry, kj, qc=qc, doc=doc, lse_c=lse_c, delta_c=delta_c, qpos_c=qpos_c, C2=C2):
            dq_c, dk_acc, dv_acc = carry
            ks = jax.lax.dynamic_slice_in_dim(k, kj * C2, C2, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, kj * C2, C2, axis=1)
            kpos = kj * C2 + jnp.arange(C2)
            s = (
                jnp.einsum("bqkgd,bskd->bkgqs", qc, ks, preferred_element_type=jnp.float32)
                * scale
            )
            ok = _expand_mask(
                _block_mask(qpos_c, kpos, causal=causal, window=window, prefix_len=prefix_len)
            )
            p = jnp.where(ok, jnp.exp(s - lse_c[..., None]), 0.0)  # (B,KV,G,C,C2)
            dv_c = jnp.einsum("bkgqs,bqkgd->bskd", p, doc)
            dp = jnp.einsum(
                "bqkgd,bskd->bkgqs", doc, vs.astype(jnp.float32)
            )
            ds = p * (dp - delta_c[..., None]) * scale
            dq_c = dq_c + jnp.einsum("bkgqs,bskd->bqkgd", ds, ks.astype(jnp.float32))
            dk_c = jnp.einsum("bkgqs,bqkgd->bskd", ds, qc.astype(jnp.float32))
            off = kj * C2
            dk_acc = jax.lax.dynamic_update_slice_in_dim(
                dk_acc, jax.lax.dynamic_slice_in_dim(dk_acc, off, C2, 1) + dk_c, off, 1
            )
            dv_acc = jax.lax.dynamic_update_slice_in_dim(
                dv_acc, jax.lax.dynamic_slice_in_dim(dv_acc, off, C2, 1) + dv_c, off, 1
            )
            return (dq_c, dk_acc, dv_acc), None

        (dq_c, dk, dv), _ = jax.lax.scan(
            body, (dq0, dk, dv), jnp.arange(start, end), unroll=unroll
        )
        dqs.append(dq_c.astype(q.dtype))

    dq = jnp.concatenate(dqs, axis=1)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# ---------------------------------------------------------------------------
# custom_vjp plumbing.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, prefix_len, causal, window, chunk, scale, unroll):
    out, _ = _flash_fwd_impl(q, k, v, prefix_len, causal, window, chunk, scale,
                             unroll=unroll)
    return out


def _flash_fwd(q, k, v, prefix_len, causal, window, chunk, scale, unroll):
    out, lse = _flash_fwd_impl(q, k, v, prefix_len, causal, window, chunk, scale,
                               unroll=unroll)
    return out, (q, k, v, prefix_len, out, lse)


def _flash_bwd(causal, window, chunk, scale, unroll, res, dout):
    q, k, v, prefix_len, out, lse = res
    dq, dk, dv = _flash_bwd_impl(
        q, k, v, prefix_len, out, lse, dout, causal, window, chunk, scale,
        unroll=unroll,
    )
    return dq, dk, dv, None


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q,
    k,
    v,
    *,
    causal: bool = True,
    window: int = 0,
    prefix_len: Optional[jax.Array] = None,
    chunk: int = 1024,
    scale: Optional[float] = None,
    unroll: bool = False,
):
    """Memory-efficient attention.

    Args:
      q: (B, Sq, NQ, HD); k, v: (B, Skv, NKV, HD) with NQ % NKV == 0.
      causal: causal masking (False => full bidirectional, encoder-style).
      window: sliding window size (0 = unlimited). Implies causal bounds.
      prefix_len: (B,) optional prefix-LM boundary; requires
        ``max(prefix_len) <= chunk`` (PaliGemma: 256 <= 1024).
      chunk: query/key chunk length (VMEM tile on TPU).
    Returns:
      (B, Sq, NQ, HD) in q.dtype.
    """
    B, Sq, NQ, HD = q.shape
    NKV = k.shape[2]
    G = NQ // NKV
    if scale is None:
        scale = HD**-0.5
    qg = q.reshape(B, Sq, NKV, G, HD)
    out = _flash(qg, k, v, prefix_len, causal, window, chunk, scale, unroll)
    return out.reshape(B, Sq, NQ, HD)


# ---------------------------------------------------------------------------
# Reference (naive, O(S^2) memory) — oracle for tests and tiny models.
# ---------------------------------------------------------------------------
def attention_reference(
    q, k, v, *, causal=True, window=0, prefix_len=None, scale=None
):
    B, Sq, NQ, HD = q.shape
    NKV = k.shape[2]
    Skv = k.shape[1]
    G = NQ // NKV
    if scale is None:
        scale = HD**-0.5
    qg = q.reshape(B, Sq, NKV, G, HD)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32) * scale
    ok = _block_mask(
        jnp.arange(Sq), jnp.arange(Skv), causal=causal, window=window, prefix_len=prefix_len
    )
    s = jnp.where(_expand_mask(ok), s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v.dtype), v)
    return out.reshape(B, Sq, NQ, HD).astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode-time attention: one query token against a (ring-buffer) KV cache.
# ---------------------------------------------------------------------------
def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window: int = 0, scale=None):
    """Single-step attention over a cache.

    Args:
      q: (B, 1, NQ, HD) query for the new token.
      k_cache, v_cache: (B, Scache, NKV, HD).
      slot_pos: (B, Scache) absolute position stored in each slot (-1 empty).
      pos: (B,) position of the query token.
      window: sliding window (0 = unlimited).
    """
    B, _, NQ, HD = q.shape
    NKV = k_cache.shape[2]
    G = NQ // NKV
    if scale is None:
        scale = HD**-0.5
    qg = q.reshape(B, 1, NKV, G, HD)
    s = (
        jnp.einsum("bqkgd,bskd->bkgqs", qg, k_cache, preferred_element_type=jnp.float32)
        * scale
    )  # (B, KV, G, 1, Scache)
    ok = (slot_pos >= 0) & (slot_pos <= pos[:, None])
    if window:
        ok &= slot_pos > (pos[:, None] - window)
    s = jnp.where(ok[:, None, None, None, :], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, 1, NQ, HD).astype(q.dtype)


def paged_decode_attention(
    q, k_pool, v_pool, page_tables, pos, *, layer=None, window: int = 0,
    scale=None
):
    """Single-step attention over a block-paged KV pool.

    The continuous-batching layout: instead of one contiguous cache per
    row, each row owns a *page table* into a shared physical pool.  Pages
    are append-only — the entry at a row's dense index ``i`` (page
    ``i // page``, offset ``i % page``) holds exactly absolute position
    ``i`` — so validity is just ``i <= pos`` and no stored slot-position
    array is needed.  Table entries past a row's reservation point at the
    trash page (0); their dense indices always exceed ``pos``, so the
    causal mask keeps them unread.

    Args:
      q: (B, 1, NQ, HD) query for the new token.
      k_pool, v_pool: (P, page, NKV*HD) heads-merged page pools, or
        (L, P, page, NKV*HD) stacked over layers with ``layer`` picking one.
      page_tables: (B, NB) int32 page ids per row.
      pos: (B,) absolute position of each row's query token.
      layer: index into a stacked pool's leading axis.  The pages are
        gathered at ``(layer, table)`` in one gather, so no layer is ever
        sliced out of the stack.
      window: sliding window (0 = unlimited).

    This is the runtime (pure-jnp) path; the Pallas TPU substrate with a
    table-indexed layout is ``repro.kernels.decode_attention.
    decode_attention_paged_fwd``.
    """
    HD = q.shape[-1]
    page, F = k_pool.shape[-2:]
    B, NB = page_tables.shape
    S = NB * page
    at = (page_tables,) if layer is None else (layer, page_tables)
    k_dense = k_pool[at].reshape(B, S, F // HD, HD)  # (B, S, NKV, HD)
    v_dense = v_pool[at].reshape(B, S, F // HD, HD)
    slot_pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None], (B, S))
    return decode_attention(
        q, k_dense, v_dense, slot_pos, pos, window=window, scale=scale
    )

"""Pallas TPU decode-attention kernel (one new token vs. a ring-buffer cache).

Decode attention is purely memory-bound: each step streams the whole KV
cache from HBM once and does O(S * D) FLOPs.  The kernel tiles the cache
sequence dimension; the grid is (batch, kv_heads, n_k_blocks) with the
k-block dimension sequential, and the (G, D) query group plus the online
softmax state live in VMEM — so each cache byte is read exactly once
(HBM-roofline optimal).

Mask semantics match ``repro.models.attention.decode_attention``: slots carry
absolute positions (ring buffers), masked by validity / causality / window.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["decode_attention_fwd", "decode_attention_paged_fwd"]

_NEG_INF = -1e30
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _kernel(pos_ref, q_ref, k_ref, v_ref, sp_ref, o_ref, acc_ref, m_ref, l_ref,
            *, scale: float, window: int, n_k_blocks: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (block_k, D)
    v = v_ref[0, 0].astype(jnp.float32)
    sp = sp_ref[0, 0]  # (block_k,) absolute positions (-1 = empty)
    pos = pos_ref[b]  # scalar query position (SMEM)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, block_k)
    ok = (sp >= 0) & (sp <= pos)
    if window:
        ok &= sp > (pos - window)
    s = jnp.where(ok[None, :], s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_fwd(
    q, k_cache, v_cache, slot_pos, pos, *,
    window: int = 0,
    scale=None,
    block_k: int = 512,
    interpret: bool = False,
):
    """q: (B, NKV, G, D); caches: (B, NKV, S, D); slot_pos: (B, S); pos: (B,).

    Returns (B, NKV, G, D).
    """
    B, NKV, G, D = q.shape
    S = k_cache.shape[2]
    if scale is None:
        scale = D**-0.5
    block_k = min(block_k, S)
    assert S % block_k == 0
    n_k = S // block_k

    kernel = functools.partial(
        _kernel, scale=scale, window=window, n_k_blocks=n_k
    )
    # ``pos`` is a scalar-prefetch operand (one SMEM scalar per row) and
    # ``slot_pos`` rides as (B, 1, S): Mosaic refuses a rank-1 (1,) block
    # and a (1, block_k) tile of a (B, S) array.
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, NKV, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ki, pos: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, pos: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, pos: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, ki, pos: (b, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ki, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NKV, G, D), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(pos.astype(jnp.int32), q, k_cache, v_cache, slot_pos.reshape(B, 1, S))


def _paged_kernel(tbl_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref,
                  *, scale: float, window: int, page: int, n_blocks: int):
    b = pl.program_id(0)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)  # (page, D)
    v = v_ref[0, 0].astype(jnp.float32)
    pos = pos_ref[b]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, page)
    # Append-only paged layout: dense index == absolute position, so the
    # validity mask is just causality.  Trash-padded table entries sit past
    # the slot's reservation (dense index > pos by construction) and are
    # masked here without any per-slot bookkeeping.
    sp = ki * page + jax.lax.broadcasted_iota(jnp.int32, (page,), 0)
    ok = sp <= pos
    if window:
        ok &= sp > (pos - window)
    s = jnp.where(ok[None, :], s, _NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_paged_fwd(
    q, k_pool, v_pool, page_tables, pos, *,
    window: int = 0,
    scale=None,
    interpret: bool = False,
):
    """Paged decode attention over a shared physical page pool.

    q: (B, NKV, G, D); pools: (P, NKV, page, D); page_tables: (B, NB) int32
    page ids into the pool; pos: (B,) per-row absolute positions.  Returns
    (B, NKV, G, D).

    The page tables ride in as *scalar-prefetch* operands
    (:class:`pltpu.PrefetchScalarGridSpec`), so the k/v block index maps can
    DMA exactly the pages each row owns — the kernel never materializes a
    gathered dense cache, and each row streams only ``NB * page`` entries
    regardless of pool size.
    """
    B, NKV, G, D = q.shape
    P, _, page, _ = k_pool.shape
    NB = page_tables.shape[1]
    if scale is None:
        scale = D**-0.5

    kernel = functools.partial(
        _paged_kernel, scale=scale, window=window, page=page, n_blocks=NB
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # page_tables, pos
        grid=(B, NKV, NB),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, ki, tbl, pos: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page, D),
                lambda b, h, ki, tbl, pos: (tbl[b, ki], h, 0, 0),
            ),
            pl.BlockSpec(
                (1, 1, page, D),
                lambda b, h, ki, tbl, pos: (tbl[b, ki], h, 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h, ki, tbl, pos: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, D), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, NKV, G, D), q.dtype),
        compiler_params=_PARAMS,
        interpret=interpret,
    )(page_tables.astype(jnp.int32), pos.astype(jnp.int32), q, k_pool, v_pool)
    return out

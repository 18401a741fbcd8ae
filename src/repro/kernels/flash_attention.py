"""Pallas TPU flash-attention (forward) kernel.

Tiling: grid = (batch * q_heads, n_q_blocks, n_k_blocks) with the k-block
dimension sequential ("arbitrary"); the (block_q, head_dim) accumulator, the
running max and the running sum live in VMEM scratch and persist across
k-blocks.  Causal/windowed pairs outside the band are skipped at block
granularity with ``pl.when`` (no wasted MXU work), matching the pure-JAX
implementation's exact-causal FLOPs.

GQA: K/V are laid out (B, KV, S, D) and indexed by ``q_head // group``, so
grouped queries never materialize repeated K/V in HBM or VMEM.

Block sizes default to (256, 512): VMEM footprint per step ~=
  q (256x128x2) + k,v (512x128x2x2) + acc (256x128x4) + p (256x512x4) ~= 1 MB,
comfortably under the ~16 MB/core budget, with MXU-aligned (>=128) tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_fwd"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int, block_q: int, block_k: int,
            n_k_blocks: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_lo = qi * block_q
    k_lo = ki * block_k
    needed = True
    if causal:
        needed = k_lo <= q_lo + block_q - 1
    if window:
        needed = needed & (k_lo + block_k - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # (block_q, d)
        k = k_ref[0, 0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (block_q, block_k)
        qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        ok = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            ok &= qpos >= kpos
        if window:
            ok &= (qpos - kpos) < window
        s = jnp.where(ok, s, _NEG_INF)

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
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_attention_fwd(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    scale=None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    return_lse: bool = False,
):
    """q: (B, NQ, S, D); k, v: (B, NKV, S, D) -> (B, NQ, S, D)
    (+ LSE (B, NQ, S) when ``return_lse``, for the backward kernels)."""
    B, NQ, S, D = q.shape
    NKV = k.shape[1]
    G = NQ // NKV
    if scale is None:
        scale = D**-0.5
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    n_q, n_k = S // block_q, S // block_k
    bh = B * NQ

    qr = q.reshape(bh, S, D)

    kernel = functools.partial(
        _kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_k_blocks=n_k,
    )
    grid = (bh, n_q, n_k)
    out, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, qi, ki, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0),
            ),
            pl.BlockSpec(
                (1, 1, block_k, D),
                lambda b, qi, ki, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0),
            ),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            # LSE rides as (bh, 1, S): a (1, block_q) tile of a (bh, S)
            # array is neither (8, 128)-aligned nor full, which Mosaic
            # refuses; a unit middle dim equals its full extent.
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, D), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, k, v)
    out = out.reshape(B, NQ, S, D)
    if return_lse:
        return out, lse.reshape(B, NQ, S)
    return out

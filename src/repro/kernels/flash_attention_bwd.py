"""Pallas TPU flash-attention backward kernels.

Standard two-kernel formulation (recompute-from-LSE, no O(S^2) residuals):

  * ``_dq_kernel``   — grid (B*NQ, n_q, n_k), k-blocks sequential: per
    q-block, accumulate dq += ds @ k with ds = p * (dp - delta) * scale.
  * ``_dkv_kernel``  — grid (B*NQ, n_k, n_q), q-blocks sequential: per
    k-block, accumulate dv += p^T @ do and dk += ds^T @ q.

GQA: both kernels run per *query* head (K/V indexed by ``q_head // group``);
dk/dv come out per-query-head and are summed over the group outside (a tiny
jnp reduction) — this keeps the grid race-free without atomics.

``delta = rowsum(dout * out)`` and the forward LSE are computed outside
(delta is one fused elementwise reduce; LSE comes from the forward kernel).
Causal/window block-skipping mirrors the forward kernel exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_bwd"]

_NEG_INF = -1e30
_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary")
)


def _masked_scores(q, k, q_lo, k_lo, scale, causal, window):
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    qpos = q_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    kpos = k_lo + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    ok = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        ok &= qpos >= kpos
    if window:
        ok &= (qpos - kpos) < window
    return jnp.where(ok, s, _NEG_INF)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_ref, *, scale, causal, window, block_q, block_k, n_k):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_lo, k_lo = qi * block_q, ki * block_k
    needed = True
    if causal:
        needed = k_lo <= q_lo + block_q - 1
    if window:
        needed = needed & (k_lo + block_k - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]  # (block_q,)
        delta = delta_ref[0, 0]
        s = _masked_scores(q, k, q_lo, k_lo, scale, causal, window)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *,
                scale, causal, window, block_q, block_k, n_q):
    ki = pl.program_id(1)
    qi = pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_lo, k_lo = qi * block_q, ki * block_k
    needed = True
    if causal:
        needed = k_lo <= q_lo + block_q - 1
    if window:
        needed = needed & (k_lo + block_k - 1 > q_lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        s = _masked_scores(q, k, q_lo, k_lo, scale, causal, window)
        p = jnp.exp(s - lse[:, None])  # (block_q, block_k)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(qi == n_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(
    q, k, v, out, dout, lse, *,
    causal: bool = True,
    window: int = 0,
    scale=None,
    block_q: int = 256,
    block_k: int = 256,
    interpret: bool = False,
):
    """Backward pass.  q/out/dout: (B, NQ, S, D); k, v: (B, NKV, S, D);
    lse: (B, NQ, S).  Returns (dq, dk, dv) in input layouts."""
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

    delta = jnp.einsum(
        "bhsd,bhsd->bhs", dout.astype(jnp.float32), out.astype(jnp.float32)
    ).reshape(bh, 1, S)
    qr = q.reshape(bh, S, D)
    dor = dout.reshape(bh, S, D)
    # Per-row statistics ride as (bh, 1, S) so their (1, 1, block_q) tiles
    # end in a full unit dim and a lane-aligned block, as Mosaic requires.
    lser = lse.reshape(bh, 1, S)

    common = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_k=block_k)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, n_k=n_k, **common),
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, qi, ki, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, qi, ki, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qr, k, v, dor, lser, delta)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_dkv_kernel, n_q=n_q, **common),
        grid=(bh, n_k, n_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, ki, qi, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, ki, qi, NQ=NQ, G=G: (b // NQ, (b % NQ) // G, ki, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, ki, qi: (b, qi, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
            pl.BlockSpec((1, 1, block_q), lambda b, ki, qi: (b, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, qi: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, S, D), k.dtype),
            jax.ShapeDtypeStruct((bh, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=_PARAMS,
        interpret=interpret,
    )(qr, k, v, dor, lser, delta)

    # Per-query-head dk/dv -> sum over the GQA group.
    dq = dq.reshape(B, NQ, S, D)
    dk = dk_h.reshape(B, NKV, G, S, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, NKV, G, S, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv

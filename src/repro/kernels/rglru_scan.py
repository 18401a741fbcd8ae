"""Pallas TPU kernel for the RG-LRU linear recurrence.

Griffin's own TPU implementation observes that the scan is *memory-bound*
(~6 FLOPs per element streamed), so the right TPU shape is: tile the width
dimension across the vector lanes, keep the hidden state resident in VMEM,
and walk the sequence dimension sequentially — each (a, bx) element is read
from HBM exactly once and h is written once, i.e. the kernel runs at HBM
bandwidth.  We adopt exactly that structure: grid = (B, n_width_blocks,
n_seq_blocks) with the sequence dimension "arbitrary" (sequential), and an
in-kernel ``fori_loop`` over 8-row tiles of the current block while the
carry lives in VMEM scratch.

(The pure-JAX path uses ``associative_scan`` — O(log S) depth but ~2x the
HBM traffic.)
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_fwd"]


def _kernel(a_ref, b_ref, h0_ref, o_ref, carry_ref, *, block_s: int, rows: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        carry_ref[...] = h0_ref[0].astype(jnp.float32)  # (1, block_w)

    # The sequence is walked ``rows`` at a time: each tile is loaded and
    # stored at a sublane-aligned offset (Mosaic cannot prove a single-row
    # offset aligned for packed dtypes), and its rows are stepped in a
    # static unroll.
    def tile(i, h):
        rs = pl.ds(pl.multiple_of(i * rows, rows), rows)
        a = a_ref[0, rs, :].astype(jnp.float32)  # (rows, block_w)
        b = b_ref[0, rs, :].astype(jnp.float32)
        hs = []
        for r in range(rows):
            h = a[r : r + 1] * h + b[r : r + 1]
            hs.append(h)
        o_ref[0, rs, :] = jnp.concatenate(hs, axis=0).astype(o_ref.dtype)
        return h

    carry_ref[...] = jax.lax.fori_loop(
        0, block_s // rows, tile, carry_ref[...]
    )


def rglru_scan_fwd(a, b, h0, *, block_s: int = 128, block_w: int = 512,
                   interpret: bool = False):
    """h_t = a_t * h_{t-1} + b_t.

    a, b: (B, S, W); h0: (B, W).  Returns h: (B, S, W).
    """
    B, S, W = a.shape
    block_s = min(block_s, S)
    block_w = min(block_w, W)
    assert S % block_s == 0 and W % block_w == 0
    n_s, n_w = S // block_s, W // block_w

    kernel = functools.partial(
        _kernel, block_s=block_s, rows=math.gcd(block_s, 8)
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, n_w, n_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_w), lambda b_, wi, si: (b_, si, wi)),
            pl.BlockSpec((1, block_s, block_w), lambda b_, wi, si: (b_, si, wi)),
            # h0 rides as (B, 1, W): a (1, block_w) tile of a (B, W) array
            # is neither (8, 128)-aligned nor full, which Mosaic refuses.
            pl.BlockSpec((1, 1, block_w), lambda b_, wi, si: (b_, 0, wi)),
        ],
        out_specs=pl.BlockSpec((1, block_s, block_w), lambda b_, wi, si: (b_, si, wi)),
        out_shape=jax.ShapeDtypeStruct((B, S, W), a.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(a, b, h0.reshape(B, 1, W))
    return out

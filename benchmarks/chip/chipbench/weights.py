"""The seeded weights that a configuration is served with.

The benchmark serves random weights drawn from ``--seed``.  This module is
the recipe that defines them, the way a checkpoint format defines a
model's weights: each weight is named by its path in the served
parameter tree, drawn from ``fold_in(key(seed), crc32(path))`` as a normal
truncated to two standard deviations, scaled by 1/sqrt(fan-in) (the
second-to-last dimension), and stored in the model's dtype; the layers of
the stack are one array with the layer first.  RMSNorm weights are ones
(zeros where the norm takes 1 + w) in float32.

The reference regenerates the weights from this recipe and never reads
the program's arrays; a program that served other weights fails the
comparison.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.spec import Model


def leaves(m: Model):
    """``(path, shape)`` of every weight of ``m``; stacked leaves carry the
    layer count first."""
    d, hd, nq, nkv = m.d_model, m.head_dim, m.n_heads, m.n_kv_heads
    L, f, V = m.n_layers, m.d_ff, m.vocab_size
    out = [(("embed", "tokens"), (V, d)), (("final_norm",), (d,))]
    if not m.tie_embeddings:
        out.append((("head",), (d, V)))
    block = [
        (("ln1",), (d,)),
        (("attn", "wq"), (d, nq * hd)),
        (("attn", "wk"), (d, nkv * hd)),
        (("attn", "wv"), (d, nkv * hd)),
        (("attn", "wo"), (nq * hd, d)),
        (("ln2",), (d,)),
        (("mlp", "wi"), (d, f)),
        (("mlp", "wg"), (d, f)),
        (("mlp", "wo"), (f, d)),
    ]
    out += [(("periods", "0") + p, (L,) + s) for p, s in block]
    return out


def _is_norm(name: str) -> bool:
    return name.startswith("ln") or name.endswith("_norm") or name == "final_norm"


def _draw(key, path, shape, m: Model):
    name = path[-1]
    if _is_norm(name):
        return jnp.full(shape, 0.0 if m.norm_offset else 1.0, jnp.float32)
    k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
    std = 1.0 / np.sqrt(max(shape[-2] if len(shape) >= 2 else shape[-1], 1))
    x = jax.random.truncated_normal(k, -2.0, 2.0, shape) * std
    return x.astype(jnp.dtype(m.dtype))


def make(m: Model, seed: int) -> dict:
    """Every weight of ``m`` for ``seed``, on the default device, in one
    jitted call: ``{"/".join(path): array}``."""

    @jax.jit
    def draw_all(key):
        return {"/".join(p): _draw(key, p, s, m) for p, s in leaves(m)}

    return draw_all(jax.random.key(seed))


def nbytes(m: Model) -> int:
    """Bytes of the served weights (norms in float32)."""
    total = 0
    for path, shape in leaves(m):
        size = int(np.prod(shape))
        total += size * (4 if _is_norm(path[-1]) else m.dtype_bytes)
    return total

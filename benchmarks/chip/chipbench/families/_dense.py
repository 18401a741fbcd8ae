"""The dense pre-norm decoder block that the ``phi3`` and ``gemma``
families share: RMSNorm, rotary multi-head (or grouped) attention, RMSNorm
and a gated MLP, in a stack of identical layers."""
from __future__ import annotations

import functools
from typing import ClassVar

import jax
import jax.numpy as jnp

from chipbench import counts, spec
from chipbench.reference import _causal_attention, _mm, _rms, _rope


class Dense(spec.Model):
    """A model of the dense block.  A family's subclass sets how its block
    differs: the activation its config names, the gated MLP, embeddings
    scaled by sqrt(hidden), RMSNorm weights taken as 1 + w, and whether the
    head is tied to the embeddings when the config does not say."""

    hidden_act: ClassVar[str]
    mlp: ClassVar[str]  # "swiglu", or "geglu" (a tanh-GELU gate)
    emb_scale: ClassVar[bool] = False
    tied_by_default: ClassVar[bool] = False


def parse(cls, name: str, hf: dict) -> Dense:
    """A ``cls`` from the keys of a published ``config.json``."""
    act = hf.get("hidden_act", hf.get("hidden_activation"))
    if act != cls.hidden_act:
        raise ValueError(f"{name}: hidden_act {act!r}, expected {cls.hidden_act!r}")
    if hf.get("rope_scaling") or hf.get("attention_bias"):
        raise ValueError(f"{name}: rope scaling and attention biases have no reference here")
    d, h = hf["hidden_size"], hf["num_attention_heads"]
    return cls(
        name=name,
        kind=hf["model_type"],
        n_layers=hf["num_hidden_layers"],
        d_model=d,
        n_heads=h,
        n_kv_heads=hf.get("num_key_value_heads", h),
        head_dim=hf.get("head_dim") or d // h,
        d_ff=hf["intermediate_size"],
        vocab_size=hf["vocab_size"],
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf["rms_norm_eps"]),
        tie_embeddings=bool(hf.get("tie_word_embeddings", cls.tied_by_default)),
        dtype=hf["torch_dtype"],
        sliding_window=hf.get("sliding_window"),
    )


def program_model(m: Dense):
    """The program's ``ModelConfig`` for ``m``."""
    from repro.models.config import ModelConfig

    return ModelConfig(
        name=m.name, family="dense", n_layers=m.n_layers, d_model=m.d_model,
        n_heads=m.n_heads, n_kv_heads=m.n_kv_heads, head_dim=m.head_dim,
        d_ff=m.d_ff, vocab_size=m.vocab_size, pattern=("attn",),
        mlp_type=m.mlp, rope_theta=m.rope_theta, norm_eps=m.norm_eps,
        tie_embeddings=m.tie_embeddings, emb_scale=m.emb_scale,
        norm_offset=m.norm_offset, dtype=m.dtype,
    )


def leaves(m: Dense):
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


def _attention(h, w, m: Dense, quant):
    B, T, _ = h.shape
    hd, nq, nkv = m.head_dim, m.n_heads, m.n_kv_heads
    q = _mm(h, w["attn/wq"], quant).reshape(B, T, nq, hd)
    k = _mm(h, w["attn/wk"], quant).reshape(B, T, nkv, hd)
    v = _mm(h, w["attn/wv"], quant).reshape(B, T, nkv, hd)
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    q, k = _rope(q, pos, m.rope_theta), _rope(k, pos, m.rope_theta)
    return _mm(_causal_attention(q, k, v, m, quant), w["attn/wo"], quant)


def _mlp(h, w, m: Dense, quant):
    gate = _mm(h, w["mlp/wi"], quant)
    up = _mm(h, w["mlp/wg"], quant)
    if m.mlp == "geglu":
        act = jax.nn.gelu(gate, approximate=True)
    else:
        act = jax.nn.silu(gate)
    return _mm(act * up, w["mlp/wo"], quant)


@functools.partial(jax.jit, static_argnums=(0, 5))
def logits(m: Dense, w: dict, tokens, rows, cols, quant):
    """The float32 forward pass of ``tokens`` (B, T); logits at ``(rows,
    cols)``."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    x = f32(w["embed/tokens"])[tokens]
    if m.emb_scale:
        x = x * jnp.sqrt(jnp.float32(m.d_model))
    stack = {k[len("periods/0/"):]: v for k, v in w.items() if k.startswith("periods/0/")}

    def layer(x, lw):
        lw = {k: f32(v) for k, v in lw.items()}
        x = x + _attention(_rms(x, lw["ln1"], m), lw, m, quant)
        x = x + _mlp(_rms(x, lw["ln2"], m), lw, m, quant)
        return x, None

    x, _ = jax.lax.scan(layer, x, stack)
    x = _rms(x[rows, cols], f32(w["final_norm"]), m)
    head = f32(w["embed/tokens"]).T if m.tie_embeddings else f32(w["head"])
    return _mm(x, head, quant)


def token_flops(m: Dense) -> int:
    """Matrix-product operations per token, outside attention scores: the
    four attention projections, the three MLP matrices and the head."""
    d, hd, nq, nkv, f = m.d_model, m.head_dim, m.n_heads, m.n_kv_heads, m.d_ff
    per_layer = 2 * d * (nq + 2 * nkv) * hd + 2 * nq * hd * d + 3 * 2 * d * f
    return m.n_layers * per_layer + 2 * d * m.vocab_size


def key_flops(m: Dense) -> int:
    """Scores and weighted values of one query, per key attended."""
    return m.n_layers * 4 * m.n_heads * m.head_dim


def kv_bytes_per_token(m: Dense) -> int:
    return m.n_layers * 2 * m.n_kv_heads * m.head_dim * m.dtype_bytes


def run_weight_bytes(m: Dense, tokens: int) -> int:
    """Every run reads every weight, however few tokens it carries."""
    return counts.step_weight_bytes(m)


def least_weight_bytes(m: Dense) -> int:
    return counts.step_weight_bytes(m)

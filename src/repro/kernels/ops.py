"""Jit'd dispatch wrappers: Pallas kernels on TPU, pure-JAX refs elsewhere.

The platform picks the path: where JAX runs on a TPU the Mosaic kernels
run, on any other backend the references do (identical math — the kernels
are checked against them in interpret mode by tests/test_kernels.py, and
compiled for v5e at real widths by tests/test_tpu_compile.py).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_fwd
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.rmsnorm import rms_norm_fwd

__all__ = [
    "on_tpu",
    "flash_attention",
    "decode_attention",
    "rglru_scan",
    "rms_norm",
]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "use_pallas"))
def flash_attention(q, k, v, *, causal=True, window=0, use_pallas=None):
    use = on_tpu() if use_pallas is None else use_pallas
    if use:
        return flash_attention_fwd(q, k, v, causal=causal, window=window)
    return ref.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("window", "use_pallas"))
def decode_attention(q, k_cache, v_cache, slot_pos, pos, *, window=0, use_pallas=None):
    use = on_tpu() if use_pallas is None else use_pallas
    if use:
        return decode_attention_fwd(q, k_cache, v_cache, slot_pos, pos, window=window)
    return ref.decode_attention_ref(q, k_cache, v_cache, slot_pos, pos, window=window)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def rglru_scan(a, b, h0, *, use_pallas=None):
    use = on_tpu() if use_pallas is None else use_pallas
    if use:
        return rglru_scan_fwd(a, b, h0)
    return ref.rglru_scan_ref(a, b, h0)


@functools.partial(jax.jit, static_argnames=("eps", "offset", "use_pallas"))
def rms_norm(x, w, *, eps=1e-6, offset=False, use_pallas=None):
    use = on_tpu() if use_pallas is None else use_pallas
    if use:
        return rms_norm_fwd(x, w, eps=eps, offset=offset)
    return ref.rms_norm_ref(x, w, eps=eps, offset=offset)

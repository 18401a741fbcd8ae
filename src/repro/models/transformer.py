"""Model assembly: heterogeneous block stacks, init, train/prefill/decode.

Parameters are built from *spec tables* — ``{name: (shape, logical_axes)}`` —
so the parameter tree and its sharding spec tree are generated from the same
source and cannot drift.  Layers are stacked per *pattern period* and run
under ``lax.scan`` (O(1) HLO size; remat per period during training).
Depths not divisible by the period length get an explicit unstacked epilogue.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.api import constrain
from repro.models import layers, moe, rglru, xlstm
from repro.models.attention import (
    decode_attention,
    flash_attention,
    paged_decode_attention,
)
from repro.models.config import ModelConfig

__all__ = [
    "init_params",
    "param_axes",
    "forward_hidden",
    "loss_fn",
    "init_cache",
    "prefill",
    "prefill_ragged",
    "decode_step",
    "init_paged_cache",
    "graft_prefill",
    "graft_prefill_batch",
    "paged_decode_step",
    "supports_paged_decode",
    "SeqContext",
]


# ---------------------------------------------------------------------------
# Spec tables.
# ---------------------------------------------------------------------------
def _attn_spec(cfg: ModelConfig):
    d, hd, nq, nkv = cfg.d_model, cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    spec = {
        "wq": ((d, nq * hd), ("embed", "heads")),
        "wk": ((d, nkv * hd), ("embed", "kv_heads")),
        "wv": ((d, nkv * hd), ("embed", "kv_heads")),
        "wo": ((nq * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        spec["q_norm"] = ((hd,), (None,))
        spec["k_norm"] = ((hd,), (None,))
    return spec


def block_spec(cfg: ModelConfig, kind: str):
    d = cfg.d_model
    ln = ((d,), ("embed",))
    if kind in ("attn", "local"):
        return {
            "ln1": ln,
            "attn": _attn_spec(cfg),
            "ln2": ln,
            "mlp": layers.mlp_init_spec(d, cfg.d_ff, cfg.mlp_type),
        }
    if kind == "moe":
        return {
            "ln1": ln,
            "attn": _attn_spec(cfg),
            "ln2": ln,
            "moe": moe.moe_init_spec(cfg),
        }
    if kind == "recurrent":
        return {
            "ln1": ln,
            "rec": rglru.rglru_init_spec(cfg),
            "ln2": ln,
            "mlp": layers.mlp_init_spec(d, cfg.d_ff, cfg.mlp_type),
        }
    if kind == "mlstm":
        return {"ln1": ln, "cell": xlstm.mlstm_init_spec(cfg)}
    if kind == "slstm":
        return {"ln1": ln, "cell": xlstm.slstm_init_spec(cfg)}
    raise ValueError(kind)


def model_spec(cfg: ModelConfig):
    d = cfg.d_model
    spec: Dict[str, Any] = {
        # Replicated over the tensor axis, FSDP on d_model: token gathers are
        # then fully local (a vocab-sharded table makes GSPMD replicate the
        # whole table inside the gather — measured on the multi-pod mesh).
        "embed": {"tokens": ((cfg.vocab_size, d), (None, "embed"))},
        "final_norm": ((d,), ("embed",)),
    }
    if not cfg.tie_embeddings:
        # Tiny classification vocabularies (HuBERT: 504) are replicated —
        # not divisible by the tensor axis, and too small to matter.
        v_ax = "vocab" if cfg.vocab_size >= 1024 else None
        spec["head"] = ((d, cfg.vocab_size), ("embed", v_ax))
    if cfg.frontend != "none":
        spec["frontend"] = {
            "proj": ((cfg.frontend_dim, d), (None, "embed")),
        }
    spec["periods"] = tuple(block_spec(cfg, k) for k in cfg.pattern)
    spec["epilogue"] = tuple(block_spec(cfg, k) for k in cfg.epilogue)
    return spec


# ---------------------------------------------------------------------------
# Init from spec.
# ---------------------------------------------------------------------------
def _init_leaf(key, name: str, shape, dtype, norm_offset: bool):
    if name.startswith("ln") or name.endswith("_norm") or name == "final_norm":
        fill = 0.0 if norm_offset else 1.0
        return jnp.full(shape, fill, dtype)
    if name == "lamb":  # RG-LRU decay: a ~ 0.95 at sigmoid midpoint
        return jnp.full(shape, 0.65, dtype)
    if name in ("bf",):  # forget-gate bias: remember by default
        return jnp.full(shape, 1.0, dtype)
    if name.endswith("_b") or name in ("bi", "bz", "bo") or name.startswith("b"):
        return jnp.zeros(shape, dtype)
    return layers.truncated_normal_init(key, shape, dtype, 1.0)


def _is_leaf_spec(node):
    return (
        isinstance(node, tuple)
        and len(node) == 2
        and isinstance(node[0], tuple)
        and all(isinstance(s, int) for s in node[0])
    )


def _walk_spec(spec, fn, path=()):  # fn(path, (shape, axes)) -> leaf value
    if _is_leaf_spec(spec):
        return fn(path, spec)
    if isinstance(spec, dict):
        return {k: _walk_spec(v, fn, path + (k,)) for k, v in spec.items()}
    if isinstance(spec, tuple):
        return tuple(_walk_spec(v, fn, path + (str(i),)) for i, v in enumerate(spec))
    raise TypeError(f"bad spec node at {path}: {type(spec)}")


def init_params(cfg: ModelConfig, key):
    dtype = jnp.dtype(cfg.dtype)
    n_p = cfg.n_periods

    def init(path, leaf):
        shape, _ = leaf
        name = path[-1]
        k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        stacked = path[0] == "periods"
        full_shape = (n_p, *shape) if stacked else shape
        ldtype = jnp.float32 if _fp32_leaf(name) else dtype
        return _init_leaf(k, name, full_shape, ldtype, cfg.norm_offset)

    return _walk_spec(model_spec(cfg), init)


def _fp32_leaf(name: str) -> bool:
    """Norms/gate biases/decays stay fp32 for stability."""
    return (
        name.startswith("ln")
        or name.endswith("_norm")
        or name == "final_norm"
        or name in ("lamb", "bi", "bf", "gate_a_b", "gate_x_b")
    )


def param_axes(cfg: ModelConfig):
    """Pytree matching init_params with logical-axis tuples as leaves."""

    def axes(path, leaf):
        _, ax = leaf
        if path[0] == "periods":
            return (None, *ax)  # stacking axis is unsharded
        return tuple(ax)

    return _walk_spec(model_spec(cfg), axes)


# ---------------------------------------------------------------------------
# Block application.
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SeqContext:
    positions: jax.Array  # (B, S) int32 absolute positions
    prefix_len: Optional[jax.Array] = None  # (B,) prefix-LM boundary
    decode: bool = False
    # Block-paged decode (continuous batching): per-row page tables into a
    # shared physical KV pool.  None => the dense ring-buffer cache path.
    page_tables: Optional[jax.Array] = None  # (B, NB) int32 page ids
    page_size: int = 0


def _norm(cfg, w, x):
    return layers.rms_norm(x, w, eps=cfg.norm_eps, offset=cfg.norm_offset)


def _kv_quant(x):
    """(…, HD) -> int8 values + per-(entry, head) scale."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(
        jnp.round(x.astype(jnp.float32) / scale[..., None]), -127, 127
    ).astype(jnp.int8)
    return q, scale


def _kv_dequant(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def _attention(cfg, p, x, ctx: SeqContext, kind: str, cache):
    B, S, _ = x.shape
    hd, nq, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    # Constrain the flattened head dim (always divisible by the tensor axis)
    # and let GSPMD propagate through the reshape — constraining the 4D
    # (B, S, H, HD) layout pads H up to the axis size for H < 16 archs.
    q = constrain(x @ p["wq"], "batch", "seq", "heads").reshape(B, S, nq, hd)
    k = constrain(x @ p["wk"], "batch", "seq", "kv_heads").reshape(B, S, nkv, hd)
    v = constrain(x @ p["wv"], "batch", "seq", "kv_heads").reshape(B, S, nkv, hd)
    if cfg.qk_norm:
        q = layers.rms_norm(q, p["q_norm"], eps=cfg.norm_eps)
        k = layers.rms_norm(k, p["k_norm"], eps=cfg.norm_eps)
    sin, cos = layers.rope(ctx.positions, hd, cfg.rope_theta)
    q = layers.apply_rope(q, sin, cos)
    k = layers.apply_rope(k, sin, cos)
    window = cfg.window if kind == "local" else 0

    if ctx.decode:
        assert cache is not None and S == 1
        # Flash-decode ("split-S") layout: q is tiny — replicate it across
        # the tensor axis and let every device attend over its *sequence*
        # shard of the cache; the output combine is a (B, NQ*HD) all-reduce
        # (KBs).  Keeping q head-sharded instead makes the einsum partition
        # by (padded) KV heads and gather the whole cache (250 MiB/layer
        # measured).
        q = constrain(q.reshape(B, S, -1), "batch", "seq", None).reshape(
            B, S, nq, hd
        )
        # Same for the new k/v: head-sharded single-token projections would
        # re-shard the whole cache on write (the head_dim all-gather below
        # was measured at 8 GiB/step).
        k = constrain(k.reshape(B, S, -1), "batch", "seq", None).reshape(
            B, S, nkv, hd
        )
        v = constrain(v.reshape(B, S, -1), "batch", "seq", None).reshape(
            B, S, nkv, hd
        )
        pos = ctx.positions[:, 0]  # (B,)
        if ctx.page_tables is not None:
            # Block-paged decode (continuous batching, `_run_paged_stack`):
            # rows advance at *independent* positions.  ``cache`` holds the
            # whole (stacked) pool and the ``layer`` to use; the B new rows
            # go in with one scatter at (layer, page, offset) and the pages
            # come out with one gather at (layer, table), so the pool is
            # updated in place.  The per-row scatter is fine here: the pool
            # is unsharded (the lockstep dynamic-update-slice below exists
            # for the seq-sharded multi-pod caches).  Inactive rows carry
            # pos=0 and an all-trash table, so their writes land in the
            # reserved trash page.
            page, tbl, layer = ctx.page_size, ctx.page_tables, cache["layer"]
            at = (tbl[jnp.arange(B), pos // page], pos % page)
            if layer is not None:
                at = (layer, *at)
            kp = cache["kp"].at[at].set(k.reshape(B, nkv * hd))
            vp = cache["vp"].at[at].set(v.reshape(B, nkv * hd))
            out = paged_decode_attention(
                q, kp, vp, tbl, pos, layer=layer, window=window
            )
            new_cache = {"kp": kp, "vp": vp}
            out = constrain(out.reshape(B, S, nq * hd), "batch", "seq", "heads")
            return out @ p["wo"], new_cache
        # Aligned decoding: all rows advance in lockstep (continuous batching
        # buckets by position at the engine layer), so the ring-buffer write
        # is one dynamic-update-slice at a shared slot — a per-row scatter
        # onto the seq-sharded cache makes GSPMD gather whole cache shards
        # (measured: 8 GiB/step of all-gather on llama3 decode_32k).
        slot = pos[0] % cache["k"].shape[1]
        if cfg.kv_cache_quant:
            kq, ks = _kv_quant(k)
            vq, vs = _kv_quant(v)
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, slot, axis=1)
            kss = jax.lax.dynamic_update_slice_in_dim(cache["k_scale"], ks, slot, axis=1)
            vss = jax.lax.dynamic_update_slice_in_dim(cache["v_scale"], vs, slot, axis=1)
            sp = jax.lax.dynamic_update_slice_in_dim(
                cache["slot_pos"], pos[:, None], slot, axis=1
            )
            out = decode_attention(
                q,
                _kv_dequant(kc, kss, k.dtype),
                _kv_dequant(vc, vss, v.dtype),
                sp, pos, window=window,
            )
            new_cache = {"k": kc, "v": vc, "slot_pos": sp,
                         "k_scale": kss, "v_scale": vss}
        else:
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
            sp = jax.lax.dynamic_update_slice_in_dim(
                cache["slot_pos"], pos[:, None], slot, axis=1
            )
            out = decode_attention(q, kc, vc, sp, pos, window=window)
            new_cache = {"k": kc, "v": vc, "slot_pos": sp}
    else:
        out = flash_attention(
            q,
            k,
            v,
            causal=cfg.causal,
            window=window,
            prefix_len=ctx.prefix_len,
            chunk=cfg.attn_chunk,
            unroll=cfg.unroll_scans,
        )
        new_cache = None
        if cache is not None:
            # Prefill cache write.  Prompt positions are static (0..S-1), so
            # the ring-buffer write is one or two STATIC slice updates — a
            # dynamic scatter here trips GSPMD's full-replication fallback
            # (measured: +50 GiB/device on 32k prefill cells).
            sc = cache["k"].shape[1]
            keep = min(S, sc)
            start = S - keep  # first kept prompt position
            slot0 = start % sc
            kc, vc, sp = cache["k"], cache["v"], cache["slot_pos"]
            if cfg.kv_cache_quant:
                kw, ksw = _kv_quant(k)
                vw, vsw = _kv_quant(v)
                kss, vss = cache["k_scale"], cache["v_scale"]
            else:
                kw, vw = k, v
            pos_tail = ctx.positions[:, start:]  # (B, keep)
            first = min(keep, sc - slot0)
            kc = kc.at[:, slot0 : slot0 + first].set(kw[:, start : start + first])
            vc = vc.at[:, slot0 : slot0 + first].set(vw[:, start : start + first])
            sp = sp.at[:, slot0 : slot0 + first].set(pos_tail[:, :first])
            if cfg.kv_cache_quant:
                kss = kss.at[:, slot0 : slot0 + first].set(ksw[:, start : start + first])
                vss = vss.at[:, slot0 : slot0 + first].set(vsw[:, start : start + first])
            if keep > first:  # wrapped remainder
                rest = keep - first
                kc = kc.at[:, :rest].set(kw[:, start + first :])
                vc = vc.at[:, :rest].set(vw[:, start + first :])
                sp = sp.at[:, :rest].set(pos_tail[:, first:])
                if cfg.kv_cache_quant:
                    kss = kss.at[:, :rest].set(ksw[:, start + first :])
                    vss = vss.at[:, :rest].set(vsw[:, start + first :])
            new_cache = {"k": kc, "v": vc, "slot_pos": sp}
            if cfg.kv_cache_quant:
                new_cache.update({"k_scale": kss, "v_scale": vss})

    out = constrain(out.reshape(B, S, nq * hd), "batch", "seq", "heads")
    return out @ p["wo"], new_cache


def apply_block(cfg, kind: str, p, x, ctx: SeqContext, cache):
    """Returns (x, new_cache, aux_loss)."""
    aux = jnp.float32(0.0)
    if kind in ("attn", "local", "moe"):
        h, attn_cache = _attention(cfg, p["attn"], _norm(cfg, p["ln1"], x), ctx, kind, cache)
        x = x + h
        h2 = _norm(cfg, p["ln2"], x)
        if kind == "moe":
            y, aux = moe.moe_apply(cfg, p["moe"], h2)
        else:
            y = layers.mlp_apply(p["mlp"], h2, cfg.mlp_type)
        x = x + y
        return x, attn_cache, aux
    if kind == "recurrent":
        h = _norm(cfg, p["ln1"], x)
        if ctx.decode:
            y, new_cache = rglru.rglru_decode_step(cfg, p["rec"], h, cache)
        else:
            h0 = cache["h"] if cache is not None else None
            tail = cache["conv_tail"] if cache is not None else None
            y, (h_last, new_tail) = rglru.rglru_apply(cfg, p["rec"], h, h0=h0, conv_tail=tail)
            new_cache = {"h": h_last, "conv_tail": new_tail} if cache is not None else None
        x = x + y
        y2 = layers.mlp_apply(p["mlp"], _norm(cfg, p["ln2"], x), cfg.mlp_type)
        return x + y2, new_cache, aux
    if kind == "mlstm":
        h = _norm(cfg, p["ln1"], x)
        if ctx.decode:
            y, new_cache = xlstm.mlstm_decode_step(cfg, p["cell"], h, cache)
        else:
            carry = (cache["C"], cache["n"]) if cache is not None else None
            y, (C, n) = xlstm.mlstm_apply(cfg, p["cell"], h, carry=carry)
            new_cache = {"C": C, "n": n} if cache is not None else None
        return x + y, new_cache, aux
    if kind == "slstm":
        h = _norm(cfg, p["ln1"], x)
        if ctx.decode:
            y, new_cache = xlstm.slstm_decode_step(cfg, p["cell"], h, cache)
        else:
            y, state = xlstm.slstm_apply(cfg, p["cell"], h, state=cache)
            new_cache = state if cache is not None else None
        return x + y, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Caches.
# ---------------------------------------------------------------------------
def _block_cache(cfg, kind, batch, max_len, dtype):
    if kind in ("attn", "moe", "local"):
        sc = max_len if kind != "local" else min(cfg.window, max_len)
        kv_dt = jnp.int8 if cfg.kv_cache_quant else dtype
        cache = {
            "k": jnp.zeros((batch, sc, cfg.n_kv_heads, cfg.head_dim), kv_dt),
            "v": jnp.zeros((batch, sc, cfg.n_kv_heads, cfg.head_dim), kv_dt),
            "slot_pos": jnp.full((batch, sc), -1, jnp.int32),
        }
        if cfg.kv_cache_quant:
            cache["k_scale"] = jnp.zeros((batch, sc, cfg.n_kv_heads), jnp.float32)
            cache["v_scale"] = jnp.zeros((batch, sc, cfg.n_kv_heads), jnp.float32)
        return cache
    if kind == "recurrent":
        return rglru.rglru_init_cache(cfg, batch, dtype)
    if kind == "mlstm":
        return xlstm.mlstm_init_cache(cfg, batch)
    if kind == "slstm":
        return xlstm.slstm_init_cache(cfg, batch)
    raise ValueError(kind)


def _stack_cache(cache, n):
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (n, *a.shape)).copy(), cache)


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    periods = tuple(
        _stack_cache(_block_cache(cfg, k, batch, max_len, dtype), cfg.n_periods)
        for k in cfg.pattern
    )
    epilogue = tuple(
        _block_cache(cfg, k, batch, max_len, dtype) for k in cfg.epilogue
    )
    return {"periods": periods, "epilogue": epilogue}


def _block_cache_axes(cfg, kind, stacked: bool):
    """Logical axes per block-kind cache (mirrors _block_cache).

    KV caches shard their *sequence* dim on the tensor axis ("seq_kv") —
    with GQA/MQA there are fewer KV heads than tensor shards, and the cache
    (not the weights) dominates decode memory, so sequence-sharding the
    cache is what makes decode_32k/long_500k fit.
    """
    pre = (None,) if stacked else ()
    if kind in ("attn", "moe", "local"):
        ax = {
            "k": pre + ("batch", "seq_kv", None, None),
            "v": pre + ("batch", "seq_kv", None, None),
            "slot_pos": pre + ("batch", "seq_kv"),
        }
        if cfg.kv_cache_quant:
            ax["k_scale"] = pre + ("batch", "seq_kv", None)
            ax["v_scale"] = pre + ("batch", "seq_kv", None)
        return ax
    if kind == "recurrent":
        return {
            "h": pre + ("batch", "lru"),
            "conv_tail": pre + ("batch", None, "lru"),
        }
    if kind == "mlstm":
        return {
            "C": pre + ("batch", None, None, None),
            "n": pre + ("batch", None, None),
        }
    if kind == "slstm":
        return {k: pre + ("batch", "lru") for k in ("c", "n", "h", "m")}
    raise ValueError(kind)


def cache_axes(cfg: ModelConfig):
    """Logical axes for the cache pytree (for sharding at the launcher)."""
    return {
        "periods": tuple(
            _block_cache_axes(cfg, k, stacked=True) for k in cfg.pattern
        ),
        "epilogue": tuple(
            _block_cache_axes(cfg, k, stacked=False) for k in cfg.epilogue
        ),
    }


# ---------------------------------------------------------------------------
# Forward passes.
# ---------------------------------------------------------------------------
def _embed_inputs(cfg, params, batch_inputs):
    """-> (x (B,S,D), positions (B,S), prefix_len or None, labels or None)."""
    dtype = jnp.dtype(cfg.dtype)
    if cfg.frontend == "audio":
        frames = batch_inputs["frames"]  # (B, S, frontend_dim)
        x = (frames.astype(dtype) @ params["frontend"]["proj"]).astype(dtype)
        B, S = x.shape[:2]
        pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        return x, pos, None, batch_inputs.get("labels")
    tokens = batch_inputs["tokens"]
    x = jnp.take(params["embed"]["tokens"], tokens, axis=0).astype(dtype)
    if cfg.emb_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), dtype)
    prefix_len = None
    if cfg.frontend == "vision" and "patches" in batch_inputs:  # absent at decode
        patches = batch_inputs["patches"]  # (B, P, frontend_dim)
        pe = (patches.astype(dtype) @ params["frontend"]["proj"]).astype(dtype)
        if cfg.emb_scale:
            pe = pe * jnp.asarray(np.sqrt(cfg.d_model), dtype)
        x = jnp.concatenate([pe, x], axis=1)
        P = patches.shape[1]
        prefix_len = jnp.full((x.shape[0],), P, jnp.int32)
    B, S = x.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    return x, pos, prefix_len, batch_inputs.get("labels")


def _run_stack(cfg, params, x, ctx: SeqContext, cache=None, collect_cache=False):
    """Scan over periods (+ epilogue).  Returns (x, new_cache, aux)."""
    n_p = cfg.n_periods
    use_cache = cache is not None

    def period_fn(x, period_params, period_caches):
        aux = jnp.float32(0.0)
        new_caches = []
        for i, kind in enumerate(cfg.pattern):
            c = period_caches[i] if use_cache else None
            x, nc, a = apply_block(cfg, kind, period_params[i], x, ctx, c)
            aux = aux + a
            new_caches.append(nc)
        if not ctx.decode:
            # Sequence-parallel boundary: no-op under the baseline rules
            # (seq_act -> None); under RULES_*_SP shards the residual stream
            # (and the scan carry) over the tensor axis.
            x = constrain(x, "batch", "seq_act", None)
        else:
            # Weight-stationary decode boundary (RULES_*_DEC): no-op under
            # the baseline.
            x = constrain(x, "batch", None, "embed_act")
        return x, tuple(new_caches), aux

    if cfg.remat and not ctx.decode and not use_cache:
        period_fn = jax.checkpoint(period_fn)

    if cfg.scan_layers and n_p > 0:
        def body(carry, xs):
            x, aux = carry
            pp = xs[0]
            pc = xs[1] if use_cache else None
            x, ncs, a = period_fn(x, pp, pc)
            ys = ncs if (use_cache or collect_cache) else None
            return (x, aux + a), ys

        xs = (params["periods"], cache["periods"]) if use_cache else (params["periods"], None)
        (x, aux), ys = jax.lax.scan(body, (x, jnp.float32(0.0)), xs)
        new_periods = ys
    else:
        aux = jnp.float32(0.0)
        new_periods_list = []
        for li in range(n_p):
            pp = jax.tree.map(lambda a: a[li], params["periods"])
            pc = jax.tree.map(lambda a: a[li], cache["periods"]) if use_cache else None
            x, ncs, a = period_fn(x, pp, pc)
            aux = aux + a
            new_periods_list.append(ncs)
        if use_cache and n_p > 0:
            new_periods = jax.tree.map(
                lambda *xs: jnp.stack(xs), *new_periods_list
            )
        else:
            new_periods = None

    new_epilogue = []
    for i, kind in enumerate(cfg.epilogue):
        c = cache["epilogue"][i] if use_cache else None
        x, nc, a = apply_block(cfg, kind, params["epilogue"][i], x, ctx, c)
        aux = aux + a
        new_epilogue.append(nc)

    new_cache = (
        {"periods": new_periods, "epilogue": tuple(new_epilogue)} if use_cache else None
    )
    return x, new_cache, aux


def forward_hidden(cfg, params, batch_inputs, cache=None, decode=False, positions=None):
    x, pos, prefix_len, _ = _embed_inputs(cfg, params, batch_inputs)
    if positions is not None:
        pos = positions
    ctx = SeqContext(positions=pos, prefix_len=prefix_len, decode=decode)
    x = constrain(x, "batch", "seq_act" if not decode else "seq", None)
    x, new_cache, aux = _run_stack(cfg, params, x, ctx, cache=cache)
    x = _norm(cfg, params["final_norm"], x)
    return x, new_cache, aux


def _head_weight(cfg, params):
    """(D, V) head weight, re-sharded once: vocab-TP, embed gathered.

    Gathering the head tile beats letting GSPMD all-reduce full (B, S, V)
    logits (measured: 12 GB/device/step of avoidable all-reduce on
    256k-vocab archs).  Callers hoist this out of the loss chunk loop.
    """
    w = params["embed"]["tokens"].T if cfg.tie_embeddings else params["head"]
    return constrain(w, None, "vocab" if cfg.vocab_size >= 1024 else None)


def _unembed(cfg, params, x, w=None):
    if w is None:
        w = _head_weight(cfg, params)
    logits = (x @ w.astype(x.dtype)).astype(jnp.float32)
    return constrain(logits, "batch", "seq", "vocab")


def loss_fn(cfg, params, batch):
    """Chunked softmax-xent.  batch: inputs dict with 'labels' (B, S_out).

    labels < 0 are ignored (prefix/padding).  Returns (loss, metrics).
    """
    x, _, aux = forward_hidden(cfg, params, batch)
    labels = batch["labels"]
    B, S = labels.shape
    x = x[:, -S:]  # align (vision prefix may extend the hidden sequence)
    C = min(cfg.loss_chunk, S)
    while S % C:
        C -= 1
    nc = S // C

    w_head = _head_weight(cfg, params)

    def body(carry, i):
        tot, cnt = carry
        xs = jax.lax.dynamic_slice_in_dim(x, i * C, C, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * C, C, axis=1)
        logits = _unembed(cfg, params, xs, w_head)
        lp = jax.nn.log_softmax(logits, axis=-1)
        valid = ls >= 0
        nll = -jnp.take_along_axis(lp, jnp.maximum(ls, 0)[..., None], axis=-1)[..., 0]
        tot = tot + jnp.sum(nll * valid)
        cnt = cnt + jnp.sum(valid)
        return (tot, cnt), None

    (tot, cnt), _ = jax.lax.scan(
        body, (jnp.float32(0.0), jnp.float32(0.0)), jnp.arange(nc),
        unroll=cfg.unroll_scans,
    )
    xent = tot / jnp.maximum(cnt, 1.0)
    loss = xent + 0.01 * aux
    return loss, {"xent": xent, "aux": aux, "tokens": cnt}


def prefill(cfg, params, batch_inputs, max_len: int):
    """Run the prompt, returning (cache, last-position logits)."""
    tokens_like = batch_inputs.get("tokens", batch_inputs.get("frames"))
    B = tokens_like.shape[0]
    cache = init_cache(cfg, B, max_len)
    x, cache, _ = forward_hidden(cfg, params, batch_inputs, cache=cache)
    logits = _unembed(cfg, params, x[:, -1:])
    return cache, logits[:, 0]


def decode_step(cfg, params, cache, token, pos):
    """One decode step.  token: (B,) int32; pos: (B,) int32 positions."""
    inputs = {"tokens": token[:, None]}
    x, cache, _ = forward_hidden(
        cfg, params, inputs, cache=cache, decode=True, positions=pos[:, None]
    )
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# Block-paged decode (continuous batching).
# ---------------------------------------------------------------------------
def supports_paged_decode(cfg: ModelConfig) -> bool:
    """Whether the paged continuous-batching path can serve this config.

    Requires an attention-only causal stack without KV-cache quantization:
    recurrent/xLSTM states are not paged, and the paged layout stores
    full-precision K/V (the continuous tier's pools are small).
    """
    kinds = tuple(cfg.pattern) + tuple(cfg.epilogue)
    return (
        cfg.causal
        and not cfg.kv_cache_quant
        and all(k in ("attn", "local", "moe") for k in kinds)
    )


def prefill_ragged(cfg, params, batch_inputs, lengths, max_len: int):
    """Prefill a right-padded batch with per-row prompt lengths.

    Fixed-shape companion to :func:`prefill`: ``tokens`` is always
    ``(B, max_len)`` wide (right-padded), ``lengths`` gives each row's real
    prompt length, and the returned logits are gathered at each row's last
    *real* position.  Causal masking makes right-padding inert — position
    ``L-1`` never attends positions ``>= L`` — so the logits equal the
    unpadded prefill's.  Cache entries past a row's length hold pad-token
    garbage; the paged graft relies on the append-only mask (and subsequent
    decode writes) to keep it unread.
    """
    tokens = batch_inputs["tokens"]
    B, S = tokens.shape
    cache = init_cache(cfg, B, max_len)
    x, cache, _ = forward_hidden(cfg, params, batch_inputs, cache=cache)
    idx = jnp.clip(lengths - 1, 0, S - 1)
    x_last = x[jnp.arange(B), idx][:, None]  # (B, 1, D)
    logits = _unembed(cfg, params, x_last)[:, 0]
    return cache, logits


def _paged_block_cache(cfg, kind, n_pages, page_size, dtype):
    if kind not in ("attn", "moe", "local"):
        raise ValueError(
            f"paged decode supports attention blocks only, got {kind!r}"
        )
    # Heads merged into the minor axis (phi3-mini: 3072 = 24 lanes): the
    # chip then tiles the pool rows-minor, and a row is written in place.
    shape = (n_pages, page_size, cfg.n_kv_heads * cfg.head_dim)
    return {"kp": jnp.zeros(shape, dtype), "vp": jnp.zeros(shape, dtype)}


def init_paged_cache(cfg: ModelConfig, n_pages: int, page_size: int):
    """Physical KV page pools for every attention layer (no batch dim —
    rows share the pool through their page tables): ``(L, P, page,
    NKV*HD)`` per block of the period, ``(P, page, NKV*HD)`` per epilogue
    block."""
    if not supports_paged_decode(cfg):
        raise ValueError(
            f"config {cfg.name!r} cannot use the paged decode path "
            "(needs a causal attention-only stack without kv quant)"
        )
    dtype = jnp.dtype(cfg.dtype)
    periods = tuple(
        _stack_cache(
            _paged_block_cache(cfg, k, n_pages, page_size, dtype), cfg.n_periods
        )
        for k in cfg.pattern
    )
    epilogue = tuple(
        _paged_block_cache(cfg, k, n_pages, page_size, dtype)
        for k in cfg.epilogue
    )
    return {"periods": periods, "epilogue": epilogue}


def graft_prefill(cfg, paged_cache, prefill_cache, row, page_table, page_size: int):
    """Copy one prefilled row's KV state into its slot's pages.

    ``prefill_cache`` comes from :func:`prefill_ragged` over a cache of
    exactly the prompt width ``W`` (positions ``0..W-1``, no ring wrap, so
    dense index == absolute position).  All ``W`` positions are scattered
    through the page table: positions past the row's reservation land in
    the trash page, positions between the row's real length and ``W`` are
    pad garbage that decode overwrites in place before the mask ever
    exposes them.  Fixed shapes throughout — one compile per prefill batch
    size.  The one-row case of :func:`graft_prefill_batch`.
    """
    rows = jnp.asarray(row)[None]
    one = {
        "periods": jax.tree.map(
            lambda a: jnp.take(a, rows, axis=1), prefill_cache["periods"]
        ),
        "epilogue": jax.tree.map(
            lambda a: jnp.take(a, rows, axis=0), prefill_cache["epilogue"]
        ),
    }
    return graft_prefill_batch(
        cfg, paged_cache, one, jnp.asarray(page_table)[None], page_size
    )


def graft_prefill_batch(cfg, paged_cache, prefill_cache, page_tables,
                        page_size: int):
    """Copy *every* prefilled row's KV state into its slot's pages at once.

    Batched companion to :func:`graft_prefill`: ``page_tables`` is
    ``(B, NB)`` int32 — one table per prefill row — and all ``B * W``
    positions scatter in a single operation, so joining a chunk costs one
    dispatch instead of one per row.  Padded ladder rows carry an all-trash
    table: their writes collapse into the reserved trash page (overlapping
    writes there are harmless — nothing masked-in ever reads it).
    """
    idx = jnp.arange(prefill_cache_width(prefill_cache))
    flat_idx = (
        page_tables[:, idx // page_size] * page_size + idx % page_size
    ).reshape(-1)  # (B*W,) flat pool positions

    def graft_leaves(pool_leaf, pre_leaf):
        # pool: (*lead, P, page, NKV*HD); pre: (*lead, B, W, NKV, HD)
        *lead, P, page, F = pool_leaf.shape
        src = pre_leaf.reshape(*lead, -1, F)  # (*lead, B*W, NKV*HD)
        flat = pool_leaf.reshape(*lead, P * page, F)
        if lead:
            flat = flat.at[:, flat_idx].set(src)
        else:
            flat = flat.at[flat_idx].set(src)
        return flat.reshape(pool_leaf.shape)

    new_periods = tuple(
        {
            "kp": graft_leaves(pc["kp"], pf["k"]),
            "vp": graft_leaves(pc["vp"], pf["v"]),
        }
        for pc, pf in zip(paged_cache["periods"], prefill_cache["periods"])
    )
    new_epilogue = tuple(
        {
            "kp": graft_leaves(pc["kp"], pf["k"]),
            "vp": graft_leaves(pc["vp"], pf["v"]),
        }
        for pc, pf in zip(paged_cache["epilogue"], prefill_cache["epilogue"])
    )
    return {"periods": new_periods, "epilogue": new_epilogue}


def prefill_cache_width(prefill_cache) -> int:
    """Sequence width of a dense prefill cache (its ring length)."""
    for group in (prefill_cache["periods"], prefill_cache["epilogue"]):
        for layer in group:
            if "k" in layer:
                return layer["k"].shape[-3]
    raise ValueError("prefill cache has no attention layers")


def _run_paged_stack(cfg, params, x, ctx: SeqContext, pool):
    """The paged decode's layer loop.  Returns (x, new_pool).

    The whole pool rides in the carry of the scan over periods, with the
    period index in its ``xs``: each attention block scatters its rows into
    the carried pool and gathers its pages straight from it (see the paged
    branch of :func:`_attention`), so under a donated pool the step updates
    it in place.  :func:`_run_stack` instead passes caches through the
    scan's ``xs``/``ys``, which slices every layer out of the stack and
    restacks it: right for the dense ring caches (sharded per row over
    devices), a copy of the whole pool per step here.
    """

    def block(kind, p, x, layer_pool, layer):
        cache = dict(layer_pool, layer=layer)
        x, layer_pool, _ = apply_block(cfg, kind, p, x, ctx, cache)
        return x, layer_pool

    def period(carry, xs):
        x, pools = carry
        period_params, layer = xs
        new_pools = []
        for kind, p, layer_pool in zip(cfg.pattern, period_params, pools):
            x, layer_pool = block(kind, p, x, layer_pool, layer)
            new_pools.append(layer_pool)
        # Weight-stationary decode boundary, as in _run_stack.
        x = constrain(x, "batch", None, "embed_act")
        return (x, tuple(new_pools)), None

    periods = pool["periods"]
    if cfg.n_periods > 0:
        (x, periods), _ = jax.lax.scan(
            period, (x, periods),
            (params["periods"], jnp.arange(cfg.n_periods)),
        )
    epilogue = []
    for kind, p, layer_pool in zip(cfg.epilogue, params["epilogue"],
                                   pool["epilogue"]):
        x, layer_pool = block(kind, p, x, layer_pool, None)
        epilogue.append(layer_pool)
    return x, {"periods": periods, "epilogue": tuple(epilogue)}


def paged_decode_step(cfg, params, paged_cache, page_tables, token, pos,
                      page_size: int):
    """One decode step over the shared page pool.

    token/pos: (B,) int32 — per-row positions (rows need *not* be in
    lockstep; that is the point).  ``page_tables``: (B, NB) int32.
    Inactive rows should carry pos=0 and an all-trash table.  Returns
    (logits, new pool); donate the pool to update it in place.
    """
    x, _, _, _ = _embed_inputs(cfg, params, {"tokens": token[:, None]})
    ctx = SeqContext(positions=pos[:, None], decode=True,
                     page_tables=page_tables, page_size=page_size)
    x = constrain(x, "batch", "seq", None)
    x, new_cache = _run_paged_stack(cfg, params, x, ctx, paged_cache)
    x = _norm(cfg, params["final_norm"], x)
    logits = _unembed(cfg, params, x)[:, 0]
    return logits, new_cache

"""Ahead-of-time compiles for a described TPU v5e, at published widths.

Nothing here runs on a chip: the TPU compiler, which is installed with JAX,
compiles each program for device 0 of a described ``v5e:2x2`` topology.
That refuses what interpret-mode kernel tests cannot see — block shapes not
aligned to the (8, 128) tiling, unsupported in-kernel ops, programs that do
not fit the chip's memory.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers must all collect the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.mdinference_zoo import ServingGeometry
from repro.kernels.decode_attention import (
    decode_attention_fwd,
    decode_attention_paged_fwd,
)
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention_bwd import flash_attention_bwd
from repro.kernels.rglru_scan import rglru_scan_fwd
from repro.kernels.rmsnorm import rms_norm_fwd
from repro.models import transformer as T
from repro.serving.backend import continuous_step_programs

# One v5e chip's HBM.
HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # The TPU library otherwise writes its logs under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of these tests.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree,
    )


def _live_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes
        + m.output_size_in_bytes
        - m.alias_size_in_bytes
        + m.temp_size_in_bytes
    )


# ---------------------------------------------------------------------------
# The continuous tier's step programs: phi3-mini-3.8b at published widths
# (32 layers, d=3072, 32 heads of 96, d_ff 8192, vocab 32064, bf16) in the
# geometry the chip smoke serves.
# ---------------------------------------------------------------------------
GEOMETRY = ServingGeometry(
    max_len=584, prompt_width=512, max_steps=128, n_slots=8, page_size=8,
    bs_ladder=(1, 2, 4, 8),
)


@pytest.fixture(scope="module")
def phi3(one_chip):
    cfg = get_config("phi3-mini-3.8b")
    g = GEOMETRY
    params = _on(
        one_chip,
        jax.eval_shape(lambda k: T.init_params(cfg, k), jax.random.key(0)),
    )
    pool = _on(
        one_chip,
        jax.eval_shape(
            lambda: T.init_paged_cache(cfg, g.total_pages, g.page_size)
        ),
    )
    prefill, graft, decode = continuous_step_programs(cfg, g)
    return cfg, params, pool, prefill, graft, decode


def test_continuous_prefill_compiles_at_batch_8(one_chip, phi3):
    cfg, params, _, prefill, _, _ = phi3
    n, g = max(GEOMETRY.bs_ladder), GEOMETRY
    compiled = prefill.lower(
        params,
        _spec(one_chip, (n, g.prompt_width), jnp.int32),
        _spec(one_chip, (n,), jnp.int32),
    ).compile()
    assert _live_bytes(compiled) < HBM_BYTES


def test_continuous_graft_compiles_at_batch_8(one_chip, phi3):
    cfg, params, pool, prefill, graft, _ = phi3
    n, g = max(GEOMETRY.bs_ladder), GEOMETRY
    tokens = _spec(one_chip, (n, g.prompt_width), jnp.int32)
    lengths = _spec(one_chip, (n,), jnp.int32)
    pcache = _on(
        one_chip,
        jax.eval_shape(lambda p, t, l: prefill(p, t, l)[0], params, tokens, lengths),
    )
    compiled = graft.lower(
        pool, pcache, _spec(one_chip, (n, g.pages_per_slot), jnp.int32)
    ).compile()
    m = compiled.memory_analysis()
    # The pool is donated: the grafted pool reuses its input's buffers.
    pool_bytes = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(pool)
    )
    assert m.alias_size_in_bytes >= pool_bytes
    weights = sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
    assert weights + _live_bytes(compiled) < HBM_BYTES


def test_continuous_decode_compiles(one_chip, phi3):
    _, params, pool, _, _, decode = phi3
    g = GEOMETRY
    slots = _spec(one_chip, (g.n_slots,), jnp.int32)
    compiled = decode.lower(
        params, pool,
        _spec(one_chip, (g.n_slots, g.pages_per_slot), jnp.int32),
        slots, slots,
    ).compile()
    assert _live_bytes(compiled) < HBM_BYTES


# ---------------------------------------------------------------------------
# Pallas kernels at real head, width and window sizes.
# ---------------------------------------------------------------------------
def _compile_kernel(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_paged_decode_kernel_compiles(one_chip):
    g = GEOMETRY
    n = g.n_slots
    _compile_kernel(
        decode_attention_paged_fwd,
        _spec(one_chip, (n, 32, 1, 96)),
        _spec(one_chip, (g.total_pages, 32, g.page_size, 96)),
        _spec(one_chip, (g.total_pages, 32, g.page_size, 96)),
        _spec(one_chip, (n, g.pages_per_slot), jnp.int32),
        _spec(one_chip, (n,), jnp.int32),
    )


def test_rms_norm_kernel_compiles(one_chip):
    _compile_kernel(
        rms_norm_fwd,
        _spec(one_chip, (8, 512, 3072)),
        _spec(one_chip, (3072,), jnp.float32),
    )


@pytest.mark.parametrize(
    "nq,nkv,seq,head_dim,window",
    [
        (32, 32, 512, 96, 0),  # phi3-mini prefill
        (10, 1, 4096, 256, 2048),  # recurrentgemma local attention
    ],
)
def test_flash_attention_fwd_kernel_compiles(one_chip, nq, nkv, seq, head_dim,
                                             window):
    _compile_kernel(
        lambda q, k, v: flash_attention_fwd(
            q, k, v, window=window, return_lse=True
        ),
        _spec(one_chip, (1, nq, seq, head_dim)),
        _spec(one_chip, (1, nkv, seq, head_dim)),
        _spec(one_chip, (1, nkv, seq, head_dim)),
    )


def test_flash_attention_bwd_kernel_compiles(one_chip):
    x = _spec(one_chip, (1, 32, 2048, 96))
    _compile_kernel(
        flash_attention_bwd, x, x, x, x, x,
        _spec(one_chip, (1, 32, 2048), jnp.float32),
    )


def test_decode_attention_kernel_compiles(one_chip):
    # recurrentgemma local attention: MQA, 10 query heads of 256, a ring
    # cache one 2048-token window long.
    b, seq = 8, 2048
    _compile_kernel(
        lambda q, k, v, sp, pos: decode_attention_fwd(
            q, k, v, sp, pos, window=seq
        ),
        _spec(one_chip, (b, 1, 10, 256)),
        _spec(one_chip, (b, 1, seq, 256)),
        _spec(one_chip, (b, 1, seq, 256)),
        _spec(one_chip, (b, seq), jnp.int32),
        _spec(one_chip, (b,), jnp.int32),
    )


def test_rglru_scan_kernel_compiles(one_chip):
    # recurrentgemma: lru_width 2560 over one 2048-token window.
    _compile_kernel(
        rglru_scan_fwd,
        _spec(one_chip, (1, 2048, 2560)),
        _spec(one_chip, (1, 2048, 2560)),
        _spec(one_chip, (1, 2560), jnp.float32),
    )

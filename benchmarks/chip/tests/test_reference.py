"""The weight recipe and the plain reference against the program, at
reduced widths on the CPU: the recipe reproduces the served weights bit
for bit, and the float32 reference agrees with the program's own float32
forward pass to rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, reference, spec, weights

PHI3 = spec.Model(name="phi3-toy", kind="phi3", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=4, head_dim=16, d_ff=128, vocab_size=300,
                  rope_theta=1e4, norm_eps=1e-5, tie_embeddings=False,
                  dtype="bfloat16", sliding_window=2047)
GEMMA = spec.Model(name="gemma-toy", kind="gemma", n_layers=1, d_model=32, n_heads=2,
                   n_kv_heads=1, head_dim=16, d_ff=128, vocab_size=256,
                   rope_theta=1e4, norm_eps=1e-6, tie_embeddings=True, dtype="float32")


@pytest.mark.parametrize("m", [PHI3, GEMMA], ids=lambda m: m.kind)
def test_recipe_reproduces_the_served_weights(m):
    from repro.models import transformer as T

    seed = 2**31 + 77
    served = jax.jit(T.init_params, static_argnums=0)(harness.program_model(m),
                                                      jax.random.key(seed))
    flat = {"/".join(str(k.key if hasattr(k, "key") else k.idx) for k in path): leaf
            for path, leaf in jax.tree_util.tree_leaves_with_path(served)}
    mine = weights.make(m, seed)
    assert sorted(flat) == sorted(mine)
    for name, leaf in flat.items():
        assert leaf.dtype == mine[name].dtype, name
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(mine[name]), name)


@pytest.mark.parametrize("m", [PHI3, GEMMA], ids=lambda m: m.kind)
def test_reference_matches_the_program_in_float32(m):
    from repro.models import transformer as T

    m32 = dataclasses.replace(m, dtype="float32")
    cfg = harness.program_model(m32)
    w = weights.make(m32, 5)
    params = jax.jit(T.init_params, static_argnums=0)(cfg, jax.random.key(5))
    tokens = np.random.default_rng(0).integers(0, m.vocab_size, (2, 24)).astype(np.int32)
    rows, cols = np.repeat([0, 1], 24), np.tile(np.arange(24), 2)
    ref = np.asarray(reference.logits(m32, w, tokens, rows, cols))
    with jax.default_matmul_precision("highest"):
        x, _, _ = T.forward_hidden(cfg, params, {"tokens": jnp.asarray(tokens)})
        prog = np.asarray(T._unembed(cfg, params, x)).reshape(-1, m.vocab_size)
    np.testing.assert_allclose(prog, ref, atol=2e-4 * np.abs(ref).max())


def test_control_is_coarser_than_the_reference():
    w = weights.make(PHI3, 1)
    tokens = np.random.default_rng(1).integers(0, 256, (2, 40)).astype(np.int32)
    rows, cols = np.repeat([0, 1], 40), np.tile(np.arange(40), 2)
    ref = np.asarray(reference.logits(PHI3, w, tokens, rows, cols))
    low = np.asarray(reference.logits(PHI3, w, tokens, rows, cols, "fp8"))
    err = np.abs(low - ref).max() / ref.std()
    assert 1e-3 < err < 1.0

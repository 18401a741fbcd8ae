"""The operation and byte counts against a count made by hand: a walk
over the matrix products of a forward pass at reduced widths that tallies
every multiply-add."""
import pytest

from chipbench import counts, weights
from chipbench.spec import Model

M = Model(name="tiny", kind="phi3", n_layers=3, d_model=8, n_heads=4, n_kv_heads=2,
          head_dim=2, d_ff=16, vocab_size=10, rope_theta=1e4, norm_eps=1e-5,
          tie_embeddings=False, dtype="bfloat16")


def macs_by_hand(m: Model, T: int, last_only: bool) -> int:
    """Multiply-adds of a causal forward over T tokens, product by product
    (only the last token's if asked)."""
    rows = 1 if last_only else T
    macs = 0

    def mm(a_rows, k, n):
        nonlocal macs
        macs += a_rows * k * n

    d, hd = m.d_model, m.head_dim
    for _ in range(m.n_layers):
        mm(rows, d, m.n_heads * hd)
        mm(rows, d, m.n_kv_heads * hd)
        mm(rows, d, m.n_kv_heads * hd)
        queries = [T - 1] if last_only else range(T)
        for q in queries:  # scores and values over keys 0..q, per head
            macs += m.n_heads * (q + 1) * hd * 2
        mm(rows, m.n_heads * hd, d)
        mm(rows, d, m.d_ff)
        mm(rows, d, m.d_ff)
        mm(rows, m.d_ff, d)
    mm(rows, d, m.vocab_size)
    return macs


@pytest.mark.parametrize("T", [1, 5, 12])
def test_prefill_flops_match_hand_count(T):
    assert counts.prefill_flops(M, T) == 2 * macs_by_hand(M, T, last_only=False)


@pytest.mark.parametrize("position", [0, 4, 11])
def test_decode_flops_match_hand_count(position):
    assert counts.decode_flops(M, position) == 2 * macs_by_hand(M, position + 1, True)


def test_bytes_match_hand_count():
    d, f, V, L = 8, 16, 10, 3
    per_layer = 2 * (d * 8 + d * 4 + d * 4 + 8 * d + 3 * d * f) + 4 * 2 * d
    w = 2 * (2 * V * d) + 4 * d + L * per_layer
    assert counts.weight_bytes(M) == w == weights.nbytes(M)
    kv = L * 2 * 2 * 2 * 2  # layers x (K, V) x kv heads x head_dim x bf16
    assert counts.kv_bytes_per_token(M) == kv
    step = w - 2 * V * d  # the untied embedding table is looked up, not read
    assert counts.step_weight_bytes(M) == step
    assert counts.decode_bytes(M, [3, 7]) == step + (4 + 8) * kv
    assert counts.prefill_bytes(M, 2, 5) == step + 2 * 5 * kv


def test_least_seconds_takes_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_seconds(1000, 50, peak) == 10.0
    assert counts.least_seconds(100, 50, peak) == 5.0


def test_unknown_device_is_an_error():
    assert counts.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        counts.peaks("cpu")

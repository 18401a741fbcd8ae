"""Phi-3 (``model_type: phi3``): the dense block with a SwiGLU MLP and a
head of its own.

Departure, inert at the served lengths: the sliding window (2047) is
applied as a mask, which no sequence of 576 tokens reaches.
"""
from chipbench.families import _dense
from chipbench.families._dense import (  # noqa: F401  the dense block's
    key_flops, kv_bytes_per_token, leaves, least_weight_bytes, logits, program_model,
    run_weight_bytes, token_flops)


class Model(_dense.Dense):
    hidden_act = "silu"
    mlp = "swiglu"


def model(name: str, hf: dict) -> Model:
    return _dense.parse(Model, name, hf)

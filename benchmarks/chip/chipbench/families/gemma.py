"""Gemma (``model_type: gemma``): the dense block with embeddings scaled by
sqrt(hidden), RMSNorm weights taken as 1 + w, a tanh-GELU gated MLP and a
head tied to the embeddings unless the config says otherwise."""
from chipbench.families import _dense
from chipbench.families._dense import (  # noqa: F401  the dense block's
    key_flops, kv_bytes_per_token, leaves, least_weight_bytes, logits, program_model,
    run_weight_bytes, token_flops)


class Model(_dense.Dense):
    hidden_act = "gelu_pytorch_tanh"
    mlp = "geglu"
    emb_scale = True
    norm_offset = True
    tied_by_default = True


def model(name: str, hf: dict) -> Model:
    return _dense.parse(Model, name, hf)

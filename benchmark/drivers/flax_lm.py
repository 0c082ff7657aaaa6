"""The program's ``TransformerLM`` at a configuration file's sizes, and the
two-way map between the benchmark's weight layout (benchmark/traffic.py) and
the flax variables the program trains."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def build_module(model: dict):
    from fedml_tpu.models.transformer import TransformerConfig, TransformerLM

    dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[model["compute_dtype"]]
    return TransformerLM(TransformerConfig(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"],
        n_heads=model["num_attention_heads"], n_layers=model["num_hidden_layers"],
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        dropout=0.0, dtype=dtype, remat=False))


@jax.jit
def to_program(weights: dict) -> dict:
    params = {"embed": {"embedding": weights["embed"]},
              "final_norm": {"scale": weights["final_norm"]},
              "lm_head": {"kernel": weights["head"]}}
    for i, w in enumerate(weights["layers"]):
        params[f"layer{i}"] = {
            "attn_norm": {"scale": w["attn_norm"]}, "mlp_norm": {"scale": w["mlp_norm"]},
            "qkv": {"kernel": jnp.stack([w["wq"], w["wk"], w["wv"]], axis=1)},
            "out_proj": {"kernel": w["wo"]},
            "wi_gate": {"kernel": w["w_gate"]}, "wi_up": {"kernel": w["w_up"]},
            "wo": {"kernel": w["w_down"]}}
    return {"params": params}


def from_program(variables: dict) -> dict:
    """Views into the program's leaves, in the benchmark's layout (works on
    device arrays and on host arrays alike)."""
    p = variables["params"]
    layers = []
    for i in range(sum(1 for k in p if k.startswith("layer"))):
        w = p[f"layer{i}"]
        qkv = w["qkv"]["kernel"]
        layers.append({
            "attn_norm": w["attn_norm"]["scale"], "mlp_norm": w["mlp_norm"]["scale"],
            "wq": qkv[:, 0], "wk": qkv[:, 1], "wv": qkv[:, 2],
            "wo": w["out_proj"]["kernel"], "w_gate": w["wi_gate"]["kernel"],
            "w_up": w["wi_up"]["kernel"], "w_down": w["wo"]["kernel"]})
    return {"embed": p["embed"]["embedding"], "final_norm": p["final_norm"]["scale"],
            "head": p["lm_head"]["kernel"], "layers": layers}

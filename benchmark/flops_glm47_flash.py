"""Operations and bytes the ``glm4_moe_lite`` decoder needs, from shapes alone
(``benchmark/flops.py``'s rules: nothing here looks at how the program computes, and
recomputed operations are not counted).

A token is multiplied by every weight of the layers it passes, except the routed
experts: of those it meets ``num_experts_per_tok`` of ``n_router_outputs``, and this
chip holds ``experts_held`` of them, so the EXPECTED assignments a token brings here
are ``k * held / routed`` (0.5 for 8 of 64 at top 4) experts of three matrices each.
The router's product over all its outputs is counted.  Every block — the
``num_hidden_layers`` of the main model and the prediction module's one — runs the
causal half-square of its latent attention at q/k width ``qk_nope_head_dim +
qk_rope_head_dim`` and v width ``v_head_dim``.  Two heads: the main model's over every
token, the prediction module's (with its block and ``W_eh``) over the L - 1 positions of
a row that have a token after next.
"""

from __future__ import annotations


def attention_blocks(model: dict) -> int:
    """Blocks that run the latent attention: the layers here and the module's."""
    return model["num_hidden_layers"] + model["num_nextn_predict_layers"]


def mla_params(model: dict) -> int:
    d, h = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expected_experts_a_token(model: dict) -> float:
    lo, hi = model["experts_held"]
    return model["num_experts_per_tok"] * (hi - lo) / model["n_router_outputs"]


def block_params(model: dict, layer: int) -> float:
    """Weights of block ``layer`` that a token is multiplied by (expected)."""
    d = model["hidden_size"]
    if layer < model["first_k_dense_replace"]:
        return mla_params(model) + 3 * d * model["intermediate_size"]
    f = model["moe_intermediate_size"]
    return (mla_params(model) + d * model["n_router_outputs"]
            + 3 * d * f * model["n_shared_experts"] + expected_experts_a_token(model) * 3 * d * f)


def main_params(model: dict) -> float:
    """The main model's layers and its head (the embedding is a row lookup)."""
    return (sum(block_params(model, i) for i in range(model["num_hidden_layers"]))
            + model["hidden_size"] * model["vocab_size"])


def module_params(model: dict) -> float:
    """The prediction module: ``W_eh``, its block, the shared head once more."""
    d = model["hidden_size"]
    return model["num_nextn_predict_layers"] * (
        2 * d * d + block_params(model, model["num_hidden_layers"]) + d * model["vocab_size"])


def matmul_params(model: dict) -> float:
    return main_params(model) + module_params(model)


def total_params(model: dict) -> int:
    d, (lo, hi) = model["hidden_size"], model["experts_held"]
    attention = mla_params(model) + model["q_lora_rank"] + model["kv_lora_rank"]
    dense = attention + 3 * d * model["intermediate_size"] + 2 * d
    f = model["moe_intermediate_size"]
    expert = (attention + 2 * d + d * model["n_router_outputs"] + model["n_router_outputs"]
              + 3 * d * f * (model["n_shared_experts"] + hi - lo))
    n_dense = model["first_k_dense_replace"]
    module = model["num_nextn_predict_layers"] * (2 * d * d + 3 * d + expert)
    return (n_dense * dense + (model["num_hidden_layers"] - n_dense) * expert + module
            + 2 * model["vocab_size"] * d + d)


def attention_flops(model: dict, sequences: float, length: int, backward: bool) -> float:
    """One block's causal half-square.  Forward QK^T (q/k width) and PV (v width);
    backward dV and dP (v width), dQ and dK (q/k width)."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    widths = 2 * (qk + model["v_head_dim"]) if backward else qk + model["v_head_dim"]
    return 2.0 * sequences * model["num_attention_heads"] * 0.5 * length * length * widths


def attention_bytes(model: dict, sequences: float, length: int, itemsize: int,
                    backward: bool) -> float:
    """One block's: forward reads q, k (q/k width), v and writes o (v width); backward
    reads q, k, v, o, dO and writes dq, dk, dv.  Row statistics are left out."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    widths = 4 * (qk + model["v_head_dim"]) if backward else 2 * (qk + model["v_head_dim"])
    return float(sequences * model["num_attention_heads"] * length * widths * itemsize)


def train_flops(model: dict, sequences: float, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` tokens, both losses."""
    attention = sum(attention_flops(model, sequences, length, backward)
                    for backward in (False, True))
    module_rows = sequences * (length - 1)
    return (6.0 * main_params(model) * sequences * length + model["num_hidden_layers"] * attention
            + 6.0 * module_params(model) * module_rows
            + model["num_nextn_predict_layers"] * attention * ((length - 1) / length) ** 2)

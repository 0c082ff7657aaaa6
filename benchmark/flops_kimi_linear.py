"""Operations and bytes the ``kimi_linear`` decoder needs, from shapes alone
(``benchmark/flops.py``'s rules: nothing here looks at how the program
computes, and recomputed operations are not counted).

A token is multiplied by every weight of the layers it passes, except the
routed experts: of those it meets ``num_experts_per_token`` of
``n_routed_experts``, and this chip holds ``experts_held`` of them, so the
EXPECTED assignments a token brings here are ``k * held / routed`` (0.25 for 8
of 256 at top 8) experts of three matrices each.  The router's product over all
``n_routed_experts`` is counted.  The KDA recurrence is counted as written,
token by token (decay the state, read it with k, add the outer product, read it
with q: 7 operations a state entry forward, twice that backward), not by the
chunkwise form's products, which depend on the chunk.  The causal softmax of an
MLA layer is half its square at q/k width ``qk_nope_head_dim + qk_rope_head_dim``
and v width ``v_head_dim``.
"""

from __future__ import annotations


def layer_kinds(model: dict) -> list:
    """[(mixer, ffn)] of the layers that are here: 'kda' | 'mla', 'dense' | 'moe'."""
    lin = model["linear_attn_config"]
    return [("kda" if i + 1 in lin["kda_layers"] else "mla",
             "dense" if i < model["first_k_dense_replace"] else "moe")
            for i in range(model["num_hidden_layers"])]


def kda_params(model: dict) -> int:
    d, lin = model["hidden_size"], model["linear_attn_config"]
    hd, r = lin["num_heads"] * lin["head_dim"], model.get("kda_gate_rank", lin["head_dim"])
    return (4 * d * hd + 2 * (d * r + r * hd) + d * lin["num_heads"]
            + 3 * lin["short_conv_kernel_size"] * hd)


def mla_params(model: dict) -> int:
    d, h = model["hidden_size"], model["num_attention_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * h * qk + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expected_experts_a_token(model: dict) -> float:
    lo, hi = model["experts_held"]
    return model["num_experts_per_token"] * (hi - lo) / model["n_routed_experts"]


def ffn_params(model: dict, kind: str) -> float:
    """Weights of a channel mixer that a token is multiplied by (expected)."""
    d = model["hidden_size"]
    if kind == "dense":
        return 3 * d * model["intermediate_size"]
    f = model["moe_intermediate_size"]
    return (d * model["n_routed_experts"] + 3 * d * f * model["num_shared_experts"]
            + expected_experts_a_token(model) * 3 * d * f)


def matmul_params(model: dict) -> float:
    """Every weight a token is multiplied by, in expectation: the layers that
    are here and the output head (the embedding is a row lookup)."""
    mixers = {"kda": kda_params(model), "mla": mla_params(model)}
    return (sum(mixers[m] + ffn_params(model, f) for m, f in layer_kinds(model))
            + model["hidden_size"] * model["vocab_size"])


def kda_recurrence_flops(model: dict, tokens: float, backward: bool) -> float:
    """The recurrence as written: 7 operations a state entry a token forward
    (decay 1, S^T k 2, outer product 2, S^T q 2), twice that backward."""
    lin = model["linear_attn_config"]
    return (14.0 if backward else 7.0) * tokens * lin["num_heads"] * lin["head_dim"] ** 2


def mla_attention_flops(model: dict, sequences: float, length: int, backward: bool) -> float:
    """Causal: half the square.  Forward QK^T (q/k width) and PV (v width);
    backward dV and dP (v width), dQ and dK (q/k width)."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    widths = 2 * (qk + model["v_head_dim"]) if backward else qk + model["v_head_dim"]
    return 2.0 * sequences * model["num_attention_heads"] * 0.5 * length * length * widths


def mla_attention_bytes(model: dict, sequences: float, length: int, itemsize: int,
                        backward: bool) -> float:
    """Forward reads q, k (q/k width), v and writes o (v width); backward reads
    q, k, v, o, dO and writes dq, dk, dv.  Row statistics are left out."""
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    widths = 4 * (qk + model["v_head_dim"]) if backward else 2 * (qk + model["v_head_dim"])
    return float(sequences * model["num_attention_heads"] * length * widths * itemsize)


def train_flops(model: dict, sequences: float, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` tokens."""
    tokens = sequences * length
    kinds = [m for m, _ in layer_kinds(model)]
    kda = kinds.count("kda") * (kda_recurrence_flops(model, tokens, False)
                                + kda_recurrence_flops(model, tokens, True))
    mla = kinds.count("mla") * (mla_attention_flops(model, sequences, length, False)
                                + mla_attention_flops(model, sequences, length, True))
    return 6.0 * matmul_params(model) * tokens + kda + mla

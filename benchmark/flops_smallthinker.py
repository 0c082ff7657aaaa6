"""Operations and bytes the SmallThinker decoder needs, from shapes alone
(``benchmark/flops.py``'s rules: nothing here looks at how the program computes,
and recomputed operations are not counted).

A token is multiplied by every weight of the layers it passes, except the
routed experts: of those it meets ``moe_num_active_primary_experts`` of
``n_routed_experts``, and this chip holds ``experts_held`` of them, so the
EXPECTED assignments a token brings here are ``k * held / routed`` (1.5 for 16 of
64 at top 6) experts of three matrices each.  The router's product over all
``n_routed_experts`` is counted.  The attention's scores and values are counted
over the (query, key) pairs a layer's mask leaves: the causal half-square of a
global layer, the band ``0 <= t - s < window`` of a windowed one.  k and v move at
``num_key_value_heads`` heads, q and the output at ``num_attention_heads``.
"""

from __future__ import annotations


def windowed(model: dict) -> list:
    """Per layer that is here: True where the attention is windowed."""
    return [bool(w) for w in model["sliding_window_layout"][:model["num_hidden_layers"]]]


def attended_pairs(length: int, window: int | None) -> float:
    """(query, key) pairs of one head over one sequence: ``sum_t min(t + 1, window)``."""
    if window is None or window >= length:
        return length * (length + 1) / 2.0
    return window * (window + 1) / 2.0 + (length - window) * float(window)


def layer_pairs(model: dict, length: int, is_windowed: bool) -> float:
    return attended_pairs(length, model["sliding_window_size"] if is_windowed else None)


def attention_flops(model: dict, sequences: float, length: int, is_windowed: bool,
                    backward: bool) -> float:
    """Forward QK^T and PV (2 products), backward dV, dP, dQ, dK (4): each 2 x
    pairs x head_dim multiply-adds a query head."""
    products = 4 if backward else 2
    return (products * 2.0 * sequences * model["num_attention_heads"]
            * layer_pairs(model, length, is_windowed) * model["head_dim"])


def attention_bytes(model: dict, sequences: float, length: int, itemsize: int,
                    backward: bool) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO and
    writes dq, dk, dv: q-sized tensors at the query heads' count, k-sized at the
    kv heads'.  Row statistics are left out."""
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    heads = 4 * hq + 4 * hkv if backward else 2 * hq + 2 * hkv
    return float(sequences * heads * length * model["head_dim"] * itemsize)


def expected_experts_a_token(model: dict) -> float:
    lo, hi = model["experts_held"]
    return model["moe_num_active_primary_experts"] * (hi - lo) / model["n_routed_experts"]


def layer_matmul_params(model: dict) -> float:
    """Weights of one layer that a token is multiplied by (expected): q and the
    output projection at the query heads, k and v at the kv heads, the router,
    and the expected experts' three matrices."""
    d, dk = model["hidden_size"], model["head_dim"]
    attention = 2 * d * dk * (model["num_attention_heads"] + model["num_key_value_heads"])
    experts = expected_experts_a_token(model) * 3 * d * model["moe_ffn_hidden_size"]
    return attention + d * model["n_routed_experts"] + experts


def matmul_params(model: dict) -> float:
    """The layers that are here and the output head (the embedding is a row lookup)."""
    return (model["num_hidden_layers"] * layer_matmul_params(model)
            + model["hidden_size"] * model["vocab_size"])


def total_params(model: dict) -> int:
    d, dk = model["hidden_size"], model["head_dim"]
    lo, hi = model["experts_held"]
    layer = (2 * d * dk * (model["num_attention_heads"] + model["num_key_value_heads"])
             + d * model["n_routed_experts"] + 2 * d
             + (hi - lo) * 3 * d * model["moe_ffn_hidden_size"])
    return model["num_hidden_layers"] * layer + 2 * model["vocab_size"] * d + d


def train_flops(model: dict, sequences: float, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` tokens."""
    attention = sum(attention_flops(model, sequences, length, w, False)
                    + attention_flops(model, sequences, length, w, True)
                    for w in windowed(model))
    return 6.0 * matmul_params(model) * sequences * length + attention

"""Operations and bytes block-diffusion training of the ``sdar_moe`` decoder needs, from
shapes alone (``benchmark/flops.py``'s rules: nothing here looks at how the program
computes, and recomputed operations are not counted).  Counted a DATA token (a row of L
tokens is one sequence of the traffic).

The layers run over two positions a data token, the noised copy and the clean one,
except the last: there the clean half is needed only for its keys and values (no later
layer and no head reads it), so the last layer counts the noised half whole and the
clean half's k and v projections alone.  A position is multiplied by every weight of a
layer it passes, except the routed experts: of those it meets ``num_experts_per_tok`` of
``n_router_outputs``, and this chip holds ``experts_held`` of them, so the EXPECTED
assignments a position brings here are ``k * held / routed`` (1 for 16 of 128 at top 8)
experts of three matrices each; the router's product over all its outputs is counted.
The attention's scores and values are counted over the pairs the block-diffusion mask
leaves: ``L (L + B) / 2`` a head for the clean queries (block-causal) and as many for the
noised ones (the earlier clean blocks and their own noised block), the last layer's
noised ones alone.  The head runs over the noised half.
"""

from __future__ import annotations


def expected_experts_a_token(model: dict) -> float:
    lo, hi = model["experts_held"]
    return model["num_experts_per_tok"] * (hi - lo) / model["n_router_outputs"]


def kv_params(model: dict) -> int:
    return 2 * model["hidden_size"] * model["head_dim"] * model["num_key_value_heads"]


def layer_matmul_params(model: dict) -> float:
    """Weights of one layer a position is multiplied by (expected): q and the output
    projection at the query heads, k and v at the kv heads, the router, the expected
    experts' three matrices."""
    d = model["hidden_size"]
    attention = 2 * d * model["head_dim"] * model["num_attention_heads"] + kv_params(model)
    experts = expected_experts_a_token(model) * 3 * d * model["moe_intermediate_size"]
    return attention + d * model["n_router_outputs"] + experts


def pairs_a_head(length: int, block: int) -> float:
    """(query, key) pairs of one head over one sequence, both halves: ``L (L + B)``."""
    return float(length) * (length + block)


def attention_flops(model: dict, sequences: float, length: int, pairs: float,
                    backward: bool) -> float:
    """Forward QK^T and PV (2 products), backward dV, dP, dQ, dK (4): each 2 x pairs x
    head_dim multiply-adds a query head."""
    products = 4 if backward else 2
    return products * 2.0 * sequences * model["num_attention_heads"] * pairs * model["head_dim"]


def kernel_flops(model: dict, sequences: float, length: int, backward: bool) -> float:
    """What the block-diffusion kernels compute in every layer: both halves' pairs."""
    return model["num_hidden_layers"] * attention_flops(
        model, sequences, length, pairs_a_head(length, model["block_length"]), backward)


def kernel_bytes(model: dict, sequences: float, length: int, itemsize: int,
                 backward: bool) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO and writes dq,
    dk, dv: q-sized tensors at the query heads' count, k-sized at the kv heads', each
    over both halves (2L).  Row statistics are left out."""
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    heads = 4 * hq + 4 * hkv if backward else 2 * hq + 2 * hkv
    return float(model["num_hidden_layers"] * sequences * heads * 2 * length
                 * model["head_dim"] * itemsize)


def train_flops(model: dict, sequences: float, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` data tokens."""
    n, B = model["num_hidden_layers"], model["block_length"]
    tokens = sequences * length
    layers = ((n - 1) * 2 * layer_matmul_params(model)
              + layer_matmul_params(model) + kv_params(model))
    pairs = pairs_a_head(length, B)
    attention = sum(attention_flops(model, sequences, length, p, b)
                    for p in ((n - 1) * pairs, pairs / 2) for b in (False, True))
    return 6.0 * tokens * (layers + model["hidden_size"] * model["vocab_size"]) + attention


def total_params(model: dict) -> int:
    d, D = model["hidden_size"], model["head_dim"]
    lo, hi = model["experts_held"]
    attention = (2 * d * D * model["num_attention_heads"] + kv_params(model) + 2 * D)
    layer = (attention + d * model["n_router_outputs"] + 2 * d
             + (hi - lo) * 3 * d * model["moe_intermediate_size"])
    return model["num_hidden_layers"] * layer + 2 * model["vocab_size"] * d + d

"""Operations and bytes training the ``nemotron_h`` decoder needs, from shapes alone
(``benchmark/flops.py``'s rules: nothing here looks at how the program computes, and
recomputed operations are not counted).

A token is multiplied by every weight of the layers it passes (6 operations a weight:
forward and backward), except the routed experts: of those it meets
``num_experts_per_tok`` of ``n_router_outputs``, and this chip holds ``experts_held`` of
them, so the EXPECTED assignments a token brings here are ``k * held / routed`` (0.375
for 8 of 128 at top 6) experts of two matrices each.  The router's product over all its
outputs is counted; the depthwise convolution's ``2 K`` operations a channel and the
gates are left out.  Attention: the causal half-square (``benchmark/flops.py``).

The SSD scan, counted at the published ``chunk_size`` whatever implements it: inside a
chunk of ``Q`` tokens the pairs ``s <= t`` (``Q (Q + 1) / 2`` of them) of ``C B^T`` once a
group and of the masked product with ``dt x`` once a head; each chunk's states
(``B^T (w dt x)``, ``N P`` a token a head) and the state's part of the output
(``C S``, as many).  The backward is twice the forward."""

from __future__ import annotations

from benchmark import flops


def expected_experts_a_token(model: dict) -> float:
    lo, hi = model["experts_held"]
    return model["num_experts_per_tok"] * (hi - lo) / model["n_router_outputs"]


def _kinds(model: dict) -> dict:
    pattern = model["hybrid_override_pattern"]
    return {k: pattern.count(k) for k in "ME*"}


def mamba_params(model: dict) -> int:
    """W_in and W_out of one Mamba-2 layer."""
    d, inner = model["hidden_size"], model["mamba_num_heads"] * model["mamba_head_dim"]
    groups_state = 2 * model["n_groups"] * model["ssm_state_size"]
    return d * (2 * inner + groups_state + model["mamba_num_heads"]) + inner * d


def attention_params(model: dict) -> int:
    d, D = model["hidden_size"], model["head_dim"]
    return 2 * d * D * (model["num_attention_heads"] + model["num_key_value_heads"])


def expert_layer_params(model: dict) -> float:
    """Expected weights a token meets in one expert layer: the router, the shared expert,
    the expected routed experts."""
    d = model["hidden_size"]
    return (d * model["n_router_outputs"]
            + 2 * d * model["moe_shared_expert_intermediate_size"]
            + expected_experts_a_token(model) * 2 * d * model["moe_intermediate_size"])


def ssd_flops(model: dict, sequences: float, length: int, backward: bool) -> float:
    """Operations of the scans of every Mamba-2 layer over ``sequences`` rows of
    ``length`` tokens (the module docstring's count)."""
    Q, N = model["chunk_size"], model["ssm_state_size"]
    H, P, G = model["mamba_num_heads"], model["mamba_head_dim"], model["n_groups"]
    pairs = (Q + 1) / 2  # a token's partners s <= t inside its chunk
    a_token = 2 * pairs * (N * G + P * H) + 2 * 2 * N * P * H
    forward = _kinds(model)["M"] * sequences * length * a_token
    return 2 * forward if backward else forward


def ssd_bytes(model: dict, sequences: float, length: int, itemsize: int,
              backward: bool) -> float:
    """What the scans must move: ``x``, ``B``, ``C`` and ``y`` in the compute dtype, ``dt``
    in float32, the state at each run's start (at the published chunk, a run of 16
    chunks); the backward reads x, B, C, dt, dy and the states and writes their
    gradients."""
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    GN = model["n_groups"] * model["ssm_state_size"]
    tokens = sequences * length
    rows = (H * P + 2 * GN) * itemsize + 4 * H  # x, B, C and dt a token
    out = H * P * itemsize
    states = 4 * H * P * model["ssm_state_size"] * sequences * -(-length // (16 * model["chunk_size"]))
    layers = _kinds(model)["M"]
    if backward:
        return float(layers * (tokens * (2 * rows + out) + states))
    return float(layers * (tokens * (rows + out) + states))


def train_flops(model: dict, sequences: float, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` tokens."""
    kinds, tokens = _kinds(model), sequences * length
    weights = (kinds["M"] * mamba_params(model) + kinds["*"] * attention_params(model)
               + kinds["E"] * expert_layer_params(model)
               + model["hidden_size"] * model["vocab_size"])
    heads, D = model["num_attention_heads"], model["head_dim"]
    attention = kinds["*"] * sum(flops.attention_flops(sequences, heads, length, D, True, b)
                                 for b in (False, True))
    scan = ssd_flops(model, sequences, length, False) + ssd_flops(model, sequences, length, True)
    return 6.0 * tokens * weights + attention + scan

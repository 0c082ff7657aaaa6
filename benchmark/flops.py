"""Operations and bytes the algorithm needs, from shapes alone.

Nothing here looks at how the program computes: a kernel that is replaced,
fused away or recomputed is held to the same count.  Recomputed operations
(``remat``, a flash backward's second pass over the scores) are not counted.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def layer_matmul_params(model: dict) -> int:
    """Weights of one decoder layer that a token is multiplied by: q, k, v and
    the output projection (4 * hidden^2) and the gated MLP (3 * hidden * ffn)."""
    h, f = model["hidden_size"], model["intermediate_size"]
    kv = model["num_key_value_heads"] * (h // model["num_attention_heads"])
    return 2 * h * h + 2 * h * kv + 3 * h * f


def matmul_params(model: dict) -> int:
    """Every weight a token is multiplied by: the layers and the output head.
    The embedding is a row lookup, not a product, and is left out."""
    return (model["num_hidden_layers"] * layer_matmul_params(model)
            + model["hidden_size"] * model["vocab_size"])


def total_params(model: dict) -> int:
    h = model["hidden_size"]
    norms = (2 * model["num_hidden_layers"] + 1) * h
    embed = model["vocab_size"] * h * (1 if model.get("tie_word_embeddings") else 2)
    return model["num_hidden_layers"] * layer_matmul_params(model) + embed + norms


def attention_flops(batch: int, heads: int, length: int, head_dim: int,
                    causal: bool, backward: bool) -> float:
    """QK^T and PV forward (2 products); dV, dP, dQ, dK backward (4).  Each is
    2*L*L*D multiply-adds per head; a causal mask needs half of the square."""
    products = 4 if backward else 2
    square = length * length * (0.5 if causal else 1.0)
    return products * 2.0 * batch * heads * square * head_dim


def attention_bytes(batch: int, heads: int, length: int, head_dim: int,
                    itemsize: int, backward: bool) -> float:
    """Forward reads q, k, v and writes o; backward reads q, k, v, o, dO and
    writes dq, dk, dv.  Row statistics (L floats a head) are left out."""
    tensors = 8 if backward else 4
    return float(tensors * batch * heads * length * head_dim * itemsize)


def train_flops(model: dict, sequences: int, length: int) -> float:
    """Forward and backward of ``sequences`` rows of ``length`` tokens: 6 per
    matmul weight per token, plus causal attention in every layer."""
    tokens = sequences * length
    heads = model["num_attention_heads"]
    head_dim = model["hidden_size"] // heads
    attn = model["num_hidden_layers"] * (
        attention_flops(sequences, heads, length, head_dim, True, False)
        + attention_flops(sequences, heads, length, head_dim, True, True))
    return 6.0 * matmul_params(model) * tokens + attn


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """Least time the chip could take, and which bound holds."""
    t_flops = flops / peaks["bf16_flops_per_s"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")

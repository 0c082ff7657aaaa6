"""Config-driven sparse decoder of ``model_type: sdar_moe`` (JetLM/SDAR-30B-A3B-Chat),
trained by block diffusion — all from a dict whose keys are those of the published
``config.json``, plus ``n_router_outputs`` / ``experts_held`` where a process holds a
share of the experts, and the block-diffusion keys the config does not give
(``block_length``, ``noise_t_range``).

Layer ``i`` (pre-norm residual, RMSNorm with a float32 scale, no bias anywhere):

1. ``x <- x + W_o Attn(RoPE(RMSNorm_q(W_q a)), RoPE(RMSNorm_k(W_k a)), W_v a)`` with
   ``a = RMSNorm_in(x)``: ``num_attention_heads`` query heads over
   ``num_key_value_heads`` key/value heads of ``head_dim``, a per-head RMSNorm on q and
   on k before the rotation (``qk_norm``, Qwen3-MoE's), rotate-half at ``rope_theta``
   over every channel (``expert_lm.GQAMixer``), under the block-diffusion mask
   (``ops.flash_attention.attention(..., block_diffusion=block_length)``), scope
   ``lm.attn.bd``.
2. ``m = RMSNorm_post(x)``; ``x <- x + sum over the chosen experts held here of w_e
   SwiGLU_e(m)``: the router's ``n_router_outputs`` logits of ``m`` in float32, the
   top ``num_experts_per_tok``, weighted by the softmax over the chosen logits (what
   ``norm_topk_prob`` gives the softmax over all of them); no shared expert
   (``expert_lm.ExpertShare``).

Final RMSNorm, untied head.

Training (BD3-LM, arXiv:2503.09573, sections 3-4; SDAR adopts it).  A row ``x`` of L
tokens is cut into blocks of ``block_length``.  Under scope ``lm.bd.noise`` and from the
rng stream ``noise`` (the step's key: ``ml/engine/train.py:build_loss_fn``), with
``k_t, k_m = split(noise key)``:

    t_b     = lo + (hi - lo) u_b,   u = uniform(k_t, [rows, L / block_length])
    masked  = uniform(k_m, [rows, L]) < t_{block(i)}
    x_noisy = where(masked, V - 1, x)

(``noise_t_range`` = [lo, hi]; [1e-3, 1] is the linear schedule
``t = eps + (1 - eps) u``; the mask id is the embedding's last row, ``V - 1``,
which the data never holds).  The blocks run over ``[x_noisy ; x]``, 2L positions with
RoPE positions 0..L-1 in both halves, except the last: nothing after it reads its clean
half but that half's keys and values, so it computes k and v over 2L and its queries,
output projection, residual, norm, router and experts over the noised half alone, and
returns that half (``ops.flash_attention`` reads q over L against k over 2L as the
noised queries alone).  The final norm and the head see the noised half, and the step's
loss, under ``fed.loss``, is

    L = mean over the batch's rows of  (1 / L) sum_b (1 / t_b) sum_{i in b, masked} -log p(x_i)

The model owns its objective (``owns_loss``): with ``targets=(labels, row mask)`` (the
labels are not read: the target is the row itself) ``__call__`` returns that loss, and
sows ``bd.positions`` (2 x rows x L) and ``bd.masked`` into ``counters`` when training,
``bd.masked`` and ``bd.correct`` (masked positions whose argmax is the token) when not.
Without ``targets`` (``init``) nothing is noised and the noised half's logits come back.

Activations and matrix products run in ``compute_dtype``; parameters, router logits and
the rotation are float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, ClassVar, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from .expert_lm import (COUNTERS, KEPT, ExpertShare, GQAMixer, _normal, compute_dtype, held_range,
                        rms_norm, route)

# what the step sows beside the expert layers' counters
BD_COUNTERS = ("bd.positions", "bd.masked")

# the published config.json's keys (the catalog's copy), what a share and the objective
# add, and what a configuration file says about itself; another key names a mechanism
# this module does not write
_PUBLISHED = {
    "attention_bias", "decoder_sparse_step", "head_dim", "hidden_act", "hidden_size",
    "intermediate_size", "max_position_embeddings", "max_window_layers", "mlp_only_layers",
    "model_type", "moe_intermediate_size", "norm_topk_prob", "num_attention_heads", "num_experts",
    "num_experts_per_tok", "num_hidden_layers", "num_key_value_heads", "rms_norm_eps",
    "rope_scaling", "rope_theta", "sliding_window", "tie_word_embeddings", "use_sliding_window",
    "vocab_size"}
_OWN = {"n_router_outputs", "experts_held", "block_length", "noise_t_range", "compute_dtype",
        "param_dtype", "remat"}
_ABOUT = {"name", "source", "reduced", "published", "assumed", "deployment", "parameters",
          "bytes_reckoned"}


@dataclasses.dataclass(frozen=True)
class SdarMoeConfig:
    hidden_size: int
    num_hidden_layers: int
    vocab_size: int
    rms_norm_eps: float
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    rope_theta: float
    moe_intermediate_size: int
    n_routed_experts: int  # the router's outputs, as ``expert_lm`` names them
    experts_held: Tuple[int, int]
    num_experts_per_token: int
    block_length: int
    noise_t_range: Tuple[float, float]
    qk_norm: ClassVar[bool] = True  # the per-head q/k RMSNorm of the Qwen3-MoE block
    num_shared_experts: int = 0
    dtype: Any = jnp.float32
    remat: bool = False

    @classmethod
    def from_dict(cls, cfg: dict) -> "SdarMoeConfig":
        """From the published keys.  ``num_experts`` counts the experts HELD where
        ``experts_held`` is given (the file then states the router's width as
        ``n_router_outputs``); a whole model gives neither."""
        unknown = sorted(set(cfg) - _PUBLISHED - _OWN - _ABOUT)
        if unknown:
            raise ValueError(f"sdar_moe: unknown keys {unknown}")
        layers = int(cfg["num_hidden_layers"])
        unsupported = {
            "model_type": cfg.get("model_type", "sdar_moe") != "sdar_moe",
            "attention_bias": bool(cfg.get("attention_bias", False)),
            "hidden_act": cfg.get("hidden_act", "silu") != "silu",
            "decoder_sparse_step": int(cfg.get("decoder_sparse_step", 1)) != 1,
            "mlp_only_layers": bool(cfg.get("mlp_only_layers", [])),
            "norm_topk_prob": not cfg.get("norm_topk_prob", True),
            "rope_scaling": cfg.get("rope_scaling") is not None,
            "use_sliding_window": bool(cfg.get("use_sliding_window", False)),
            "tie_word_embeddings": bool(cfg.get("tie_word_embeddings", False)),
        }
        bad = sorted(k for k, v in unsupported.items() if v)
        if bad:
            raise NotImplementedError(f"sdar_moe: no code for the given {bad}")
        heads, kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        if heads % kv_heads:
            raise ValueError(f"{heads} query heads are no multiple of {kv_heads} key/value heads")
        total = int(cfg.get("n_router_outputs", cfg["num_experts"]))
        held = held_range(cfg, total)
        if "experts_held" in cfg and held[1] - held[0] != int(cfg["num_experts"]):
            raise ValueError(f"num_experts counts the experts held: {cfg['num_experts']} "
                             f"against {held} of {total}")
        top_k = int(cfg["num_experts_per_tok"])
        if not 0 < top_k <= total:
            raise ValueError(f"{top_k} experts a token of {total}")
        block = int(cfg["block_length"])
        if block < 1 or 128 % block:
            raise ValueError(f"block_length {block} must divide 128 (the kernels' sub-block)")
        lo, hi = (float(t) for t in cfg.get("noise_t_range", (1e-3, 1.0)))
        if not 0.0 < lo <= hi <= 1.0:
            raise ValueError(f"noise_t_range must lie in (0, 1]: {[lo, hi]}")
        return cls(
            hidden_size=int(cfg["hidden_size"]), num_hidden_layers=layers,
            vocab_size=int(cfg["vocab_size"]),
            rms_norm_eps=float(cfg["rms_norm_eps"]), num_attention_heads=heads,
            num_key_value_heads=kv_heads, head_dim=int(cfg["head_dim"]),
            rope_theta=float(cfg["rope_theta"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]), n_routed_experts=total,
            experts_held=held, num_experts_per_token=top_k, block_length=block,
            noise_t_range=(lo, hi), dtype=compute_dtype(cfg), remat=bool(cfg.get("remat", False)))


def draw_noise(key, rows: int, length: int, block_length: int, t_range):
    """(t [rows, L / block_length], masked [rows, L]) of one step: the module docstring's
    rule, which ``benchmark/reference_sdar.py`` writes again."""
    k_t, k_m = jax.random.split(key)
    lo, hi = t_range
    t = lo + (hi - lo) * jax.random.uniform(k_t, (rows, length // block_length), jnp.float32)
    u = jax.random.uniform(k_m, (rows, length), jnp.float32)
    return t, u < jnp.repeat(t, block_length, axis=1)


class Block(nn.Module):
    """One layer over ``[x_noised ; x_clean]`` (2L positions) -> the same, except the
    last layer's: its clean half is needed only for its keys and values, so it takes
    2L positions and returns the noised half's L (queries, output projection,
    residual, ``ffn_norm``, router and experts over L); ``init`` runs it whole."""
    cfg: SdarMoeConfig
    index: int  # 0-based

    @nn.compact
    def __call__(self, x, train: bool = False):
        cfg = self.cfg
        # ``init`` runs the last block whole: the parameters are the same either way, and an
        # eager init then reuses the other blocks' small programs (a last block of its own
        # shapes compiled 253 of them where 179 do at the tiny preset, about 6 s more of the
        # ``sdar`` cell's set-up on a v5e)
        kv_only = self.index == cfg.num_hidden_layers - 1 and not self.is_initializing()

        def norm(name):
            scale = self.param(name, nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
            return rms_norm(x, scale, cfg.rms_norm_eps)

        with jax.named_scope("lm.norm"):
            a = norm("attn_norm")
        with jax.named_scope("lm.attn.bd"):
            y = GQAMixer(cfg, None, True, cfg.block_length, cfg.qk_norm, name="attn")(a, kv_only)
            x = (x[:, :y.shape[1]] if kv_only else x) + y
        with jax.named_scope("lm.norm"):
            h = norm("ffn_norm")
        with jax.named_scope("lm.moe.route"):
            w_r = self.param("router", _normal(cfg.hidden_size),
                             (cfg.hidden_size, cfg.n_routed_experts), jnp.float32)
            logits = jnp.matmul(h.reshape(-1, cfg.hidden_size).astype(jnp.float32), w_r,
                                precision=jax.lax.Precision.HIGHEST)
            routing = route(logits, None, cfg.num_experts_per_token, softmax_chosen=True)
        return x + ExpertShare(cfg, name="moe")(h, train, routing)


class SdarMoeLM(nn.Module):
    """The LM shell of block-diffusion training (module docstring): noise, embedding
    of both copies, the blocks over 2L positions, the last of which returns the
    noised half alone (each recomputed in the backward pass where ``cfg.remat``, all
    but what ``KEPT`` names), the final norm and the head over that half, and the
    loss.  Gauge ``bd.kv_only_layers``: the blocks whose clean half ran only through
    k and v (1), set when the model is traced."""
    cfg: SdarMoeConfig
    # the packed round asks for these sums beside the loss (ml/engine/packed.py)
    round_counters: Tuple[str, ...] = COUNTERS + BD_COUNTERS

    # the step's loss is the model's: ``ml/engine/train.py:build_loss_fn`` hands it
    # ``targets`` and the rng stream ``noise`` and adds no term of its own
    owns_loss: ClassVar[bool] = True
    takes_targets: ClassVar[bool] = True
    init_length: ClassVar[int] = 64  # as ``DecoderLM``'s: shapes alone depend on nothing longer

    @nn.compact
    def __call__(self, tokens, train: bool = False, targets=None):
        from ..core import obs

        cfg = self.cfg
        B = cfg.block_length
        obs.gauge_set("bd.block_length", B)
        if self.is_initializing():
            tokens = tokens[:, :max(self.init_length, B)]
        rows, length = tokens.shape
        if length % B:
            raise ValueError(f"a row of {length} tokens is no whole number of blocks of {B}")
        embed = self.param("embed", _normal(cfg.hidden_size),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        if targets is None:  # ``init`` or a bare forward: nothing is noised
            noisy = tokens
        else:
            with jax.named_scope("lm.bd.noise"):
                key = self.scope.rngs["noise"].as_jax_rng()  # the engine's key, unfolded
                t, masked = draw_noise(key, rows, length, B, cfg.noise_t_range)
                noisy = jnp.where(masked, cfg.vocab_size - 1, tokens)
        with jax.named_scope("lm.embed"):
            x = embed.astype(cfg.dtype)[jnp.concatenate([noisy, tokens], axis=1)]
        block_cls = (nn.remat(Block, static_argnums=(2,),
                              policy=jax.checkpoint_policies.save_only_these_names(*KEPT))
                     if cfg.remat else Block)
        for i in range(cfg.num_hidden_layers):
            x = block_cls(cfg, i, name=f"layer{i}")(x, train)
        # the last block returned the noised half alone: it read its clean half for k and v only
        obs.gauge_set("bd.kv_only_layers", int(x.shape[1] == length))
        scale = self.param("final_norm", nn.initializers.ones, (cfg.hidden_size,), jnp.float32)
        head = self.param("head", _normal(cfg.hidden_size),
                          (cfg.hidden_size, cfg.vocab_size), jnp.float32)
        with jax.named_scope("lm.head"):
            logits = rms_norm(x, scale, cfg.rms_norm_eps) @ head.astype(cfg.dtype)
        if targets is None:
            return logits
        _, row_mask = targets
        with jax.named_scope("fed.loss"):
            row_mask = row_mask.astype(jnp.float32)
            live = masked.astype(jnp.float32) * row_mask[:, None]
            weight = live / jnp.repeat(t, B, axis=1)
            per = optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), tokens)
            loss = jnp.sum(per * weight) / length / jnp.maximum(jnp.sum(row_mask), 1.0)
            zero = lambda: jnp.zeros((), jnp.float32)  # noqa: E731
            self.sow("counters", "bd.masked", jnp.sum(live), reduce_fn=jnp.add, init_fn=zero)
            if train:
                self.sow("counters", "bd.positions", 2.0 * length * jnp.sum(row_mask),
                         reduce_fn=jnp.add, init_fn=zero)
            else:
                right = (jnp.argmax(logits, axis=-1) == tokens).astype(jnp.float32)
                self.sow("counters", "bd.correct", jnp.sum(right * live), reduce_fn=jnp.add,
                         init_fn=zero)
        return loss

"""The description a decoder is built from: one ``BlockSpec`` per layer
(which mixer, which feed-forward layer) and the sizes they share.

The keys of ``config.model.lm`` are the published ``config.json``'s (a key
one family's config.json lacks is read with the value that family implies:
``scoring_func`` sigmoid, tied embeddings, no expert bias, one ``rope_theta``
where there is no ``rope_parameters`` by layer type), plus the chip's share of
a deployment: ``experts_held`` (first expert and count
of the routed experts whose weights live here), ``heads_held`` (first head and
count of a latent-attention layer's heads whose up- and output projections
live here: attention tensor-parallel over heads) and ``vocab_held`` (rows of
the embedding held here; ids, logits and the loss are over them).

A ``deepseek_v2``/``v3``-shaped config.json (``model_type`` ``xing4_0``) is
read by its own keys where it has them: ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``, ``rope_theta`` with
a ``rope_scaling`` group (``yarn`` with ``mscale`` and ``mscale_all_dim``),
``n_shared_experts``, ``hc_mult`` / ``hc_sinkhorn_iters`` / ``hc_eps`` /
``mhc_h_res_clamp_min`` / ``_max`` (the residual streams),
``num_nextn_predict_layers``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional, Tuple

IGNORE = -1     # target of a position that is padding or has no next token
ATTENTIONS = ("full_attention", "sliding_attention", "latent_attention")
MIXERS = ("conv",) + ATTENTIONS
FFNS = ("dense", "moe")
SCORING = ("sigmoid", "softmax")


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    # "conv": gated short convolution; "full_attention": rotary GQA, causal;
    # "sliding_attention": the same over the last ``sliding_window`` keys;
    # "latent_attention": queries and keys/values through low-rank latents, one
    # rotary key shared by every head (layers.py::LatentAttention), causal
    mixer: str
    ffn: str        # "dense": SwiGLU; "moe": routed experts

    def __post_init__(self):
        if self.mixer not in MIXERS or self.ffn not in FFNS:
            raise ValueError(f"unknown block {self}")


@dataclasses.dataclass(frozen=True)
class RotaryRule:
    """One entry of a config.json's ``rope_parameters``: how a kind of layer
    turns positions into angles.  ``yarn`` scales the slow frequencies by
    ``factor`` (layers.py::rotary_frequencies) and cos and sin by
    ``attention_factor``; ``default`` reads ``theta`` alone."""

    rope_type: str
    theta: float
    factor: float = 1.0
    original_max_position_embeddings: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.0

    def __post_init__(self):
        if self.rope_type not in ("default", "yarn"):
            raise ValueError(f"unknown rope_type {self.rope_type!r}")

    @classmethod
    def from_config(cls, entry) -> "RotaryRule":
        if entry["rope_type"] == "default":
            return cls("default", float(entry["rope_theta"]))
        return cls(
            "yarn", float(entry["rope_theta"]), float(entry["factor"]),
            int(entry["original_max_position_embeddings"]), float(entry["beta_fast"]),
            float(entry["beta_slow"]), float(entry["attention_factor"]))

    @classmethod
    def from_rope_scaling(cls, theta: float, scaling) -> Tuple["RotaryRule", float]:
        """(rule, factor on the softmax scale) of a ``deepseek``-shaped config:
        one ``rope_theta`` and, where it has one, a ``rope_scaling`` group of type
        ``yarn``.  cos and sin carry ``mscale(factor, mscale) / mscale(factor,
        mscale_all_dim)``, the softmax scale ``mscale(factor, mscale_all_dim)``
        squared, ``mscale(f, m) = 0.1 m ln f + 1`` (1 where f <= 1)."""
        if not scaling:
            return cls("default", float(theta)), 1.0
        if scaling["type"] != "yarn":
            raise ValueError(f"unknown rope_scaling type {scaling['type']!r}")
        factor = float(scaling["factor"])

        def mscale(m: float) -> float:
            return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

        over_all = mscale(float(scaling.get("mscale_all_dim", 0.0)))
        rule = cls("yarn", float(theta), factor,
                   int(scaling["original_max_position_embeddings"]),
                   float(scaling.get("beta_fast", 32.0)), float(scaling.get("beta_slow", 1.0)),
                   mscale(float(scaling.get("mscale", 1.0))) / over_all)
        return rule, over_all ** 2


@dataclasses.dataclass(frozen=True)
class LMSpec:
    blocks: Tuple[BlockSpec, ...]
    vocab_held: int
    hidden_size: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int                  # the router's width
    experts_per_tok: int
    experts_held: Tuple[int, int]     # (first, count)
    conv_kernel: int
    rotary: Tuple[Tuple[str, RotaryRule], ...]    # (kind of attention layer, its rule)
    sliding_window: Optional[int]     # keys a sliding layer's query sees, its own among them
    norm_eps: float
    scoring_func: str                 # the router's scores: "sigmoid" or "softmax"
    norm_topk_prob: bool
    routed_scaling_factor: float
    use_expert_bias: bool
    tie_word_embeddings: bool
    dtype: Any
    # -- latent attention (a deepseek-shaped config.json's keys; 0: no such layer)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    heads_held: Optional[Tuple[int, int]] = None     # (first, count); None: every head
    softmax_scale_factor: float = 1.0   # on a latent layer's (nope + rope)^-0.5: YaRN's mscale^2
    # -- a shared expert beside the routed ones (every chip computes it alike)
    n_shared_experts: int = 0
    # -- residual streams (hyper-connections; 1: the plain residual path)
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 0
    hc_eps: float = 0.0
    hc_clamp: Tuple[float, float] = (-30.0, 30.0)
    # -- multi-token-prediction modules after the trunk, and their loss's weight
    mtp_layers: int = 0
    mtp_loss_weight: float = 0.0

    @property
    def recompute_blocks(self) -> bool:
        """Whether a block is made again on the way back (``nn.remat``): where
        its boundary is several streams wide, so that keeping one block's
        internals costs less than keeping every block's."""
        return self.hc_mult > 1

    def rotary_rule(self, mixer: str) -> RotaryRule:
        return dict(self.rotary)[mixer]

    def window(self, mixer: str) -> Optional[int]:
        return self.sliding_window if mixer == "sliding_attention" else None

    @classmethod
    def from_config(cls, lm, dtype) -> "LMSpec":
        layer_types = tuple(lm.layer_types)
        if len(layer_types) != lm.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(layer_types)} layers, "
                f"num_hidden_layers is {lm.num_hidden_layers}")
        first, count = (int(v) for v in lm.experts_held)
        if not (0 <= first and first + count <= lm.num_experts and count > 0):
            raise ValueError(f"experts_held {lm.experts_held} outside {lm.num_experts} experts")
        blocks = tuple(
            BlockSpec(mixer, "dense" if i < lm.num_dense_layers else "moe")
            for i, mixer in enumerate(layer_types))
        # one rule a kind of layer where the config.json has ``rope_parameters``
        # by layer type, else its one ``rope_theta`` for every attention layer
        by_type = lm.get("rope_parameters")
        latent = "latent_attention" in layer_types
        if latent:      # one theta and a rope_scaling group, the softmax scale's factor with it
            rule, scale_factor = RotaryRule.from_rope_scaling(
                lm.rope_theta, lm.get("rope_scaling"))
            rotary, heads = (("latent_attention", rule),), int(lm.num_attention_heads)
            first_head, held_heads = (int(v) for v in lm.get("heads_held") or (0, heads))
            if not (0 <= first_head and first_head + held_heads <= heads and held_heads > 0):
                raise ValueError(f"heads_held {lm.heads_held} outside {heads} heads")
            if set(layer_types) != {"latent_attention"}:
                raise ValueError("latent_attention layers share their config's one rope rule "
                                 "and mix with no other kind of layer")
        else:
            rotary = tuple(
                (kind, RotaryRule.from_config(by_type[kind]) if by_type
                 else RotaryRule("default", float(lm.rope_theta)))
                for kind in ATTENTIONS if kind in layer_types)
        hc_mult = int(lm.get("hc_mult", 1))
        mtp_layers = int(lm.get("num_nextn_predict_layers", 0))
        if mtp_layers > 1:
            raise ValueError("one multi-token-prediction module at most")
        window = int(lm.get("sliding_window") or 0) or None
        if "sliding_attention" in layer_types and not window:
            raise ValueError("sliding_attention layers need model.lm.sliding_window")
        scoring = lm.get("scoring_func", "sigmoid")
        if scoring not in SCORING:
            raise ValueError(f"unknown scoring_func {scoring!r}")
        return cls(
            blocks=blocks,
            vocab_held=int(lm.vocab_held),
            hidden_size=int(lm.hidden_size),
            num_heads=int(lm.num_attention_heads),
            num_kv_heads=int(lm.num_key_value_heads),
            head_dim=int(lm.get("head_dim") or (
                lm.qk_nope_head_dim + lm.qk_rope_head_dim if latent
                else lm.hidden_size // lm.num_attention_heads)),
            intermediate_size=int(lm.intermediate_size),
            moe_intermediate_size=int(lm.moe_intermediate_size),
            num_experts=int(lm.num_experts),
            experts_per_tok=int(lm.num_experts_per_tok),
            experts_held=(first, count),
            conv_kernel=int(lm.get("conv_L_cache", 0)),
            rotary=rotary,
            sliding_window=window,
            # the key is the config.json's own: ``norm_eps`` or ``rms_norm_eps``
            norm_eps=float(lm["norm_eps"] if "norm_eps" in lm else lm["rms_norm_eps"]),
            scoring_func=scoring,
            norm_topk_prob=bool(lm.norm_topk_prob),
            routed_scaling_factor=float(lm.get("routed_scaling_factor", 1.0)),
            use_expert_bias=bool(lm.get("use_expert_bias", False)),
            tie_word_embeddings=bool(lm.get("tie_word_embeddings", True)),
            dtype=dtype,
            hc_mult=hc_mult,
            n_shared_experts=int(lm.get("n_shared_experts", 0)),
            mtp_layers=mtp_layers,
            mtp_loss_weight=float(lm.get("mtp_loss_weight", 0.0)) if mtp_layers else 0.0,
            **(dict(
                q_lora_rank=int(lm.q_lora_rank), kv_lora_rank=int(lm.kv_lora_rank),
                qk_nope_head_dim=int(lm.qk_nope_head_dim),
                qk_rope_head_dim=int(lm.qk_rope_head_dim), v_head_dim=int(lm.v_head_dim),
                heads_held=(first_head, held_heads), softmax_scale_factor=scale_factor,
            ) if latent else {}),
            **(dict(
                hc_sinkhorn_iters=int(lm.hc_sinkhorn_iters), hc_eps=float(lm.hc_eps),
                hc_clamp=(float(lm.get("mhc_h_res_clamp_min", -30.0)),
                          float(lm.get("mhc_h_res_clamp_max", 30.0))),
            ) if hc_mult > 1 else {}),
        )

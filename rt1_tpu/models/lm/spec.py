"""The description a decoder is built from: one ``BlockSpec`` per layer
(which mixer, which feed-forward layer) and the sizes they share.

The keys of ``config.model.lm`` are the published ``config.json``'s (a key
one family's config.json lacks is read with the value that family implies:
``scoring_func`` sigmoid, tied embeddings, no expert bias, one ``rope_theta``
where there is no ``rope_parameters`` by layer type), plus the chip's share of
a deployment: ``experts_held`` (first expert and count
of the routed experts whose weights live here) and ``vocab_held`` (rows of
the embedding held here; ids, logits and the loss are over them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

IGNORE = -1     # target of a position that is padding or has no next token
ATTENTIONS = ("full_attention", "sliding_attention")
MIXERS = ("conv",) + ATTENTIONS
FFNS = ("dense", "moe")
SCORING = ("sigmoid", "softmax")


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    # "conv": gated short convolution; "full_attention": rotary GQA, causal;
    # "sliding_attention": the same over the last ``sliding_window`` keys
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
        rotary = tuple(
            (kind, RotaryRule.from_config(by_type[kind]) if by_type
             else RotaryRule("default", float(lm.rope_theta)))
            for kind in ATTENTIONS if kind in layer_types)
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
            head_dim=int(lm.get("head_dim") or lm.hidden_size // lm.num_attention_heads),
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
        )

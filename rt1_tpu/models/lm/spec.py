"""The description a decoder is built from: one ``BlockSpec`` per layer
(which mixer, which feed-forward layer) and the sizes they share.

The keys of ``config.model.lm`` are the published ``config.json``'s, plus
the chip's share of a deployment: ``experts_held`` (first expert and count
of the routed experts whose weights live here) and ``vocab_held`` (rows of
the embedding held here; ids, logits and the loss are over them).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

IGNORE = -1     # target of a position that is padding or has no next token
MIXERS = ("conv", "full_attention")
FFNS = ("dense", "moe")


@dataclasses.dataclass(frozen=True)
class BlockSpec:
    mixer: str      # "conv": gated short convolution; "full_attention": rotary GQA
    ffn: str        # "dense": SwiGLU; "moe": routed experts

    def __post_init__(self):
        if self.mixer not in MIXERS or self.ffn not in FFNS:
            raise ValueError(f"unknown block {self}")


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
    rope_theta: float
    norm_eps: float
    norm_topk_prob: bool
    routed_scaling_factor: float
    use_expert_bias: bool
    dtype: Any

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
            conv_kernel=int(lm.conv_L_cache),
            rope_theta=float(lm.rope_theta),
            norm_eps=float(lm.norm_eps),
            norm_topk_prob=bool(lm.norm_topk_prob),
            routed_scaling_factor=float(lm.routed_scaling_factor),
            use_expert_bias=bool(lm.use_expert_bias),
            dtype=dtype,
        )

"""The yardstick of a decoder's training step whose attention layers do not all
keep the same mask, and whose output head is a leaf of its own, from shapes.

    python -m benchmarks.flops_lm_mixed <config-name>

As ``benchmarks/flops_lm.py`` counts (which stays the yardstick of the
configurations that name it): per token, forward, every matrix product as 2
FLOPs a multiply-add, the experts at the balanced load (top-k x held / routed
experts a token), forward + backward = three times that; element-wise work,
norms, the softmax, the optimizer, the health pack and anything recomputed are
not counted.  What differs: QK^T and PV are counted over the pairs (query, key)
a layer's mask keeps, ``s (s + 1) / 2`` is rounded to half the square for a full
causal layer as there, ``s w - w (w - 1) / 2`` for a sliding layer whose query
sees its own key and the ``w - 1`` before it; every layer is routed; the head's
rows are parameters beside the embedding's.  The count is of the mathematics
and does not change with the kernel that implements it.

``attention_cost`` gives the operations and bytes of the attention kernels for
the metric ``attention_masked_roofline``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Tuple


def lm_sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    return {k[len("model.lm."):]: v for k, v in overrides.items() if k.startswith("model.lm.")}


def pairs_kept(kind: str, seq_len: int, window: int) -> float:
    """(query, key) pairs of one head of one sequence that the mask keeps."""
    if kind == "full_attention":
        return seq_len * seq_len / 2.0
    if kind == "sliding_attention":
        w = min(int(window), seq_len)
        return seq_len * w - w * (w - 1) / 2.0
    raise ValueError(f"no attention mask for a layer of kind {kind!r}")


def parameters(lm: Dict[str, Any]) -> Dict[str, float]:
    d, fe = lm["hidden_size"], lm["moe_intermediate_size"]
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    attention = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 2 * hd
    routed = lm["experts_held"][1] * 3 * d * fe + d * lm["num_experts"]
    layer = attention + routed + 2 * d
    tables = (1 if lm["tie_word_embeddings"] else 2) * lm["vocab_held"] * d
    return {"attention_mixer": attention, "routed_ffn": routed, "layer": layer,
            "embedding_and_head": tables,
            "total": len(lm["layer_types"]) * layer + tables + d}


def forward_flops_per_token(lm: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    d, fe = lm["hidden_size"], lm["moe_intermediate_size"]
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    share = lm["num_experts_per_tok"] * lm["experts_held"][1] / lm["num_experts"]

    def scores(kind):       # QK^T and PV over the keys a query sees on average
        return 2.0 * 2 * heads * hd * pairs_kept(kind, seq_len, lm["sliding_window"]) / seq_len

    parts = {
        "attention_projections": 2.0 * (2 * d * heads * hd + 2 * d * kv * hd),
        "attention_scores_full": scores("full_attention"),
        "attention_scores_sliding": scores("sliding_attention"),
        "router": 2.0 * d * lm["num_experts"],
        "experts": share * 2.0 * 3 * d * fe,
        "head": 2.0 * d * lm["vocab_held"],
    }
    total = parts["head"]
    for kind in lm["layer_types"]:
        total += parts["attention_projections"] + parts["router"] + parts["experts"]
        total += parts["attention_scores_full" if kind == "full_attention"
                       else "attention_scores_sliding"]
    return dict(parts, total=total)


def yardstick(config_file: Dict[str, Any]) -> Dict[str, float]:
    lm = lm_sizes(config_file["overrides"])
    seq_len = lm["seq_len"]
    batch = config_file["overrides"]["per_host_batch_size"]
    forward = forward_flops_per_token(lm, seq_len)
    count = parameters(lm)["total"]
    batch_bytes = batch * seq_len * 2 * 4           # int32 tokens and targets
    return {
        "forward_flops_per_token": forward["total"],
        "flops_per_sample": 3.0 * forward["total"] * seq_len,
        # float32 masters and both Adam moments read and written, the batch read once
        "min_bytes_per_step": float(2 * count * 4 + 2 * count * 8 + batch_bytes),
        "parameters": float(count),
    }


def attention_cost(batch: int, seq_len: int, lm: Dict[str, Any]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the attention kernels of every layer in
    ``layer_types``, forward and backward, over the pairs each layer's mask
    keeps: QK^T and PV forward, four products backward (the recomputed scores
    do not count).  Bytes: q, k, v, the output and their gradients, bfloat16,
    once each."""
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    pairs = sum(pairs_kept(kind, seq_len, lm["sliding_window"]) for kind in lm["layer_types"])
    moved = batch * seq_len * hd * (2 * heads + 2 * kv) * 2.0
    return 3.0 * 2.0 * 2 * batch * heads * hd * pairs, len(lm["layer_types"]) * 2.0 * moved


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", sys.argv[1] + ".json")) as f:
        cf = json.load(f)
    lm = lm_sizes(cf["overrides"])
    out = yardstick(cf)
    out["parameters_by_part"] = parameters(lm)
    out["forward_flops_per_token_by_part"] = forward_flops_per_token(lm, lm["seq_len"])
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

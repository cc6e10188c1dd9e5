"""The yardstick of a language-model training step, from shapes.

    python -m benchmarks.flops_lm <config-name>

``benchmarks/flops.py`` walks a jaxpr, and a grouped product's routed rows
are not in one; so the count is written out.  Per token, forward: every matrix
product as 2 FLOPs a multiply-add; causal attention at half the square (QK^T
and PV over s / 2 keys on average); the experts at the balanced load, top-k x
held / routed experts a token.  Forward + backward = three times that.
Element-wise work, norms, the softmax, the short convolution's taps, the
optimizer, the health pack and anything recomputed are not counted.  Least
bytes a step as flops.py has them: parameters and Adam's moments read and
written once, the batch read once.

``experts_cost`` and ``attention_cost`` give the operations and bytes of the
two kernels for the metrics ``moe_experts_roofline`` and
``attention_roofline``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Tuple


def lm_sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    return {k[len("model.lm."):]: v for k, v in overrides.items() if k.startswith("model.lm.")}


def parameters(lm: Dict[str, Any]) -> Dict[str, float]:
    d, f, fe = lm["hidden_size"], lm["intermediate_size"], lm["moe_intermediate_size"]
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    held = lm["experts_held"][1]
    conv = d * 3 * d + d * d + lm["conv_L_cache"] * d
    attn = d * heads * hd + 2 * d * kv * hd + heads * hd * d + 2 * hd
    dense = 3 * d * f
    routed = held * 3 * d * fe + d * lm["num_experts"] + (
        lm["num_experts"] if lm["use_expert_bias"] else 0)
    total = lm["vocab_held"] * d + d
    for i, mixer in enumerate(lm["layer_types"]):
        total += (conv if mixer == "conv" else attn) + 2 * d
        total += dense if i < lm["num_dense_layers"] else routed
    return {"conv_mixer": conv, "attention_mixer": attn, "dense_ffn": dense,
            "routed_ffn": routed, "total": total}


def forward_flops_per_token(lm: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    d, f, fe = lm["hidden_size"], lm["intermediate_size"], lm["moe_intermediate_size"]
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    share = lm["num_experts_per_tok"] * lm["experts_held"][1] / lm["num_experts"]
    parts = {
        "conv_mixer": 2.0 * (d * 3 * d + d * d),
        "attention_projections": 2.0 * (2 * d * heads * hd + 2 * d * kv * hd),
        "attention_scores": 2.0 * 2 * heads * hd * seq_len / 2,
        "dense_ffn": 2.0 * 3 * d * f,
        "router": 2.0 * d * lm["num_experts"],
        "experts": share * 2.0 * 3 * d * fe,
        "head": 2.0 * d * lm["vocab_held"],
    }
    total = parts["head"]
    for i, mixer in enumerate(lm["layer_types"]):
        total += parts["conv_mixer"] if mixer == "conv" else (
            parts["attention_projections"] + parts["attention_scores"])
        total += parts["dense_ffn"] if i < lm["num_dense_layers"] else (
            parts["router"] + parts["experts"])
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


def experts_cost(rows: float, layers: int, lm: Dict[str, Any]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the grouped products of ``layers`` routed
    layers over ``rows`` computed rows in all, forward and backward: three
    products a row one way (x W_1, x W_3, h W_2), each again twice on the way
    back.  Bytes: the stacks read each way and their gradients written once
    (bfloat16), each row's input, two hidden activations and output moved
    once each way."""
    d, fe, held = lm["hidden_size"], lm["moe_intermediate_size"], lm["experts_held"][1]
    flops = 3.0 * rows * 2.0 * 3 * d * fe
    stacks = layers * held * 3 * d * fe * 2.0
    activations = rows * (2 * d + 3 * fe) * 2.0
    return flops, 3.0 * stacks + 2.0 * activations


def attention_cost(batch: int, seq_len: int, layers: int, lm: Dict[str, Any]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of causal attention forward and backward at half
    the square: QK^T and PV forward, four products backward (the recomputed
    scores do not count).  Bytes: q, k, v, the output and their gradients,
    bfloat16, once each."""
    heads, kv, hd = lm["num_attention_heads"], lm["num_key_value_heads"], lm["head_dim"]
    forward = 2.0 * 2 * batch * heads * seq_len * seq_len * hd / 2
    moved = batch * seq_len * hd * (2 * heads + 2 * kv) * 2.0
    return layers * 3.0 * forward, layers * 2.0 * moved


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

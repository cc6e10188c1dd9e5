"""The yardstick of a decoder's training step with latent attention, residual
streams mixed by maps a token, a shared expert beside the routed ones and a
multi-token-prediction module, from shapes.

    python -m benchmarks.flops_lm_mla <config-name>

As ``benchmarks/flops_lm.py`` counts (which stays the yardstick of the
configurations that name it): per token, forward, every matrix product as 2
FLOPs a multiply-add, the experts at the balanced load (top-k x held / routed
experts a token), forward + backward = three times that; element-wise work,
norms, the softmax, the Sinkhorn rounds, the streams' mixes, the optimizer, the
health pack and anything recomputed are not counted.  What differs:

* a latent layer's five projections at their own widths, the up- and output
  projections over the heads held (``heads_held``); QK^T over the keys' width
  (nope + rope) and PV over the values', on the causal half of the square;
* a sublayer's maps: one product of the n d-wide streams with the n (n + 2)
  columns of phi;
* a shared expert every token passes, beside the routed ones;
* the prediction module: its merge (2 d x d), one routed block, and the head a
  second time; the head's rows are parameters beside the embedding's.

Least bytes a step: parameters and Adam's moments read and written once, the
batch read once, and the one activation traffic the mechanism itself is made
of: every sublayer's n streams read once and written once each way, at the type
the configuration holds them in (``streams_bytes``; its file's ``arithmetic``
says bfloat16).  The count is of the mathematics and does not change with the
kernel that implements it.

``attention_cost`` and ``hyper_connection_cost`` give the operations and bytes
for the metrics ``latent_attention_roofline`` and ``hyper_connection_roofline``.
"""

from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict, Tuple

STREAMS_BYTES = 2       # the residual streams are held in bfloat16


def lm_sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    return {k[len("model.lm."):]: v for k, v in overrides.items() if k.startswith("model.lm.")}


def blocks(lm: Dict[str, Any]) -> Tuple[int, int]:
    """(dense blocks, routed blocks) of a step: the trunk's and the module's."""
    dense = int(lm["num_dense_layers"])
    return dense, int(lm["num_hidden_layers"]) - dense + int(lm["num_nextn_predict_layers"])


def parameters(lm: Dict[str, Any]) -> Dict[str, float]:
    d, f, fe = lm["hidden_size"], lm["intermediate_size"], lm["moe_intermediate_size"]
    h = lm["heads_held"][1]
    qk, dv = lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"], lm["v_head_dim"]
    n = lm["hc_mult"]
    mixer = (d * lm["q_lora_rank"] + lm["q_lora_rank"] * h * qk
             + d * (lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
             + lm["kv_lora_rank"] * h * (lm["qk_nope_head_dim"] + dv) + h * dv * d
             + lm["q_lora_rank"] + lm["kv_lora_rank"])
    maps = n * d * n * (n + 2) + n * d + 3 + n * (n + 2)
    routed = (lm["experts_held"][1] * 3 * d * fe + d * lm["num_experts"]
              + (lm["num_experts"] if lm["use_expert_bias"] else 0)
              + lm["n_shared_experts"] * 3 * d * fe)
    around = 2 * maps + 2 * d           # both sublayers' maps and norms
    dense_blocks, routed_blocks = blocks(lm)
    module = lm["num_nextn_predict_layers"] * (2 * d * d + 3 * d)
    tables = (1 if lm["tie_word_embeddings"] else 2) * lm["vocab_held"] * d
    return {"latent_mixer": mixer, "maps_a_sublayer": maps, "dense_ffn": 3 * d * f,
            "routed_ffn": routed, "module_merge_and_norms": module,
            "embedding_and_head": tables,
            "total": (dense_blocks * (mixer + around + 3 * d * f)
                      + routed_blocks * (mixer + around + routed) + module + tables + d)}


def forward_flops_per_token(lm: Dict[str, Any], seq_len: int) -> Dict[str, float]:
    d, f, fe = lm["hidden_size"], lm["intermediate_size"], lm["moe_intermediate_size"]
    h = lm["heads_held"][1]
    qk, dv = lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"], lm["v_head_dim"]
    n = lm["hc_mult"]
    share = lm["num_experts_per_tok"] * lm["experts_held"][1] / lm["num_experts"]
    parts = {
        "latent_projections": 2.0 * (
            d * lm["q_lora_rank"] + lm["q_lora_rank"] * h * qk
            + d * (lm["kv_lora_rank"] + lm["qk_rope_head_dim"])
            + lm["kv_lora_rank"] * h * (lm["qk_nope_head_dim"] + dv) + h * dv * d),
        "attention_scores": 2.0 * h * (qk + dv) * seq_len / 2,
        "maps": 2 * 2.0 * n * d * n * (n + 2),          # both sublayers of a block
        "dense_ffn": 2.0 * 3 * d * f,
        "router": 2.0 * d * lm["num_experts"],
        "experts": share * 2.0 * 3 * d * fe,
        "shared_expert": lm["n_shared_experts"] * 2.0 * 3 * d * fe,
        "module_merge": lm["num_nextn_predict_layers"] * 2.0 * 2 * d * d,
        "head": 2.0 * d * lm["vocab_held"],
    }
    mixer = parts["latent_projections"] + parts["attention_scores"] + parts["maps"]
    routed = parts["router"] + parts["experts"] + parts["shared_expert"]
    dense_blocks, routed_blocks = blocks(lm)
    total = (dense_blocks * (mixer + parts["dense_ffn"]) + routed_blocks * (mixer + routed)
             + parts["module_merge"] + (1 + lm["num_nextn_predict_layers"]) * parts["head"])
    return dict(parts, total=total)


def hyper_connection_cost(batch: int, seq_len: int, lm: Dict[str, Any]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of every sublayer's maps and mixes, forward and
    backward.  FLOPs: the phi products (three times the forward).  Bytes: a
    sublayer's n streams read once and written once each way, at
    ``STREAMS_BYTES`` an element; what the sublayer itself reads and writes (one
    stream wide) is its own."""
    n, d = lm["hc_mult"], lm["hidden_size"]
    sublayers = 2 * sum(blocks(lm))
    tokens = batch * seq_len
    flops = 3.0 * sublayers * tokens * 2.0 * n * d * n * (n + 2)
    return flops, sublayers * 2.0 * 2.0 * tokens * n * d * STREAMS_BYTES


def attention_cost(batch: int, seq_len: int, lm: Dict[str, Any]) -> Tuple[float, float]:
    """(FLOPs, least bytes) of the attention kernels of every block, forward
    and backward, at half the square: QK^T over the keys' width and PV over the
    values', four products backward (the recomputed scores do not count).
    Bytes: q, k (nope + rope wide), v, the output (``v_head_dim`` wide) and
    their gradients, bfloat16, once each."""
    h = lm["heads_held"][1]
    qk, dv = lm["qk_nope_head_dim"] + lm["qk_rope_head_dim"], lm["v_head_dim"]
    layers = sum(blocks(lm))
    forward = 2.0 * batch * h * (qk + dv) * seq_len * seq_len / 2
    moved = batch * seq_len * h * (2 * qk + 2 * dv) * 2.0
    return layers * 3.0 * forward, layers * 2.0 * moved


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
        # float32 masters and both Adam moments read and written, the batch read
        # once, the streams each way
        "min_bytes_per_step": float(2 * count * 4 + 2 * count * 8 + batch_bytes
                                    + hyper_connection_cost(batch, seq_len, lm)[1]),
        "parameters": float(count),
    }


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

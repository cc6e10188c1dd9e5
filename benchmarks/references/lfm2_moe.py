"""Plain reference of one chip's share of an LFM2-MoE decoder's training loss
(model_type ``lfm2_moe``; https://huggingface.co/LiquidAI/LFM2-24B-A2B):
gated short convolutions and rotary grouped-query attention as mixers, a
SwiGLU and sigmoid-routed top-k experts as feed-forward layers, RMSNorm, tied
output head, next-token cross-entropy.

Straightforward ``jax.numpy`` in float32 with every product at precision
``highest``; no kernels, no sort, no grouped product, no flax, nothing
imported from ``rt1_tpu``.  The weights come in as the nested dict that
benchmarks/weights.py made from the seed.  ``prec`` other than ``"highest"``
rounds the operands of every matrix product of the compute path, forward and
backward, to that type (the control of benchmarks/check.py); the router's
product stays float32 there too, as the configuration states it.

    block:  h = x + Mixer(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    conv:   [B, C, u] = split3(x W_in); c_t = sum_j k_j * (B*u)_{t-j}; out = (C*c) W_out
    attn:   q, k RMSNorm over the head, rotate-half rotary, causal softmax(q k^T / sqrt(d)) v, W_o
    dense:  W_2(silu(x W_1) * x W_3)
    routed: s = sigmoid(x W_g); I = top_k(s + b); w_i = s_i / (sum_{j in I} s_j + 1e-6) * scale;
            out = sum_{i in I, i held} w_i E_i(x)
    loss:   mean over the targets that count of -log softmax(x E^T)[target]

The experts are a loop over the experts held, each applied to every token and
weighted by the token's normalised score for it (0 where it was not selected).

Departures from the published description, all the configuration's and stated
in its file: only the experts in ``experts_held`` contribute (the chip's
share; what the absent ones would add is left out); the vocabulary is the
slice held, for ids, logits and loss; input and output embeddings are tied;
the expert bias is a constant (its balancing update is not in the config);
documents attend and convolve across packing boundaries.  To fit beside the
optimizer state at the published widths, attention runs one block of queries
at a time against all keys (masked), the loss one block of tokens at a time,
each layer, block and expert under ``jax.checkpoint``.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.references.rt1 import HI, product

IGNORE = -1
QUERY_BLOCK = 512       # queries of one attention block (against all keys)
TOKEN_BLOCK = 4096      # tokens of one block of the output head and the loss


def sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's values."""
    g = lambda k: overrides["model.lm." + k]  # noqa: E731
    return {
        "layer_types": list(g("layer_types")), "dense_layers": g("num_dense_layers"),
        "heads": g("num_attention_heads"), "kv_heads": g("num_key_value_heads"),
        "head_dim": g("head_dim"), "top_k": g("num_experts_per_tok"),
        "experts_held": list(g("experts_held")), "theta": g("rope_theta"),
        "eps": g("norm_eps"), "norm_topk": g("norm_topk_prob"),
        "scaling": g("routed_scaling_factor"), "expert_bias": g("use_expert_bias"),
        "taps": g("conv_L_cache"),
        "query_block": QUERY_BLOCK, "token_block": TOKEN_BLOCK,
    }


def mm(a, b, prec):
    return product(lambda x, y: jnp.matmul(x, y, precision=HI), a, b, prec)


def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def rotary(x, theta):
    """x: (b, s, h, d); rotate-half."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def short_conv(x, p, sz, prec):
    d = x.shape[-1]
    bcu = mm(x, p["in_proj"]["kernel"], prec)
    gate_b, gate_c, u = bcu[..., :d], bcu[..., d:2 * d], bcu[..., 2 * d:]
    v = gate_b * u
    c = jnp.zeros_like(v)
    for j in range(sz["taps"]):     # tap j weighs the input j positions back
        shifted = v if j == 0 else jnp.pad(v, ((0, 0), (j, 0), (0, 0)))[:, :-j]
        c = c + p["kernel"][j] * shifted
    return mm(gate_c * c, p["out_proj"]["kernel"], prec)


def attention(x, p, sz, prec):
    b, s, _ = x.shape
    h, kvh, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    q = mm(x, p["q_proj"]["kernel"], prec).reshape(b, s, h, d)
    k = mm(x, p["k_proj"]["kernel"], prec).reshape(b, s, kvh, d)
    v = mm(x, p["v_proj"]["kernel"], prec).reshape(b, s, kvh, d)
    q = rotary(rms_norm(q, p["q_norm"], sz["eps"]), sz["theta"])
    k = rotary(rms_norm(k, p["k_norm"], sz["eps"]), sz["theta"])
    # each KV head serves heads / kv_heads query heads
    k = jnp.repeat(k, h // kvh, axis=2)
    v = jnp.repeat(v, h // kvh, axis=2)
    block = min(sz["query_block"], s)
    assert s % block == 0, (s, block)

    @jax.checkpoint
    def one(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=1)
        scores = product(lambda a, c: jnp.einsum("bqhd,bkhd->bhqk", a, c, precision=HI),
                         qb, k, prec) / jnp.sqrt(jnp.float32(d))
        q_pos = start + jnp.arange(block)[:, None]
        scores = jnp.where(q_pos >= jnp.arange(s)[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        return product(lambda a, c: jnp.einsum("bhqk,bkhd->bqhd", a, c, precision=HI),
                       probs, v, prec)

    out = lax.map(one, jnp.arange(0, s, block))             # (blocks, b, block, h, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, s, h * d)
    return mm(out, p["o_proj"]["kernel"], prec)


def swiglu(x, w1, w3, w2, prec):
    return mm(jax.nn.silu(mm(x, w1, prec)) * mm(x, w3, prec), w2, prec)


def route(x, p, sz):
    """(indices, weights): (tokens, top_k) each, over ALL the router's experts."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=HI))
    select = scores + lax.stop_gradient(p["expert_bias"]["kernel"]) if sz["expert_bias"] else scores
    _, idx = lax.top_k(select, sz["top_k"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if sz["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return idx, weights * sz["scaling"]


def routed_ffn(x, p, sz, prec, held=None):
    """The part of the routed layer's output that the experts ``held``
    (first, count) give; the stacks in ``p`` are theirs."""
    first, count = sz["experts_held"] if held is None else held
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    idx, weights = route(flat, p, sz)
    stacks = p["experts"]

    @jax.checkpoint
    def one(out, xs):
        w1, w3, w2, expert = xs
        weight = jnp.sum(jnp.where(idx == expert, weights, 0.0), axis=-1)
        return out + weight[:, None] * swiglu(flat, w1, w3, w2, prec), None

    out, _ = lax.scan(one, jnp.zeros_like(flat), (
        stacks["w1"]["kernel"], stacks["w3"]["kernel"], stacks["w2"]["kernel"],
        first + jnp.arange(count)))
    return out.reshape(shape)


def block(x, p, mixer, ffn, sz, prec):
    normed = rms_norm(x, p["mixer_norm"], sz["eps"])
    mix = short_conv if mixer == "conv" else attention
    h = x + mix(normed, p["mixer"], sz, prec)
    normed = rms_norm(h, p["ffn_norm"], sz["eps"])
    if ffn == "dense":
        f = p["ffn"]
        return h + swiglu(normed, f["w1"]["kernel"], f["w3"]["kernel"], f["w2"]["kernel"], prec)
    return h + routed_ffn(normed, p["ffn"], sz, prec)


def hidden(params, tokens, sz, prec):
    x = params["embed"]["embedding"][tokens]
    for i, mixer in enumerate(sz["layer_types"]):
        ffn = "dense" if i < sz["dense_layers"] else "moe"
        x = jax.checkpoint(block, static_argnums=(2, 3, 4, 5))(
            x, params[f"layer_{i}"], mixer, ffn, _frozen(sz), prec)
    return rms_norm(x, params["final_norm"], sz["eps"])


class _frozen(dict):
    """The sizes as a static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def logits_fn(params, tokens, sz, prec="highest"):
    x = hidden(params, jnp.asarray(tokens), sz, prec)
    return mm(x, params["embed"]["embedding"].T, prec)


def selected_experts(params, tokens, sz):
    """Per routed layer, the (tokens, top_k) experts each token selects."""
    x = params["embed"]["embedding"][jnp.asarray(tokens)]
    out = []
    for i, mixer in enumerate(sz["layer_types"]):
        p = params[f"layer_{i}"]
        if i >= sz["dense_layers"]:
            mix = short_conv if mixer == "conv" else attention
            h = x + mix(rms_norm(x, p["mixer_norm"], sz["eps"]), p["mixer"], sz, "highest")
            normed = rms_norm(h, p["ffn_norm"], sz["eps"])
            out.append(route(normed.reshape(-1, normed.shape[-1]), p["ffn"], sz)[0])
        x = block(x, p, mixer, "dense" if i < sz["dense_layers"] else "moe", sz, "highest")
    return out


def loss_fn(params, batch_stats, batch, step_key, sz, prec="highest"):
    """(loss, batch_stats): the training loss of one batch."""
    del step_key        # nothing in the step is random
    observations, actions = batch
    tokens = jnp.asarray(observations["tokens"])
    targets = jnp.asarray(actions["targets"])
    x = hidden(params, tokens, sz, prec)
    flat = x.reshape(-1, x.shape[-1])
    flat_targets = targets.reshape(-1)
    n = flat.shape[0]
    size = min(sz["token_block"], n)
    assert n % size == 0, (n, size)
    head = params["embed"]["embedding"].T

    @jax.checkpoint
    def one(total, start):
        xb = lax.dynamic_slice_in_dim(flat, start, size, axis=0)
        tb = lax.dynamic_slice_in_dim(flat_targets, start, size, axis=0)
        logp = jax.nn.log_softmax(mm(xb, head, prec), axis=-1)
        ce = -jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(tb != IGNORE, ce, 0.0)), None

    total, _ = lax.scan(one, jnp.zeros((), jnp.float32), jnp.arange(0, n, size))
    counted = jnp.maximum(jnp.sum(flat_targets != IGNORE), 1)
    return total / counted, batch_stats

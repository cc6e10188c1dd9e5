"""Plain reference of one chip's share of a Mellum decoder's training loss
(model_type ``mellum``; https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct):
sliding-window and full causal grouped-query attention in the layer order the
configuration lists, a rotary rule per kind of layer (YaRN on the full layers,
the default on the sliding ones), softmax-routed top-k experts in every layer,
RMSNorm, an output head of its own (untied), next-token cross-entropy.

Straightforward ``jax.numpy`` in float32 with every product at precision
``highest``; no kernels, no sort, no grouped product, no flax, nothing imported
from ``rt1_tpu``.  The weights come in as the nested dict that
benchmarks/weights.py made from the seed.

    block:   h = x + Attn_l(RMSNorm(x));  y = h + MoE(RMSNorm(h))
    attn:    q, k RMSNorm over the head, rotate-half rotary by the layer's rule,
             softmax(q k^T / sqrt(d) + mask) v, each KV head serving heads / kv_heads
             query heads, W_o
      full:    mask j <= i; YaRN: c(r) = D ln(L0 / (2 pi r)) / (2 ln theta),
               lo = max(floor(c(beta_fast)), 0), hi = min(ceil(c(beta_slow)), D - 1),
               ramp_j = clip((j - lo) / (hi - lo), 0, 1), f_j = theta^(-2j/D),
               inv_freq_j = f_j / factor * ramp_j + f_j (1 - ramp_j);
               cos and sin times attention_factor
      sliding: mask i - window < j <= i; inv_freq_j = theta^(-2j/D), no factor
    routed:  p = softmax(x W_g) over all the router's experts; I = top_k(p);
             w_i = p_i / sum_{j in I} p_j; out = sum_{i in I, i held} w_i E_i(x),
             E(x) = W_2(silu(x W_1) * x W_3)
    loss:    mean over the targets that count of -log softmax(x W_head^T)[target]

The experts are a loop over the experts held, each applied to every token and
weighted by the token's normalised score for it (0 where it was not selected).
The masks are made from the positions of the queries and keys of a block.

``prec`` names what is computed.  ``"highest"``: the above.  A type
(``"int8"``): the operands of every matrix product of the compute path rounded
to it, forward and backward (the control of benchmarks/check.py; the router's
product stays float32 there too, as the configuration states it).  And two
controls that take one of this configuration's mechanisms away, at ``highest``:
``"no_window"`` (the sliding layers see every key at or before the query) and
``"default_rotary"`` (the full layers take the default rotary rule, no YaRN, no
factor): a comparison that passes either does not see the mechanism.

Departures from the published description, all the configuration's and stated
in its file: only the experts in ``experts_held`` contribute (the chip's share;
what the absent ones would add is left out); the vocabulary is the slice held,
for ids, logits and loss, in the embedding and in the head; q and k take an
RMSNorm over the head dimension; the multi-token-prediction head is left out;
documents attend across packing boundaries.  To fit beside the optimizer state
at the published widths (benchmarks/check.py::follow holds six copies of the
parameters' size while it steps: 14.3 of the chip's 16.9 GB at this
configuration), attention runs one block of queries at a time, from the
block's rows of x to its rows of the layer's output (a sliding layer against
the keys its block's windows can reach, a full layer against all of them,
masked), the loss one block of tokens at a time, each layer, each half of a
layer, each block and each expert under ``jax.checkpoint``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.references.rt1 import HI, product

IGNORE = -1
QUERY_BLOCK = 64        # queries of one attention block
TOKEN_BLOCK = 1024      # tokens of one block of the output head and the loss


def sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's values."""
    g = lambda k: overrides["model.lm." + k]  # noqa: E731
    kinds = sorted(set(g("layer_types")))
    rope = {
        kind: {k[len(f"model.lm.rope_parameters.{kind}."):]: v for k, v in overrides.items()
               if k.startswith(f"model.lm.rope_parameters.{kind}.")}
        for kind in kinds}
    return {
        "layer_types": list(g("layer_types")),
        "heads": g("num_attention_heads"), "kv_heads": g("num_key_value_heads"),
        "head_dim": g("head_dim"), "top_k": g("num_experts_per_tok"),
        "experts_held": list(g("experts_held")), "window": g("sliding_window"),
        "rope": rope, "eps": g("rms_norm_eps"), "norm_topk": g("norm_topk_prob"),
        "query_block": QUERY_BLOCK, "token_block": TOKEN_BLOCK,
    }


def controlled(sz: Dict[str, Any], prec: str):
    """(sizes, precision) of what ``prec`` names."""
    if prec == "no_window":
        return dict(sz, window=None), "highest"
    if prec == "default_rotary":
        full = sz["rope"]["full_attention"]
        rope = dict(sz["rope"], full_attention={"rope_type": "default",
                                                "rope_theta": full["rope_theta"]})
        return dict(sz, rope=rope), "highest"
    return sz, prec


def mm(a, b, prec):
    return product(lambda x, y: jnp.matmul(x, y, precision=HI), a, b, prec)


def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def inverse_frequencies(rule: Dict[str, Any], d: int):
    """(inv_freq (d / 2,), the factor on cos and sin) of one ``rope_parameters`` entry."""
    j = jnp.arange(d // 2, dtype=jnp.float32)
    f = jnp.float32(rule["rope_theta"]) ** (-2.0 * j / d)
    if rule["rope_type"] == "default":
        return f, 1.0
    assert rule["rope_type"] == "yarn", rule

    def c(turns):
        return (d * math.log(rule["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(rule["rope_theta"])))

    lo = max(math.floor(c(rule["beta_fast"])), 0)
    hi = min(math.ceil(c(rule["beta_slow"])), d - 1)
    ramp = jnp.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return f / rule["factor"] * ramp + f * (1.0 - ramp), rule["attention_factor"]


def rotary(x, rule, positions):
    """x: (b, n, h, d) at ``positions`` (n,); rotate-half."""
    d = x.shape[-1]
    inv_freq, factor = inverse_frequencies(rule, d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = factor * jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[None, :, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(x, p, kind, sz, prec):
    b, s, _ = x.shape
    h, kvh, d = sz["heads"], sz["kv_heads"], sz["head_dim"]
    window = sz["window"] if kind == "sliding_attention" else None
    rule = sz["rope"][kind]
    k = mm(x, p["k_proj"]["kernel"], prec).reshape(b, s, kvh, d)
    v = mm(x, p["v_proj"]["kernel"], prec).reshape(b, s, kvh, d)
    k = rotary(rms_norm(k, p["k_norm"], sz["eps"]), rule, jnp.arange(s))
    block = min(sz["query_block"], s)
    assert s % block == 0, (s, block)
    # the keys one block of queries can see: all of them, or under a window the
    # last block + window - 1 up to the block's end (a slice that stays inside
    # the sequence; the mask below is made from positions either way)
    span = s if window is None else min(s, block + window - 1)

    @jax.checkpoint
    def one(start):
        """The layer's output for one block of queries, from the block's rows
        of x: their q (32 heads of 128 for all 16,384 positions never exists
        at once), the scores against the keys, and W_o."""
        i = start + jnp.arange(block)
        first = jnp.clip(start + block - span, 0, s - span)
        j = first + jnp.arange(span)
        q = mm(lax.dynamic_slice_in_dim(x, start, block, axis=1), p["q_proj"]["kernel"], prec)
        q = rotary(rms_norm(q.reshape(b, block, h, d), p["q_norm"], sz["eps"]), rule, i)
        q = q.reshape(b, block, kvh, h // kvh, d)   # KV head n serves query heads n g .. n g + g - 1
        kb = lax.dynamic_slice_in_dim(k, first, span, axis=1)
        vb = lax.dynamic_slice_in_dim(v, first, span, axis=1)
        scores = product(lambda a, c: jnp.einsum("bqhgd,bkhd->bhgqk", a, c, precision=HI),
                         q, kb, prec) / jnp.sqrt(jnp.float32(d))
        mask = j[None, :] <= i[:, None]
        if window is not None:
            mask = mask & (i[:, None] - window < j[None, :])
        probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
        out = product(lambda a, c: jnp.einsum("bhgqk,bkhd->bqhgd", a, c, precision=HI),
                      probs, vb, prec)
        return mm(out.reshape(b, block, h * d), p["o_proj"]["kernel"], prec)

    out = lax.map(one, jnp.arange(0, s, block))         # (blocks, b, block, hidden)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def swiglu(x, w1, w3, w2, prec):
    return mm(jax.nn.silu(mm(x, w1, prec)) * mm(x, w3, prec), w2, prec)


def route(x, p, sz):
    """(indices, weights): (tokens, top_k) each, over ALL the router's experts."""
    scores = jax.nn.softmax(jnp.matmul(x, p["router"]["kernel"], precision=HI), axis=-1)
    weights, idx = lax.top_k(scores, sz["top_k"])
    if sz["norm_topk"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return idx, weights


def routed_ffn(x, p, sz, prec, held=None):
    """The part of the routed layer's output that the experts ``held``
    (first, count) give; the stacks in ``p`` are theirs."""
    first, count = sz["experts_held"] if held is None else held
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    idx, weights = route(flat, p, sz)
    stacks = p["experts"]

    @jax.checkpoint
    def expert_part(xs):        # nothing of it is kept for the way back but its arguments
        w1, w3, w2, expert = xs
        weight = jnp.sum(jnp.where(idx == expert, weights, 0.0), axis=-1)
        return weight[:, None] * swiglu(flat, w1, w3, w2, prec)

    # the running sum is not an argument of the checkpointed part, so the way
    # back keeps no copy of it per expert
    out, _ = lax.scan(lambda out, xs: (out + expert_part(xs), None), jnp.zeros_like(flat), (
        stacks["w1"]["kernel"], stacks["w3"]["kernel"], stacks["w2"]["kernel"],
        first + jnp.arange(count)))
    return out.reshape(shape)


def block(x, p, kind, sz, prec):
    # each half under its own checkpoint: the way back of a block makes one
    # half's forward again at a time, not both
    mix = jax.checkpoint(attention, static_argnums=(2, 3, 4))
    ffn = jax.checkpoint(routed_ffn, static_argnums=(2, 3))
    h = x + mix(rms_norm(x, p["mixer_norm"], sz["eps"]), p["mixer"], kind, _frozen(sz), prec)
    return h + ffn(rms_norm(h, p["ffn_norm"], sz["eps"]), p["ffn"], _frozen(sz), prec)


class _frozen(dict):
    """The sizes as a static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def hidden(params, tokens, sz, prec):
    x = params["embed"]["embedding"][tokens]
    for i, kind in enumerate(sz["layer_types"]):
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(
            x, params[f"layer_{i}"], kind, _frozen(sz), prec)
    return rms_norm(x, params["final_norm"], sz["eps"])


def logits_fn(params, tokens, sz, prec="highest"):
    sz, prec = controlled(sz, prec)
    x = hidden(params, jnp.asarray(tokens), sz, prec)
    return mm(x, params["lm_head"]["embedding"].T, prec)


def selected_experts(params, tokens, sz):
    """Per layer, the (tokens, top_k) experts each token selects."""
    x = params["embed"]["embedding"][jnp.asarray(tokens)]
    out = []
    for i, kind in enumerate(sz["layer_types"]):
        p = params[f"layer_{i}"]
        h = x + attention(rms_norm(x, p["mixer_norm"], sz["eps"]), p["mixer"], kind, sz, "highest")
        normed = rms_norm(h, p["ffn_norm"], sz["eps"])
        out.append(route(normed.reshape(-1, normed.shape[-1]), p["ffn"], sz)[0])
        x = block(x, p, kind, sz, "highest")
    return out


def loss_fn(params, batch_stats, batch, step_key, sz, prec="highest"):
    """(loss, batch_stats): the training loss of one batch."""
    del step_key        # nothing in the step is random
    sz, prec = controlled(sz, prec)
    observations, actions = batch
    tokens = jnp.asarray(observations["tokens"])
    targets = jnp.asarray(actions["targets"])
    x = hidden(params, tokens, sz, prec)
    flat = x.reshape(-1, x.shape[-1])
    flat_targets = targets.reshape(-1)
    n = flat.shape[0]
    size = min(sz["token_block"], n)
    assert n % size == 0, (n, size)
    head = params["lm_head"]["embedding"].T

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

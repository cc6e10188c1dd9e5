"""Plain reference of one chip's share of a Xing4.0 decoder's training loss
(model_type ``xing4_0``; https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B):
multi-head latent attention with one rotary key shared by every head, four
residual streams a token mixed by maps of which one is made doubly stochastic
by Sinkhorn iterations, a leading dense SwiGLU layer, then sigmoid-routed top-k
experts beside a shared expert, an untied head, and one multi-token-prediction
module with a second, weighted loss term.

Straightforward ``jax.numpy`` in float32 with every product at precision
``highest``; no kernels, no sort, no grouped product, no flax, nothing imported
from ``rt1_tpu``.  The weights come in as the nested dict that
benchmarks/weights.py made from the seed.  n = ``hc_mult``, X in R^{n x d} a token.

    embed:   X_j = Emb(t) for every stream j
    sublayer F (a block's mixer, then its FFN), each with maps of its own:
             x~ = RMSNorm(vec(X))                            (n d wide, stream-major)
             [H~_pre | H~_post | vec(H~_res)] = alpha . (x~ phi) + b   (n, n, n x n; one alpha a map)
             H_pre = sigmoid(H~_pre);  H_post = 2 sigmoid(H~_post)
             H_res = Sinkhorn(exp(clamp(H~_res + 2 I, -30, 30))): 20 rounds of every row,
                     then every column, divided by its sum + hc_eps
             X <- H_res X + H_post^T F(RMSNorm(H_pre X))
             (b_res = 2 I + the bias leaf: a leaf near zero is a diagonally dominant H_res)
    mixer:   c_q = RMSNorm(x W_qa);  [q_nope | q_rope] a head = c_q W_qb
             [c_kv | k_rope] = x W_kva;  c_kv = RMSNorm(c_kv);  [k_nope | v] a head = c_kv W_kvb
             q = [q_nope | rot(q_rope)];  k = [k_nope | rot(k_rope)], k_rope shared by the heads
             out = concat_heads(softmax(q k^T (128 + 64)^-0.5 m^2 + causal mask) v) W_o
             rot: rotate-half, YaRN over the rotary part's D / 2 pairs:
               c(r) = D ln(L0 / (2 pi r)) / (2 ln theta), lo = max(floor(c(beta_fast)), 0),
               hi = min(ceil(c(beta_slow)), D - 1), ramp_j = clip((j - lo) / (hi - lo), 0, 1),
               f_j = theta^(-2j/D), inv_freq_j = f_j / factor * ramp_j + f_j (1 - ramp_j);
               mscale(f, a) = 0.1 a ln f + 1;  m = mscale(factor, mscale_all_dim);
               cos and sin times mscale(factor, mscale) / m
    dense:   W_2(silu(x W_1) * x W_3)
    routed:  s = sigmoid(x W_g);  I = top_k(s + bias);  w_i = s_i / (sum_{j in I} s_j + 1e-6) x scaling
             out = sum_{i in I, i held} w_i E_i(x) + S(x),  E, S SwiGLUs of the experts' width
    trunk:   h = sum_j X_j after the last block;  logits = RMSNorm(h) W_head^T
    mtp:     h'_i = M [RMSNorm(h_i) ; RMSNorm(Emb(t_{i+1}))];  X_j = h' for every j;  one routed
             block;  logits' = RMSNorm(sum_j X_j) W_head^T, which predict t_{i+2}
    loss:    L_next + lambda L_mtp, each the mean over its own counted targets of
             -log softmax(logits)[target]; an MTP target counts where t_{i+1} and t_{i+2} both do

The experts are a loop over the experts held, each applied to every token and
weighted by the token's normalised score for it (0 where it was not selected).

``prec`` names what is computed.  ``"highest"``: the above.  A type
(``"int8"``): the operands of every matrix product of the compute path rounded
to it, forward and backward (the control of benchmarks/check.py; the router's
product and the streams' mixing stay float32 there too, as the configuration
states them).  And three controls that take one of this configuration's
mechanisms away, at ``highest``: ``"plain_residual"`` (H_res = I, H_pre = 1/n,
H_post = 1: the streams stay copies of one plain residual path),
``"no_yarn_scale"`` (the default rotary rule, no factor, m = 1) and ``"no_mtp"``
(lambda = 0): a comparison that passes one of them does not see the mechanism.

Departures from the published description, all the configuration's and stated
in its file: only the experts in ``experts_held`` and the heads in
``heads_held`` contribute (the chip's share; what the absent ones would add to
a sum is left out); the vocabulary is the slice held, for ids, logits and both
losses, in the embedding and in the head; documents attend across packing
boundaries.  To fit beside the four copies of the parameters' size that
benchmarks/check.py::follow holds while it steps (13.5 of the chip's 16.9 GB at
this configuration; a float32 block boundary is 470 MB), the blocks are
checkpointed in nested segments (a segment of blocks, each block), a block runs
a chunk of tokens at a time (everything but the keys and values is a token's
own: ``block`` says how), the loss one block of tokens at a time, each chunk
and each expert under ``jax.checkpoint``.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.references.rt1 import HI, product

IGNORE = -1
TOKEN_CHUNK = 256       # tokens of one chunk of a block
QUERY_BLOCK = 256       # queries of one attention block
SEGMENT = 3             # blocks of one checkpointed segment of the trunk
TOKEN_BLOCK = 1024      # tokens of one block of the output head and the loss
RES_START = 2.0         # b_res = RES_START I + the bias leaf
CONTROLS = ("plain_residual", "no_yarn_scale", "no_mtp")


def sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's values."""
    g = lambda k: overrides["model.lm." + k]  # noqa: E731
    prefix = "model.lm.rope_scaling."
    return {
        "layers": g("num_hidden_layers"), "dense_layers": g("num_dense_layers"),
        "heads_held": list(g("heads_held")), "experts_held": list(g("experts_held")),
        "q_rank": g("q_lora_rank"), "kv_rank": g("kv_lora_rank"),
        "nope": g("qk_nope_head_dim"), "rope": g("qk_rope_head_dim"), "v_dim": g("v_head_dim"),
        "theta": g("rope_theta"),
        "rope_scaling": {k[len(prefix):]: v for k, v in overrides.items()
                         if k.startswith(prefix)},
        "top_k": g("num_experts_per_tok"), "norm_topk": g("norm_topk_prob"),
        "scaling": g("routed_scaling_factor"), "expert_bias": g("use_expert_bias"),
        "shared": g("n_shared_experts"), "eps": g("rms_norm_eps"),
        "streams": g("hc_mult"), "sinkhorn_iters": g("hc_sinkhorn_iters"),
        "hc_eps": g("hc_eps"),
        "clamp": [g("mhc_h_res_clamp_min"), g("mhc_h_res_clamp_max")],
        "mtp": g("num_nextn_predict_layers"), "mtp_weight": g("mtp_loss_weight"),
        "plain_residual": False,
        "token_chunk": TOKEN_CHUNK, "query_block": QUERY_BLOCK, "token_block": TOKEN_BLOCK,
        "segment": SEGMENT,
    }


def controlled(sz: Dict[str, Any], prec: str):
    """(sizes, precision) of what ``prec`` names."""
    if prec == "plain_residual":
        return dict(sz, plain_residual=True), "highest"
    if prec == "no_yarn_scale":
        return dict(sz, rope_scaling={}), "highest"
    if prec == "no_mtp":
        return dict(sz, mtp_weight=0.0), "highest"
    return sz, prec


class _frozen(dict):
    """The sizes as a static argument of ``jax.checkpoint``."""

    def __hash__(self):
        return hash(repr(sorted(self.items())))


def mm(a, b, prec):
    return product(lambda x, y: jnp.matmul(x, y, precision=HI), a, b, prec)


def rms_norm(x, p, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


# ------------------------------------------------------------------ rotary

def mscale(factor: float, weight: float) -> float:
    return 0.1 * weight * math.log(factor) + 1.0 if factor > 1 else 1.0


def inverse_frequencies(theta: float, scaling: Dict[str, Any], d: int):
    """(inv_freq (d / 2,), the factor on cos and sin, the factor on the softmax
    scale) of a ``rope_theta`` and a ``rope_scaling`` group (empty: none)."""
    j = jnp.arange(d // 2, dtype=jnp.float32)
    f = jnp.float32(theta) ** (-2.0 * j / d)
    if not scaling:
        return f, 1.0, 1.0
    assert scaling["type"] == "yarn", scaling
    factor = float(scaling["factor"])

    def c(turns):
        return (d * math.log(scaling["original_max_position_embeddings"] / (2 * math.pi * turns))
                / (2 * math.log(theta)))

    lo = max(math.floor(c(scaling["beta_fast"])), 0)
    hi = min(math.ceil(c(scaling["beta_slow"])), d - 1)
    ramp = jnp.clip((j - lo) / (hi - lo), 0.0, 1.0)
    m = mscale(factor, scaling["mscale_all_dim"])
    return f / factor * ramp + f * (1.0 - ramp), mscale(factor, scaling["mscale"]) / m, m * m


def rotary(x, sz, positions):
    """x: (b, n, h, d) at ``positions`` (n,); rotate-half."""
    d = x.shape[-1]
    inv_freq, factor, _ = inverse_frequencies(sz["theta"], sz["rope_scaling"], d)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = factor * jnp.concatenate([jnp.cos(angles), jnp.cos(angles)], -1)[None, :, None, :]
    sin = factor * jnp.concatenate([jnp.sin(angles), jnp.sin(angles)], -1)[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


# ------------------------------------------------------------------ sublayers

def kv_latents(x, p, sz, prec):
    """[RMSNorm(c_kv) | k_rope before its rotary] of the rows of x: (b, rows,
    kv_lora_rank + rope).  A row's latents depend on that row alone."""
    rank = sz["kv_rank"]
    kv = mm(x, p["kv_a_proj"]["kernel"], prec)
    return jnp.concatenate(
        [rms_norm(kv[..., :rank], p["kv_a_layernorm"], sz["eps"]), kv[..., rank:]], -1)


def keys_values(latents, p, sz, prec, h):
    """(k (b, s, h, nope + rope), v (b, s, h, v_dim)) of every position from
    its latents: the up-projection a head, and the one rotary key, which every
    head shares."""
    b, s, _ = latents.shape
    nope, rope, dv, rank = sz["nope"], sz["rope"], sz["v_dim"], sz["kv_rank"]
    k_rope = rotary(latents[..., rank:][:, :, None, :], sz, jnp.arange(s))
    up = mm(latents[..., :rank], p["kv_b_proj"]["kernel"], prec).reshape(b, s, h, nope + dv)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(k_rope, (b, s, h, rope))], -1)
    return k, up[..., nope:]


def attend(x, first, k, v, p, sz, prec, h):
    """The layer's output for the rows x (b, rows, hidden), which stand at
    positions ``first`` .., against the keys and values of every position at or
    before each: a block of queries at a time, each block under
    ``jax.checkpoint``."""
    b, rows, _ = x.shape
    nope, rope, dv = sz["nope"], sz["rope"], sz["v_dim"]
    scale = (nope + rope) ** -0.5 * inverse_frequencies(sz["theta"], sz["rope_scaling"], rope)[2]
    block = min(sz["query_block"], rows)
    assert rows % block == 0, (rows, block)

    @jax.checkpoint
    def one(start):
        positions = first + start + jnp.arange(block)
        c_q = rms_norm(mm(lax.dynamic_slice_in_dim(x, start, block, axis=1),
                          p["q_a_proj"]["kernel"], prec), p["q_a_layernorm"], sz["eps"])
        q = mm(c_q, p["q_b_proj"]["kernel"], prec).reshape(b, block, h, nope + rope)
        q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], sz, positions)], -1)
        scores = product(lambda a, c: jnp.einsum("bqhd,bkhd->bhqk", a, c, precision=HI),
                         q, k, prec) * scale
        mask = jnp.arange(k.shape[1])[None, :] <= positions[:, None]
        out = product(lambda a, c: jnp.einsum("bhqk,bkhd->bqhd", a, c, precision=HI),
                      jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1), v, prec)
        return mm(out.reshape(b, block, h * dv), p["o_proj"]["kernel"], prec)

    out = lax.map(one, jnp.arange(0, rows, block))         # (blocks, b, block, hidden)
    return jnp.moveaxis(out, 0, 1).reshape(b, rows, -1)


def latent_attention(x, p, sz, prec, heads=None):
    """The part of the layer's output that the heads ``heads`` (first, count)
    give; ``W_qb``, ``W_kvb`` and ``W_o`` in ``p`` are theirs."""
    h = (sz["heads_held"] if heads is None else heads)[1]
    k, v = keys_values(kv_latents(x, p, sz, prec), p, sz, prec, h)
    return attend(x, 0, k, v, p, sz, prec, h)


def swiglu(x, p, prec):
    return mm(jax.nn.silu(mm(x, p["w1"]["kernel"], prec)) * mm(x, p["w3"]["kernel"], prec),
              p["w2"]["kernel"], prec)


def route(x, p, sz):
    """(indices, weights): (tokens, top_k) each, over ALL the router's experts."""
    scores = jax.nn.sigmoid(jnp.matmul(x, p["router"]["kernel"], precision=HI))
    select = scores + lax.stop_gradient(p["expert_bias"]["kernel"]) if sz["expert_bias"] else scores
    _, idx = lax.top_k(select, sz["top_k"])
    weights = jnp.take_along_axis(scores, idx, axis=-1)
    if sz["norm_topk"]:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return idx, weights * sz["scaling"]


def routed_experts(x, p, sz, prec, held=None):
    """The part of the routed experts' sum that the experts ``held`` (first,
    count) give; the stacks in ``p`` are theirs.  No shared expert."""
    first, count = sz["experts_held"] if held is None else held
    shape = x.shape
    flat = x.reshape(-1, shape[-1])
    idx, weights = route(flat, p, sz)
    stacks = p["experts"]

    @jax.checkpoint
    def expert_part(xs):        # nothing of it is kept for the way back but its arguments
        w1, w3, w2, expert = xs
        weight = jnp.sum(jnp.where(idx == expert, weights, 0.0), axis=-1)
        hidden = jax.nn.silu(mm(flat, w1, prec)) * mm(flat, w3, prec)
        return weight[:, None] * mm(hidden, w2, prec)

    out, _ = lax.scan(lambda out, xs: (out + expert_part(xs), None), jnp.zeros_like(flat), (
        stacks["w1"]["kernel"], stacks["w3"]["kernel"], stacks["w2"]["kernel"],
        first + jnp.arange(count)))
    return out.reshape(shape)


def routed_ffn(x, p, prec, sz):
    out = routed_experts(x, p, sz, prec)
    return out + swiglu(x, p["shared_expert"], prec) if sz["shared"] else out


# ------------------------------------------------------------------ the streams

def sinkhorn(logits, iters: int, eps: float, clamp):
    """(..., n, n): ``exp(clamp(logits))`` made doubly stochastic, a plain loop."""
    m = jnp.exp(jnp.clip(logits, clamp[0], clamp[1]))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)      # every row by its sum
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)      # every column by its sum
    return m


def maps(X, p, sz, prec):
    """(H_pre (b, s, n), H_post (b, s, n), H_res (b, s, n, n)) of X (b, s, n, d)."""
    b, s, n, d = X.shape
    if sz["plain_residual"]:
        return (jnp.full((b, s, n), 1.0 / n), jnp.ones((b, s, n)),
                jnp.broadcast_to(jnp.eye(n), (b, s, n, n)))
    normed = rms_norm(X.reshape(b, s, n * d), p["norm"], sz["eps"])
    raw = mm(normed, p["phi"]["kernel"], prec)
    alpha, bias = p["alpha"]["scale"], p["maps_bias"]["bias"]
    pre = alpha[0] * raw[..., :n] + bias[:n]
    post = alpha[1] * raw[..., n:2 * n] + bias[n:2 * n]
    res = (alpha[2] * raw[..., 2 * n:] + bias[2 * n:]).reshape(b, s, n, n) + RES_START * jnp.eye(n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(res, sz["sinkhorn_iters"], sz["hc_eps"], sz["clamp"]))


def mix_in(X, p_maps, p_norm, sz, prec):
    """``RMSNorm(H_pre X)``: a sublayer's input."""
    h_pre, _, _ = maps(X, p_maps, sz, prec)
    x = sum(h_pre[..., j, None] * X[:, :, j] for j in range(X.shape[2]))
    return rms_norm(x, p_norm, sz["eps"])


def write_back(X, p_maps, out, sz, prec):
    """``H_res X + H_post^T out``: the streams after a sublayer whose output is ``out``."""
    _, h_post, h_res = maps(X, p_maps, sz, prec)
    mixed = sum(h_res[..., :, j, None] * X[:, :, None, j] for j in range(X.shape[2]))
    return mixed + h_post[..., None] * out[:, :, None, :]


def sublayer(X, p_maps, p_norm, fn, sz, prec):
    """``H_res X + H_post^T F(RMSNorm(H_pre X))`` for the rows X (b, rows, n, d)."""
    return write_back(X, p_maps, fn(mix_in(X, p_maps, p_norm, sz, prec)), sz, prec)


def streams_of(x, n: int):
    """(b, rows, n, d): x copied into the n streams."""
    return jnp.broadcast_to(x[:, :, None, :], x.shape[:2] + (n,) + x.shape[2:])


def block(X, p, ffn, sz, prec):
    """One block over the streams X (b, s, n, d); an X one stream wide (the
    embedding, the prediction module's merge: (b, s, d)) is copied into the n
    streams inside.  Everything but the keys and values is a token's own, so
    the block runs a chunk of tokens at a time, each chunk under
    ``jax.checkpoint``: first the key-value latents of every position (19 MB at
    the published sizes) and from them k and v, then for each chunk both
    sublayers, its queries against k and v.  Nothing four streams wide is ever
    s rows long but the block's own input and output."""
    b, s = X.shape[:2]
    rows = min(sz["token_chunk"], s)
    assert s % rows == 0, (s, rows)
    h = sz["heads_held"][1]
    chunks = jnp.moveaxis(X.reshape((b, s // rows, rows) + X.shape[2:]), 1, 0)

    def widen(Xc):
        return streams_of(Xc, sz["streams"]) if Xc.ndim == 3 else Xc

    @jax.checkpoint
    def latents(Xc):
        return kv_latents(mix_in(widen(Xc), p["mixer_hc"], p["mixer_norm"], sz, prec),
                          p["mixer"], sz, prec)

    kv = jnp.moveaxis(lax.map(latents, chunks), 0, 1).reshape(b, s, -1)
    k, v = keys_values(kv, p["mixer"], sz, prec, h)
    feed_forward = swiglu if ffn == "dense" else functools.partial(routed_ffn, sz=sz)

    @jax.checkpoint
    def one(args):
        Xc, first = args
        Xc = sublayer(widen(Xc), p["mixer_hc"], p["mixer_norm"],
                      lambda x: attend(x, first, k, v, p["mixer"], sz, prec, h), sz, prec)
        return sublayer(Xc, p["ffn_hc"], p["ffn_norm"],
                        lambda x: feed_forward(x, p["ffn"], prec=prec), sz, prec)

    out = lax.map(one, (chunks, jnp.arange(0, s, rows)))
    return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])


def _segment(x, layers, first, sz, prec):
    """Blocks ``first`` .. of the trunk, each under ``jax.checkpoint``."""
    for k, p in enumerate(layers):
        ffn = "dense" if first + k < sz["dense_layers"] else "moe"
        x = jax.checkpoint(block, static_argnums=(2, 3, 4))(x, p, ffn, _frozen(sz), prec)
    return x


def trunk(params, tokens, sz, prec):
    """(b, s, d): the summed streams after the last block, before the final norm."""
    x = params["embed"]["embedding"][tokens]
    for first in range(0, sz["layers"], sz["segment"]):
        layers = [params[f"layer_{i}"]
                  for i in range(first, min(first + sz["segment"], sz["layers"]))]
        x = jax.checkpoint(_segment, static_argnums=(2, 3, 4))(x, layers, first, _frozen(sz), prec)
    return jnp.sum(x, axis=2)


def mtp_hidden(h, p, embedding, targets, sz, prec):
    """The module's output after its own final norm, from the trunk's output
    and the embedding of the next token (the target of each position)."""
    following = embedding[jnp.maximum(targets, 0)]
    merged = mm(jnp.concatenate([rms_norm(h, p["hnorm"], sz["eps"]),
                                 rms_norm(following, p["enorm"], sz["eps"])], -1),
                p["eh_proj"]["kernel"], prec)
    X = jax.checkpoint(block, static_argnums=(2, 3, 4))(
        merged, p["layer"], "moe", _frozen(sz), prec)
    return rms_norm(jnp.sum(X, axis=2), p["final_norm"], sz["eps"])


def mtp_targets(targets):
    """Position i's second target, t_{i+2} = targets[i + 1], where it and
    t_{i+1} = targets[i] both count."""
    shifted = jnp.concatenate([targets[:, 1:], jnp.full_like(targets[:, :1], IGNORE)], axis=1)
    return jnp.where(targets != IGNORE, shifted, IGNORE)


def head_logits(x, table, prec):
    """``x table^T`` for the head's rows ``table`` (vocabulary held, d): no
    transposed copy of the table is made."""
    return product(lambda a, c: jnp.einsum("...d,vd->...v", a, c, precision=HI), x, table, prec)


def cross_entropy(x, table, targets, sz, prec):
    """Mean over the targets that count, a block of tokens at a time."""
    flat, flat_targets = x.reshape(-1, x.shape[-1]), targets.reshape(-1)
    n = flat.shape[0]
    size = min(sz["token_block"], n)
    assert n % size == 0, (n, size)

    @jax.checkpoint
    def one(total, start):
        xb = lax.dynamic_slice_in_dim(flat, start, size, axis=0)
        tb = lax.dynamic_slice_in_dim(flat_targets, start, size, axis=0)
        logp = jax.nn.log_softmax(head_logits(xb, table, prec), axis=-1)
        ce = -jnp.take_along_axis(logp, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        return total + jnp.sum(jnp.where(tb != IGNORE, ce, 0.0)), None

    total, _ = lax.scan(one, jnp.zeros((), jnp.float32), jnp.arange(0, n, size))
    return total / jnp.maximum(jnp.sum(flat_targets != IGNORE), 1)


def logits_fn(params, tokens, sz, prec="highest"):
    """(b, s, vocabulary held): the trunk's logits."""
    sz, prec = controlled(sz, prec)
    x = rms_norm(trunk(params, jnp.asarray(tokens), sz, prec), params["final_norm"], sz["eps"])
    return head_logits(x, params["lm_head"]["embedding"], prec)


def loss_terms(params, batch, sz, prec="highest"):
    """(L_next, L_mtp): both loss terms, each a mean over its own counted targets."""
    sz, prec = controlled(sz, prec)
    observations, actions = batch
    tokens = jnp.asarray(observations["tokens"])
    targets = jnp.asarray(actions["targets"])
    head = params["lm_head"]["embedding"]
    h = trunk(params, tokens, sz, prec)
    l_next = cross_entropy(rms_norm(h, params["final_norm"], sz["eps"]), head, targets, sz, prec)
    if not sz["mtp"]:
        return l_next, jnp.zeros((), jnp.float32)
    y = jax.checkpoint(mtp_hidden, static_argnums=(4, 5))(
        h, params["mtp"], params["embed"]["embedding"], targets, _frozen(sz), prec)
    return l_next, cross_entropy(y, head, mtp_targets(targets), sz, prec)


def loss_fn(params, batch_stats, batch, step_key, sz, prec="highest"):
    """(loss, batch_stats): the training loss of one batch."""
    del step_key        # nothing in the step is random
    weight = controlled(sz, prec)[0]["mtp_weight"]
    l_next, l_mtp = loss_terms(params, batch, sz, prec)
    return l_next + weight * l_mtp, batch_stats

"""Plain reference of the RT-1 training loss (arXiv:2212.06817) as the
configuration trains it: FiLM-EfficientNet + TokenLearner image tokenizer,
causal decoder with the RT-1 action mask, token cross-entropy.

Straightforward ``jax.numpy`` in float32 with every product at precision
``highest``; no kernels, no flax, nothing imported from ``rt1_tpu``.  The
weights come in as the nested dict that benchmarks/weights.py made from the
seed, under the names a flax tree gives them.  ``prec`` other than
``"highest"`` rounds the operands of every matrix product and convolution,
forward and backward, to that type's precision: the control of
benchmarks/check.py.

The step's randomness is part of what it computes, so it is restated here:
the trainer derives the ``crop`` and ``dropout`` streams as ``fold_in(step
key, 0)`` and ``fold_in(step key, 1)``, and flax derives a module's key from
a stream by folding in the first four bytes of the SHA-1 of the module's
path and the call's count (flax.core.scope, ``_fold_in_static``).

Departures from the paper, all the program's and documented there: the FFN
of a decoder block is one square Dense with no activation; the random shift
crop draws one offset per batch; the loss is divided by b*t*(I+A).
"""

from __future__ import annotations

import hashlib
import math
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST

# EfficientNet-B0 table (Tan & Le 2019, table 1): kernel, repeats, in, out,
# expand ratio, stride; squeeze-excite ratio 0.25 of the block's input.
B0_STAGES = (
    (3, 1, 32, 16, 1, 1), (3, 2, 16, 24, 6, 2), (5, 2, 24, 40, 6, 2),
    (3, 3, 40, 80, 6, 2), (5, 3, 80, 112, 6, 1), (5, 4, 112, 192, 6, 2),
    (3, 1, 192, 320, 6, 1),
)
SCALING = {"efficientnet_b3": (1.2, 1.4), "efficientnet_small": (0.35, 0.35)}
DROP_CONNECT = 0.2
CROP_RATIO = 0.07
ACTION_LOW, ACTION_HIGH = -0.1, 0.1     # Language-Table's 2-D effector delta
TOKENS_PER_ACTION = 3                   # terminate_episode + 2 action dims


def sizes(overrides: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference needs, from the configuration file's values."""
    g = lambda k: overrides["model." + k]
    return {
        "scaling": SCALING[g("image_tokenizer")],
        "vocab": g("vocab_size"), "layers": g("num_layers"),
        "heads": g("num_heads"), "key_dim": g("layer_size"),
        "dropout": g("dropout_rate"), "window": g("time_sequence_length"),
        "image_tokens": g("num_image_tokens"),
    }


# ------------------------------------------------------------------ randomness

def flax_key(stream: jax.Array, path: Sequence[str], count: int = 1) -> jax.Array:
    m = hashlib.sha1()
    for x in tuple(path) + (count,):
        m.update(x.encode("utf-8") if isinstance(x, str)
                 else x.to_bytes((x.bit_length() + 7) // 8, "big"))
    return jax.random.fold_in(stream, jnp.uint32(int.from_bytes(m.digest()[:4], "big")))


def dropout(x, rate, key):
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


# ------------------------------------------------------------------ products

def _q(a, prec):
    """``a`` at the precision of ``prec``.  A float type: the significand
    rounded to that type's bits, the exponent left alone (the type with ideal
    scaling, the most a lower-precision path can hope for).  ``int8``: plain
    symmetric quantisation with one scale for the tensor."""
    if prec == "int8":      # one scale a tensor, 127 steps to its largest value
        scale = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 127.0
        return jnp.round(a / scale) * scale
    bits = jnp.finfo(jnp.dtype(prec)).nmant + 1
    m, e = jnp.frexp(a)
    return jnp.ldexp(jnp.round(m * (1 << bits)) / (1 << bits), e)


def product(f, a, b, prec):
    """``f(a, b)`` for a matrix product or convolution ``f``.  At ``highest``
    that is all.  Otherwise both operands are rounded to ``prec`` first, and
    so are the operands of the two products of the backward pass (the
    incoming gradient too): the control of benchmarks/check.py."""
    if prec == "highest":
        return f(a, b)

    @jax.custom_vjp
    def rounded(a, b):
        return f(_q(a, prec), _q(b, prec))

    def forward(a, b):
        return rounded(a, b), (a, b)

    def backward(saved, dy):
        _, vjp = jax.vjp(f, _q(saved[0], prec), _q(saved[1], prec))
        return vjp(_q(dy, prec))

    rounded.defvjp(forward, backward)
    return rounded(a, b)


def dense(x, p, prec):
    y = product(lambda a, b: jnp.matmul(a, b, precision=HI), x, p["kernel"], prec)
    return y + p["bias"] if "bias" in p else y


def conv(x, kernel, stride, pad, groups, prec):
    return product(
        lambda a, b: lax.conv_general_dilated(
            a, b, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups,
            precision=HI),
        x, kernel, prec,
    )


def einsum(spec, a, b, prec):
    return product(lambda x, y: jnp.einsum(spec, x, y, precision=HI), a, b, prec)


def layer_norm(x, p, eps=1e-6):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def batch_norm_train(x, p, eps=1e-5):
    mean = jnp.mean(x, (0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), (0, 1, 2))
    return (x - mean) * lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------------ tokenizer

def round_filters(filters, width):
    filters *= width
    new = max(8, int(filters + 4) // 8 * 8)
    if new < 0.9 * filters:
        new += 8
    return int(new)


def block_table(width, depth) -> List[Dict[str, Any]]:
    repeats = [int(math.ceil(depth * r)) for _, r, *_ in B0_STAGES]
    total = float(sum(repeats))
    blocks, b = [], 0
    for (k, _, cin, cout, expand, stride), n in zip(B0_STAGES, repeats):
        cin, cout = round_filters(cin, width), round_filters(cout, width)
        for j in range(n):
            blocks.append(dict(k=k, cin=cin if j == 0 else cout, cout=cout,
                               expand=expand, stride=stride if j == 0 else 1,
                               drop=DROP_CONNECT * b / total))
            b += 1
    return blocks


def conv_norm_act(x, p, k, stride, groups, act, prec):
    x = conv(x, p["conv"]["kernel"], stride, (k - 1) // 2, groups, prec)
    x = batch_norm_train(x, p["bn"])
    return silu(x) if act else x


def mbconv(x, p, cfg, drop_key, prec):
    inputs = x
    width = cfg["cin"] * cfg["expand"]
    if cfg["expand"] != 1:
        x = conv_norm_act(x, p["expand"], 1, 1, 1, True, prec)
    x = conv_norm_act(x, p["depthwise"], cfg["k"], cfg["stride"], width, True, prec)
    s = jnp.mean(x, (1, 2), keepdims=True)
    s = silu(conv(s, p["se"]["fc1"]["kernel"], 1, 0, 1, prec) + p["se"]["fc1"]["bias"])
    s = jax.nn.sigmoid(conv(s, p["se"]["fc2"]["kernel"], 1, 0, 1, prec) + p["se"]["fc2"]["bias"])
    x = x * s
    x = conv_norm_act(x, p["project"], 1, 1, 1, False, prec)
    if cfg["stride"] == 1 and cfg["cin"] == cfg["cout"]:
        if cfg["drop"] > 0:
            keep = 1.0 - cfg["drop"]
            mask = jax.random.bernoulli(drop_key, keep, (x.shape[0], 1, 1, 1))
            x = jnp.where(mask, x / keep, 0.0)
        x = inputs + x
    return x


def film(x, p, context, prec):
    add = dense(context, p["projection_add"], prec)[:, None, None, :]
    mult = dense(context, p["projection_mult"], prec)[:, None, None, :]
    return (1.0 + mult) * x + add


def image_tokens(params, frames, context, dropout_stream, sz, prec, name, remat=True):
    """frames (N, H, W, 3) float in [0, 1], context (N, 512) -> (N, I, 512).
    ``name`` is the tokenizer's name in the tree, part of its modules' paths."""
    enc = params["encoder"]
    net = enc["EfficientNet_0"]
    width, depth = sz["scaling"]
    x = conv_norm_act(frames, net["stem"], 3, 2, 1, True, prec)
    path = (name, "encoder", "EfficientNet_0")
    for i, cfg in enumerate(block_table(width, depth)):
        key = flax_key(dropout_stream, path + (f"block_{i}",))

        def block(x, p, f, key, context, cfg=cfg):
            return film(mbconv(x, p, cfg, key, prec), f, context, prec)

        if remat:
            block = jax.checkpoint(block)
        x = block(x, net[f"block_{i}"], net[f"film_{i}"], key, context)
    x = conv_norm_act(x, net["top"], 1, 1, 1, True, prec)
    x = conv(x, enc["conv1x1"]["kernel"], 1, 0, 1, prec)
    x = film(x, enc["film"], context, prec)
    tl = params["token_learner"]
    n, h, w, c = x.shape
    y = layer_norm(x, tl["norm"])
    y = conv(y, tl["conv1"]["kernel"], 1, 0, 1, prec) + tl["conv1"]["bias"]
    y = jax.nn.gelu(y, approximate=True)
    y = conv(y, tl["conv2"]["kernel"], 1, 0, 1, prec) + tl["conv2"]["bias"]
    maps = jax.nn.softmax(y.reshape(n, h * w, -1).transpose(0, 2, 1), axis=-1)
    return einsum("bts,bsc->btc", maps, x.reshape(n, h * w, c), prec)


# ------------------------------------------------------------------ decoder

def attention_mask(window, per_image, per_action) -> np.ndarray:
    """Causal, and an action token never sees an action token of its own or
    an earlier time step (RT-1's mask)."""
    step = per_image + per_action
    size = window * step
    is_action = (np.arange(size) % step) >= per_image
    t = np.arange(size) // step
    mask = np.tril(np.ones((size, size), bool))
    blocked = is_action[:, None] & is_action[None, :] & (t[None, :] <= t[:, None])
    return mask & ~blocked


def decoder(params, tokens, dropout_stream, sz, prec):
    """tokens (b, s, 512) -> logits (b, s, vocab)."""
    b, s, _ = tokens.shape
    h, k, rate = sz["heads"], sz["key_dim"], sz["dropout"]
    mask = jnp.asarray(attention_mask(sz["window"], sz["image_tokens"], TOKENS_PER_ACTION))
    x = dense(tokens, params["token_emb"], prec) + params["position_emb"]["embedding"][:s][None]
    for i in range(sz["layers"]):
        p = params[f"layer_{i}"]
        path = ("transformer", f"layer_{i}")
        y = layer_norm(x, p["norm_1"])
        q = dense(y, p["attn"]["query"], prec).reshape(b, s, h, k)
        kk = dense(y, p["attn"]["key"], prec).reshape(b, s, h, k)
        v = dense(y, p["attn"]["value"], prec).reshape(b, s, h, k)
        logits = einsum("bshd,bthd->bhst", q, kk, prec)
        logits = jnp.where(mask[None, None], logits / math.sqrt(k), -1e9)
        probs = jax.nn.softmax(logits, axis=-1)
        if rate > 0:
            probs = dropout(probs, rate, flax_key(dropout_stream, path + ("attn", "Dropout_0")))
        out = einsum("bhst,bthd->bshd", probs, v, prec)
        x = x + dense(out.reshape(b, s, h * k), p["attn"]["out"], prec)
        y = dense(layer_norm(x, p["norm_2"]), p["ff"], prec)
        if rate > 0:
            y = dropout(y, rate, flax_key(dropout_stream, path + ("Dropout_0",)))
        x = x + y
    return dense(x, params["output_tokens"], prec)


# ------------------------------------------------------------------ the loss

def random_shift_crop(images, key):
    h, w = images.shape[-3], images.shape[-2]
    ud, lr = int(h * CROP_RATIO), int(w * CROP_RATIO)
    padded = jnp.pad(images, [(0, 0), (0, 0), (ud, ud), (lr, lr), (0, 0)])
    kh, kw = jax.random.split(key)
    sh = jax.random.randint(kh, (), 0, 2 * ud + 1)
    sw = jax.random.randint(kw, (), 0, 2 * lr + 1)
    zero = jnp.zeros((), jnp.int32)
    return lax.dynamic_slice(padded, [zero, zero, sh, sw, zero], images.shape)


def action_labels(actions, vocab):
    a = jnp.clip(jnp.asarray(actions["action"], jnp.float32), ACTION_LOW, ACTION_HIGH)
    box = ((a - ACTION_LOW) / (ACTION_HIGH - ACTION_LOW) * (vocab - 1)).astype(jnp.int32)
    term = jnp.asarray(actions["terminate_episode"]).astype(jnp.int32)[..., None]
    return jnp.concatenate([term, box], axis=-1)


def loss_fn(params, batch_stats, batch, step_key, sz, prec="highest"):
    """(loss, batch_stats): the training loss of one batch, train mode."""
    obs, actions = batch
    image = jnp.asarray(obs["image"])
    b, t = image.shape[:2]
    image = image.astype(jnp.float32) / 255.0 if image.dtype == jnp.uint8 else image
    crop_stream = jax.random.fold_in(step_key, 0)
    dropout_stream = jax.random.fold_in(step_key, 1)
    image = random_shift_crop(image, flax_key(crop_stream, ()))
    context = jnp.asarray(obs["natural_language_embedding"], jnp.float32)
    # a tokenizer handed to the policy as a module (the small test scaling)
    # sits under the field's name
    name = "image_tokenizer" if "image_tokenizer" in params else "image_tokenizer_def"
    tokens = image_tokens(
        params[name], image.reshape((b * t,) + image.shape[2:]),
        context.reshape(b * t, -1), dropout_stream, sz, prec, name,
    )
    per_image = sz["image_tokens"]
    step = per_image + TOKENS_PER_ACTION
    tokens = tokens.reshape(b, t, per_image, -1)
    seq = jnp.concatenate(
        [tokens, jnp.zeros((b, t, TOKENS_PER_ACTION, tokens.shape[-1]))], axis=2
    ).reshape(b, t * step, -1)
    logits = decoder(params["transformer"], seq, dropout_stream, sz, prec)
    positions = np.array([ti * step + per_image + a - 1
                          for ti in range(t) for a in range(TOKENS_PER_ACTION)])
    action_logits = logits[:, positions].reshape(b, t, TOKENS_PER_ACTION, -1)
    labels = action_labels(actions, sz["vocab"])
    logp = jax.nn.log_softmax(action_logits, axis=-1)
    ce = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    per_step = jnp.mean(ce, axis=-1) / (float(b * t) * step)
    return jnp.mean(per_step), batch_stats

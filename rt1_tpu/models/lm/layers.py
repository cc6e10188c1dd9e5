"""Mixers and the dense feed-forward layer of the block-spec decoder.

Every weight is a float32 master cast to the compute dtype where it is used;
norm statistics, rotary angles and the softmax are float32.  No layer has a
bias.  Leaves are named ``kernel``, ``scale``, ``bias`` or ``embedding``.
"""

from __future__ import annotations

import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from rt1_tpu.models.lm.spec import LMSpec

_KERNEL_INIT = nn.initializers.lecun_normal()


class Leaf(nn.Module):
    """One float32 leaf under a name of its own in the tree."""

    leaf: str
    shape: Tuple[int, ...]
    init: Any

    @nn.compact
    def __call__(self):
        return self.param(self.leaf, self.init, self.shape, jnp.float32)


class Linear(nn.Module):
    """``x @ kernel``, the kernel cast to the compute dtype at use."""

    features: int
    dtype: Any

    @nn.compact
    def __call__(self, x):
        kernel = self.param("kernel", _KERNEL_INIT, (x.shape[-1], self.features), jnp.float32)
        return jnp.dot(x.astype(self.dtype), kernel.astype(self.dtype))


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (y * scale).astype(self.dtype)


def rotary(x, theta: float):
    """Rotate-half rotary embedding over the last axis of (b, s, h, d)."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


# ---------------------------------------------------------------- attention
#
# q: (b, s, kv_heads, group, d); k, v: (b, s, kv_heads, d); causal, exact.

def _scores_to_out(q, k, v, scale, q_start: int):
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_start + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(q_pos >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def dense_attention(q, k, v, scale):
    """The whole (s, s) square a head: short sequences and tests."""
    return _scores_to_out(q, k, v, scale, 0)


FLASH_BLOCK = 1024      # the largest the kernel's scratch allows at head size 64
BLOCKWISE_BLOCK = 512


def causal_attention(q, k, v, scale):
    """Exact causal attention without an (s, s) tensor a head, either way: the
    library's Pallas kernel on a TPU, query blocks in plain ``lax`` elsewhere
    (the Pallas kernel compiles for TPUs only); short sequences take the square."""
    if q.shape[1] <= BLOCKWISE_BLOCK:
        return dense_attention(q, k, v, scale)
    if jax.default_backend() == "tpu":
        return flash_attention(q, k, v, scale, FLASH_BLOCK)
    return blockwise_attention(q, k, v, scale, BLOCKWISE_BLOCK)


def blockwise_attention(q, k, v, scale, block: int):
    """Query blocks against the keys at or before them, each block under
    ``jax.checkpoint``: no (s, s) tensor either way, half the square's work."""
    s = q.shape[1]
    if s <= block:
        return dense_attention(q, k, v, scale)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of the attention block {block}")

    @functools.partial(jax.checkpoint, static_argnums=(3,))
    def one(qb, kb, vb, start):
        return _scores_to_out(qb, kb, vb, scale, start)

    outs = [one(q[:, i:i + block], k[:, :i + block], v[:, :i + block], i)
            for i in range(0, s, block)]
    return jnp.concatenate(outs, axis=1)


def flash_attention(q, k, v, scale, block: int):
    """The library's Pallas TPU kernel (forward and backward kernels of its
    own).  It has one head count, so each KV head is repeated for its group."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    b, s, kvh, g, d = q.shape
    qh = q.reshape(b, s, kvh * g, d).transpose(0, 2, 1, 3)
    kh = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3)
    vh = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)
    blk = min(block, s)
    sizes = fa.BlockSizes(
        block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk, block_q_dkv=blk,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
    with jax.named_scope("kernel"):
        out = fa.flash_attention(qh, kh, vh, causal=True, sm_scale=scale, block_sizes=sizes)
    return out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, d)


class GQAttention(nn.Module):
    """Grouped-query attention with RMSNorm on q and k heads and rotary."""

    spec: LMSpec

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        b, s, _ = x.shape
        h, kvh, d = sp.num_heads, sp.num_kv_heads, sp.head_dim
        with jax.named_scope("attention"):
            q = Linear(h * d, sp.dtype, name="q_proj")(x).reshape(b, s, h, d)
            k = Linear(kvh * d, sp.dtype, name="k_proj")(x).reshape(b, s, kvh, d)
            v = Linear(kvh * d, sp.dtype, name="v_proj")(x).reshape(b, s, kvh, d)
            q = rotary(RMSNorm(sp.norm_eps, sp.dtype, name="q_norm")(q), sp.rope_theta)
            k = rotary(RMSNorm(sp.norm_eps, sp.dtype, name="k_norm")(k), sp.rope_theta)
            q = q.reshape(b, s, kvh, h // kvh, d)
            out = causal_attention(q, k, v, d ** -0.5)
            return Linear(sp.hidden_size, sp.dtype, name="o_proj")(out.reshape(b, s, h * d))


class ShortConv(nn.Module):
    """Gated short convolution: ``[B, C, u] = split(x W_in)``, a depthwise
    causal convolution of ``B * u`` over ``conv_kernel`` taps, gated by ``C``."""

    spec: LMSpec

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        d, taps = sp.hidden_size, sp.conv_kernel
        with jax.named_scope("shortconv"):
            gate_b, gate_c, u = jnp.split(Linear(3 * d, sp.dtype, name="in_proj")(x), 3, axis=-1)
            v = gate_b * u
            kernel = self.param(
                "kernel", nn.initializers.normal(taps ** -0.5), (taps, d), jnp.float32
            ).astype(sp.dtype)
            s = x.shape[1]
            padded = jnp.pad(v, ((0, 0), (taps - 1, 0), (0, 0)))
            # tap j weighs the input j positions back
            c = sum(kernel[j] * padded[:, taps - 1 - j:taps - 1 - j + s] for j in range(taps))
            return Linear(d, sp.dtype, name="out_proj")(gate_c * c)


class SwiGLU(nn.Module):
    spec: LMSpec

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        with jax.named_scope("dense_ffn"):
            gate = Linear(sp.intermediate_size, sp.dtype, name="w1")(x)
            up = Linear(sp.intermediate_size, sp.dtype, name="w3")(x)
            return Linear(sp.hidden_size, sp.dtype, name="w2")(jax.nn.silu(gate) * up)

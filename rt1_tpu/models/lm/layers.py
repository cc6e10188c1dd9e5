"""Mixers and the dense feed-forward layer of the block-spec decoder.

Every weight is a float32 master cast to the compute dtype where it is used;
norm statistics, rotary angles and the softmax are float32.  No layer has a
bias.  Leaves are named ``kernel``, ``scale``, ``bias`` or ``embedding``.
"""

from __future__ import annotations

import functools
import logging
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from rt1_tpu.models.lm.spec import LMSpec
from rt1_tpu.obs.trace import span

_LOG = logging.getLogger(__name__)
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
# q: (b, s, kv_heads, group, d); k, v: (b, s, kv_heads, d); causal, exact:
# bfloat16 operands, float32 scores, softmax and accumulation, the
# probabilities cast to v's type for PV, on every path.  Past 512 positions a
# TPU runs the library's splash attention (jax.experimental.pallas.ops.tpu.
# splash_attention): q goes in head-major as (b, heads, s, d), k and v as
# (b, kv_heads, s, d) with their own head count (the kernel maps query head h to
# KV head h // group), all three head-size-minor; the backward is one kernel
# that makes dq beside dk and dv.

def _scores_to_out(q, k, v, scale, q_start: int):
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_start + jnp.arange(q.shape[1])[:, None]
    scores = jnp.where(q_pos >= jnp.arange(k.shape[1])[None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def dense_attention(q, k, v, scale):
    """The whole (s, s) square a head: short sequences and tests."""
    return _scores_to_out(q, k, v, scale, 0)


# The splash kernels' blocks and backward, from the chip at 2 x 8,192 positions,
# 32 heads over 8 KV heads of 64, both ways with the layout changes in
# (scripts/lm_kernel_probe.py --only attention; PERF.md section 6, PR 30; the old
# flash kernel with its KV heads repeated read 47.0 ms).  Queries and keys go in
# blocks of the largest of these that divides the sequence: 38.2-45.0 ms at
# blocks of 512 against 31.5-37.9 at 1,024, and a query block of 2,048 leaves
# the forward no faster (9.0 ms) and the fused backward no room in VMEM.  The
# backward is the fused kernel (dq as one partial sum a key block, added up
# outside): 31.5 ms against 37.9 with a dq kernel of its own, at 0.6 GB more
# scratch.  k sequence-minor reads the same (31.5), so all stay head-size-minor.
SPLASH_BLOCKS = (1024, 512, 256, 128)
# The forward's inner block of keys: 8.8 ms at 512 against 9.8 at 1,024 and 9.1
# at 256; the fused backward reads the other way, 17.8 at 1,024 against 18.2,
# so it keeps the whole block.  30.5 ms in all.
SPLASH_FORWARD_COMPUTE = 512
BLOCKWISE_BLOCK = 512


def causal_attention(q, k, v, scale):
    """Exact causal attention without an (s, s) tensor a head, either way: the
    library's splash kernels on a TPU (grouped-query and block-sparse: k and v
    unrepeated, the blocks above the diagonal never visited), query blocks in
    plain ``lax`` elsewhere (the Pallas kernels compile for TPUs only); short
    sequences take the square.  The choice is the backend's and the shape's;
    the kernel's blocks are a rule of the sequence length (``splash_blocks``)."""
    if q.shape[1] <= BLOCKWISE_BLOCK:
        return dense_attention(q, k, v, scale)
    if jax.default_backend() == "tpu":
        return splash_attention(q, k, v, scale)
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


def splash_blocks(s: int) -> int:
    """The kernel's block for a sequence of ``s`` positions, queries and keys
    alike, forward and backward."""
    for block in SPLASH_BLOCKS:
        if s % block == 0:
            return block
    raise ValueError(
        f"sequence {s} is not a multiple of the attention kernel's smallest block "
        f"{SPLASH_BLOCKS[-1]}")


@functools.lru_cache(maxsize=None)
def _splash_kernel(s: int, heads: int, interpret: bool):
    """One kernel object a shape: its mask tables are numpy work over every
    (head, query block, key block), made once and not once a trace."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    block = splash_blocks(s)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=min(block, SPLASH_FORWARD_COMPUTE),
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    chosen = dict(attention_impl="splash", seq=s, heads=heads, block_q=block, block_kv=block,
                  block_kv_compute=sizes.block_kv_compute, fused_bwd=sizes.use_fused_bwd_kernel,
                  k_layout=sizes.k_layout.name)
    _LOG.info("attention kernel: %s", chosen)        # once a shape: which kernel a run timed
    with span("lm/attention_kernel", **chosen):
        mask = sm.MultiHeadMask([sm.CausalMask((s, s))] * heads)
        with jax.ensure_compile_time_eval():        # the tables are constants, not tracers
            return sk.make_splash_mha(
                mask, block_sizes=sizes, head_shards=1, q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, scale, interpret: bool = False):
    """The library's splash attention (Pallas TPU kernels, forward and
    backward): grouped-query, so k and v go in with their own head count, and
    block-sparse, so the blocks above the diagonal are never visited and the
    mask is applied on the diagonal blocks only.  The scale is folded into q
    (in float32, so a scale that is no power of two rounds once)."""
    b, s, kvh, g, d = q.shape
    kernel = _splash_kernel(s, kvh * g, interpret)
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(b, s, kvh * g, d)
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (qh, k, v))
    with jax.named_scope("kernel"):
        out = jax.vmap(kernel)(qh, kh, vh)
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

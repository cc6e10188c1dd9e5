"""Mixers and the dense feed-forward layer of the block-spec decoder.

Every weight is a float32 master cast to the compute dtype where it is used;
norm statistics, rotary angles and the softmax are float32.  No layer has a
bias.  Leaves are named ``kernel``, ``scale``, ``bias`` or ``embedding``.
"""

from __future__ import annotations

import functools
import logging
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from rt1_tpu.models.lm.spec import LMSpec, RotaryRule
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


def rotary_frequencies(rule: RotaryRule, d: int):
    """(inverse frequencies (d / 2,) float32, factor on cos and sin) of a head
    of ``d`` dimensions.  ``yarn`` as ``transformers`` computes it, ``truncate``
    on: a frequency that turns more than ``beta_fast`` times over the original
    length is kept, one that turns less than ``beta_slow`` times is divided by
    ``factor``, a linear ramp over the dimensions between."""
    inv_freq = 1.0 / (rule.theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    if rule.rope_type == "default":
        return inv_freq, 1.0

    def dimension(turns: float) -> float:        # the dimension that turns so often
        return (d * math.log(rule.original_max_position_embeddings / (turns * 2 * math.pi))
                / (2 * math.log(rule.theta)))

    low = max(math.floor(dimension(rule.beta_fast)), 0)
    high = min(math.ceil(dimension(rule.beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0, 1)
    return inv_freq / rule.factor * ramp + inv_freq * (1 - ramp), rule.attention_factor


def rotary(x, rule: RotaryRule):
    """Rotate-half rotary embedding over the last axis of (b, s, h, d)."""
    s, d = x.shape[1], x.shape[-1]
    inv_freq, factor = rotary_frequencies(rule, d)
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)[None, :, None, :]
    if factor != 1.0:       # a score carries its square
        cos, sin = cos * factor, sin * factor
    x32 = x.astype(jnp.float32)
    x1, x2 = jnp.split(x32, 2, axis=-1)
    return (x32 * cos + jnp.concatenate([-x2, x1], axis=-1) * sin).astype(x.dtype)


# ---------------------------------------------------------------- attention
#
# q: (b, s, kv_heads, group, d); k, v: (b, s, kv_heads, d); causal, exact:
# bfloat16 operands, float32 scores, softmax and accumulation, the
# probabilities cast to v's type for PV, on every path.  ``window`` (None: all
# the keys at or before the query) is the number of keys a query sees, its own
# among them: query i sees keys i - window < j <= i, on every path, and a path
# visits only the key blocks a query block's window reaches.  Past 512 positions a
# TPU runs the library's splash attention (jax.experimental.pallas.ops.tpu.
# splash_attention): q goes in head-major as (b, heads, s, d), k and v as
# (b, kv_heads, s, d) with their own head count (the kernel maps query head h to
# KV head h // group), all three head-size-minor; the backward is one kernel
# that makes dq beside dk and dv.

def _scores_to_out(q, k, v, scale, q_start: int, k_start: int = 0,
                   window: Optional[int] = None):
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32) * scale
    q_pos = q_start + jnp.arange(q.shape[1])[:, None]
    k_pos = jnp.arange(k.shape[1])[None, :]
    if k_start:     # a causal layer's keys start at 0: its program has no add here
        k_pos = k_start + k_pos
    keep = q_pos >= k_pos
    if window is not None:
        keep = keep & (q_pos - k_pos < window)
    scores = jnp.where(keep, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)


def dense_attention(q, k, v, scale, window: Optional[int] = None):
    """The whole (s, s) square a head: short sequences and tests."""
    return _scores_to_out(q, k, v, scale, 0, 0, window)


# The splash kernels' blocks and backward, from the chip at 2 x 8,192 positions,
# 32 heads over 8 KV heads of 64, both ways with the layout changes in
# (scripts/lm_kernel_probe.py --only attention; PERF.md section 6, PR 30; the old
# flash kernel with its KV heads repeated read 47.0 ms).  Queries and keys go in
# blocks of the largest of these that divides the sequence: 38.2-45.0 ms at
# blocks of 512 against 31.5-37.9 at 1,024, and a query block of 2,048 leaves
# the forward no faster (9.0 ms) and the fused backward no room in VMEM.  The causal
# layers' backward is the fused kernel (dq as one partial sum a key block, added up
# outside): 31.5 ms against 37.9 with a dq kernel of its own, at 0.6 GB more
# scratch.  k sequence-minor reads the same (31.5), so all stay head-size-minor.
SPLASH_BLOCKS = (1024, 512, 256, 128)
# The forward's inner block of keys: 8.8 ms at 512 against 9.8 at 1,024 and 9.1
# at 256; the fused backward reads the other way, 17.8 at 1,024 against 18.2,
# so it keeps the whole block.  30.5 ms in all.
SPLASH_FORWARD_COMPUTE = 512
BLOCKWISE_BLOCK = 512


def causal_attention(q, k, v, scale, window: Optional[int] = None):
    """Exact causal attention without an (s, s) tensor a head, either way: the
    library's splash kernels on a TPU (grouped-query and block-sparse: k and v
    unrepeated, the blocks above the diagonal and those left of the window
    never visited), query blocks in plain ``lax`` elsewhere (the Pallas kernels
    compile for TPUs only); short sequences take the square.  The choice is the
    backend's and the shape's; the kernel's blocks are a rule of the sequence
    length and the window (``splash_blocks``)."""
    if q.shape[1] <= BLOCKWISE_BLOCK:
        return dense_attention(q, k, v, scale, window)
    if jax.default_backend() == "tpu":
        return splash_attention(q, k, v, scale, window)
    return blockwise_attention(q, k, v, scale, BLOCKWISE_BLOCK, window)


def blockwise_attention(q, k, v, scale, block: int, window: Optional[int] = None):
    """Query blocks against the keys at or before them (and inside the window
    of the block's first query), each block under ``jax.checkpoint``: no (s, s)
    tensor either way, half the square's work or the window's."""
    s = q.shape[1]
    if s <= block:
        return dense_attention(q, k, v, scale, window)
    if s % block:
        raise ValueError(f"sequence {s} is not a multiple of the attention block {block}")

    @functools.partial(jax.checkpoint, static_argnums=(3, 4))
    def one(qb, kb, vb, start, first_key):
        return _scores_to_out(qb, kb, vb, scale, start, first_key, window)

    outs = []
    for i in range(0, s, block):
        first_key = 0 if window is None else max(0, i + 1 - window)
        outs.append(one(q[:, i:i + block], k[:, first_key:i + block], v[:, first_key:i + block],
                        i, first_key))
    return jnp.concatenate(outs, axis=1)


# Under a window the smaller block wins and the backward is better unfused, from
# the chip at 1 x 16,384 positions, 32 heads over 4 KV heads of 128, a window of
# 1,024 keys, both ways with the layout changes in (scripts/lm_kernel_probe.py
# --only attention --window 1024; PERF.md section 6, PR 31).  A query block of
# 1,024 visits 2 key blocks (2,048 keys), one of 512 visits 3 (1,536), one of 256
# visits 5 (1,280) and pays for its grid steps: unfused 17.06 / 15.23 / 23.18 ms at
# 1,024 / 512 / 256.  The fused backward writes dq as one float32 partial a key
# block for every query, nearly all of them zero under a window, and adds them up
# outside: 20.66 ms at 1,024 (6.05 around the kernels against 3.04), 27.41 at 512.
SPLASH_WINDOW_BLOCKS = (512, 256, 128)


def splash_blocks(s: int, window: Optional[int] = None) -> int:
    """The kernel's block for a sequence of ``s`` positions under a window of
    ``window`` keys (None: causal), queries and keys alike, forward and
    backward: the largest of the mask's blocks that divides ``s``."""
    blocks = SPLASH_BLOCKS if window is None else SPLASH_WINDOW_BLOCKS
    for block in blocks:
        if s % block == 0:
            return block
    raise ValueError(
        f"sequence {s} is not a multiple of the attention kernel's smallest block "
        f"{blocks[-1]}")


@functools.lru_cache(maxsize=None)
def _splash_kernel(s: int, heads: int, window: Optional[int], interpret: bool,
                   head_dim_v: int = 0):
    """One kernel object a shape and mask: its mask tables are numpy work over
    every (head, query block, key block), made once and not once a trace.
    ``head_dim_v`` (the values' width where it is not the keys': the kernel
    reads both from its operands) is there for the span alone."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    block = splash_blocks(s, window)
    fused = window is None
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=min(block, SPLASH_FORWARD_COMPUTE),
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=None if fused else block, block_kv_dq=None if fused else block,
        use_fused_bwd_kernel=fused)
    chosen = dict(attention_impl="splash", seq=s, heads=heads,
                  mask="causal" if window is None else "local", window=window or 0,
                  block_q=block, block_kv=block,
                  block_kv_compute=sizes.block_kv_compute, fused_bwd=sizes.use_fused_bwd_kernel,
                  k_layout=sizes.k_layout.name, head_dim_v=head_dim_v)
    _LOG.info("attention kernel: %s", chosen)        # once a kernel object: which a run timed
    with span("lm/attention_kernel", **chosen):
        one = (sm.CausalMask((s, s)) if window is None
               else sm.LocalMask((s, s), window_size=(window - 1, 0), offset=0))
        mask = sm.MultiHeadMask([one] * heads)
        with jax.ensure_compile_time_eval():        # the tables are constants, not tracers
            return sk.make_splash_mha(
                mask, block_sizes=sizes, head_shards=1, q_seq_shards=1, interpret=interpret)


def splash_attention(q, k, v, scale, window: Optional[int] = None, interpret: bool = False):
    """The library's splash attention (Pallas TPU kernels, forward and
    backward): grouped-query, so k and v go in with their own head count, and
    block-sparse, so the blocks above the diagonal (and, under a window, those
    wholly left of it) are never visited and the mask is applied on the blocks
    it cuts only.  The scale is folded into q (in float32, so a scale that is
    no power of two rounds once).  v may be narrower than q and k (latent
    attention: keys of 192, values of 128); the output has v's width."""
    b, s, kvh, g, d = q.shape
    kernel = _splash_kernel(s, kvh * g, window, interpret, v.shape[-1])
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(b, s, kvh * g, d)
    qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (qh, k, v))
    with jax.named_scope("kernel"), jax.named_scope("full" if window is None else "window"):
        out = jax.vmap(kernel)(qh, kh, vh)
    return out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, v.shape[-1])


class GQAttention(nn.Module):
    """Grouped-query attention with RMSNorm on q and k heads and rotary; the
    kind of layer (``mixer``) gives the rotary rule and the window."""

    spec: LMSpec
    mixer: str = "full_attention"

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        rule, window = sp.rotary_rule(self.mixer), sp.window(self.mixer)
        b, s, _ = x.shape
        h, kvh, d = sp.num_heads, sp.num_kv_heads, sp.head_dim
        with jax.named_scope("attention"):
            q = Linear(h * d, sp.dtype, name="q_proj")(x).reshape(b, s, h, d)
            k = Linear(kvh * d, sp.dtype, name="k_proj")(x).reshape(b, s, kvh, d)
            v = Linear(kvh * d, sp.dtype, name="v_proj")(x).reshape(b, s, kvh, d)
            q = rotary(RMSNorm(sp.norm_eps, sp.dtype, name="q_norm")(q), rule)
            k = rotary(RMSNorm(sp.norm_eps, sp.dtype, name="k_norm")(k), rule)
            q = q.reshape(b, s, kvh, h // kvh, d)
            out = causal_attention(q, k, v, d ** -0.5, window)
            return Linear(sp.hidden_size, sp.dtype, name="o_proj")(out.reshape(b, s, h * d))


class LatentAttention(nn.Module):
    """Multi-head latent attention (the ``deepseek_v2``/``v3`` form), unabsorbed,
    for the heads held here (``spec.heads_held``: attention tensor-parallel over
    heads; the output is the held heads' part of ``concat_heads(...) W_o``).

        c_q = RMSNorm(x W_qa);   [q_nope | q_rope] a head = c_q W_qb
        [c_kv | k_rope] = x W_kva;   c_kv = RMSNorm(c_kv)
        [k_nope | v] a head = c_kv W_kvb
        q = [q_nope | rotary(q_rope)];   k = [k_nope | rotary(k_rope)], the one
        rotary key shared by every head
        out = concat_heads(softmax(q k^T (nope + rope)^-0.5 m^2, causal) v) W_o

    Both down-projections, the latent norms and the shared key are computed by
    every chip of a head-parallel group alike; ``W_qb``, ``W_kvb`` and ``W_o``
    hold the held heads' columns (rows).  Rotate-half pairing: the published
    interleave is undone by a fixed permutation of ``W_qb``'s and ``W_kva``'s
    rotary columns."""

    spec: LMSpec

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        rule = sp.rotary_rule("latent_attention")
        b, s, _ = x.shape
        h = sp.heads_held[1]
        nope, rope, dv = sp.qk_nope_head_dim, sp.qk_rope_head_dim, sp.v_head_dim
        with jax.named_scope("attention"):
            with jax.named_scope("latent"):
                c_q = RMSNorm(sp.norm_eps, sp.dtype, name="q_a_layernorm")(
                    Linear(sp.q_lora_rank, sp.dtype, name="q_a_proj")(x))
                q = Linear(h * (nope + rope), sp.dtype, name="q_b_proj")(c_q)
                q_nope, q_rope = jnp.split(q.reshape(b, s, h, nope + rope), [nope], axis=-1)
                c_kv, k_rope = jnp.split(
                    Linear(sp.kv_lora_rank + rope, sp.dtype, name="kv_a_proj")(x),
                    [sp.kv_lora_rank], axis=-1)
                c_kv = RMSNorm(sp.norm_eps, sp.dtype, name="kv_a_layernorm")(c_kv)
                kv = Linear(h * (nope + dv), sp.dtype, name="kv_b_proj")(c_kv)
                k_nope, v = jnp.split(kv.reshape(b, s, h, nope + dv), [nope], axis=-1)
                k_rope = rotary(k_rope[:, :, None, :], rule)
                q = jnp.concatenate([q_nope, rotary(q_rope, rule)], axis=-1)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_rope, (b, s, h, rope))], axis=-1)
            scale = (nope + rope) ** -0.5 * sp.softmax_scale_factor
            out = causal_attention(q[:, :, :, None, :], k, v, scale)
            return Linear(sp.hidden_size, sp.dtype, name="o_proj")(out.reshape(b, s, h * dv))


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
    """``W_2(silu(x W_1) * x W_3)``: the dense feed-forward layer, or (with a
    ``width`` and a ``scope_name`` of its own) a shared expert."""

    spec: LMSpec
    width: Optional[int] = None     # None: ``intermediate_size``
    scope_name: str = "dense_ffn"

    @nn.compact
    def __call__(self, x):
        sp = self.spec
        width = self.width or sp.intermediate_size
        with jax.named_scope(self.scope_name):
            gate = Linear(width, sp.dtype, name="w1")(x)
            up = Linear(width, sp.dtype, name="w3")(x)
            return Linear(sp.hidden_size, sp.dtype, name="w2")(jax.nn.silu(gate) * up)

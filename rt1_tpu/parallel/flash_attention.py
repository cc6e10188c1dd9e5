"""Fused masked attention as a Pallas TPU kernel.

RT-1's attention is small (66 tokens/window) but latency-critical at
inference: the 10 Hz control loop runs `tokens_per_action`-free single-pass
decoding (`rt1_tpu/models/rt1.py::infer_step`), and at these sizes the
HBM round-trips between the QK^T, mask/softmax, and PV stages dominate over
FLOPs. This kernel keeps the whole (s, s) score matrix in VMEM for one
(batch, head) program: logits, masking, fp32 softmax, and the value matmul
all fuse with zero HBM intermediates.

Scope (documented): forward-only — used for inference; training uses the
XLA dense path (which autodiffs). Whole-sequence blocks are used rather
than a flash-style K/V loop because s^2 fp32 fits VMEM comfortably up to
s ~ 1024 (4 MB).

Set `interpret=True` to run on CPU (tests do this; on TPU it lowers to
Mosaic).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e9


def _attention_kernel(q_ref, k_ref, v_ref, mask_ref, out_ref, *, scale):
    """One (batch, head) program: full fused attention in VMEM.

    q_ref/k_ref/v_ref: (1, s, d) blocks; mask_ref: (s, s) int32 or None;
    out_ref: (1, s, d).
    """
    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    logits = jax.lax.dot_general(
        q,
        k,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (s, s)
    if mask_ref is not None:
        logits = jnp.where(mask_ref[:] != 0, logits, NEG_INF)
    # Numerically-stable softmax in fp32 on the VPU.
    m = jnp.max(logits, axis=-1, keepdims=True)
    p = jnp.exp(logits - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / l
    out = jax.lax.dot_general(
        probs,
        v_ref[0].astype(jnp.float32),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    out_ref[0] = out.astype(out_ref.dtype)


def fused_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused multi-head attention. q/k/v: (b, s, h, d); mask: (s, s) 0/1.

    Returns (b, s, h, d), matching
    `tests/attention_reference.py::dense_attention_reference`.
    """
    b, s_in, h, d_in = q.shape
    if scale is None:
        scale = 1.0 / (d_in**0.5)

    # Mosaic tiles fp32 as (8, 128) and bf16 as (16, 128): pad sequence to a
    # multiple of 16 (covers both) and head_dim to a multiple of 128 so the
    # kernel lowers on real TPUs (RT-1's s=66, d=64 is unaligned). Padding
    # changes no real output: padded K/V columns are masked out of every
    # real row, padded Q rows attend only to themselves (keeps their softmax
    # finite) and are sliced away.
    s = -(-s_in // 16) * 16
    d = -(-d_in // 128) * 128
    pad_sd = [(0, 0), (0, s - s_in), (0, 0), (0, d - d_in)]
    if s != s_in or d != d_in:
        q = jnp.pad(q, pad_sd)
        k = jnp.pad(k, pad_sd)
        v = jnp.pad(v, pad_sd)
    if s != s_in:
        # Zero-padded d columns need no masking (they add zeros to the
        # logits); padded sequence positions do.
        if mask is None:
            mask = jnp.ones((s_in, s_in), jnp.int32)
        mask = jnp.pad(mask.astype(jnp.int32), [(0, s - s_in), (0, s - s_in)])
        mask = mask.at[jnp.arange(s_in, s), jnp.arange(s_in, s)].set(1)

    # One grid program per (batch, head): layout as (b*h, s, d).
    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    qbh, kbh, vbh = to_bh(q), to_bh(k), to_bh(v)

    qkv_spec = pl.BlockSpec((1, s, d), lambda i: (i, 0, 0))
    kernel = functools.partial(_attention_kernel, scale=scale)

    in_specs = [qkv_spec, qkv_spec, qkv_spec]
    args = [qbh, kbh, vbh]
    if mask is not None:
        # Mask replicated across programs.
        in_specs.append(pl.BlockSpec((s, s), lambda i: (0, 0)))
        args.append(mask.astype(jnp.int32))
        wrapped = kernel
    else:
        wrapped = lambda q_ref, k_ref, v_ref, out_ref: kernel(
            q_ref, k_ref, v_ref, None, out_ref
        )

    out = pl.pallas_call(
        wrapped,
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        grid=(b * h,),
        in_specs=in_specs,
        out_specs=qkv_spec,
        interpret=interpret,
    )(*args)
    out = out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
    return out[:, :s_in, :, :d_in]

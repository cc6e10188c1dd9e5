"""Ring attention: exact attention over a sequence-sharded mesh axis.

The reference has no long-context support — its window is a fixed 66 tokens
(SURVEY.md §5 "long-context: absent"). This module makes sequence/context
parallelism first-class for long-horizon variants: Q/K/V live sharded over
the mesh's ``seq`` axis, and K/V blocks rotate around the ring via
`jax.lax.ppermute` while each device folds one block per hop into a running
flash-attention-style (online softmax) accumulator. Attention is EXACT — the
rotation only changes where each block is multiplied, not the math — and
peak memory per device is O(T/S · T/S) per hop instead of O(T · T).

Design refs (public): Liu et al., "Ring Attention with Blockwise
Transformers" (2023); the `jax.lax.ppermute` collective rides ICI
neighbor-to-neighbor on a TPU slice, overlapping with the per-hop matmuls.

Masks use the framework convention (nonzero = attend, 0 = blocked,
`rt1_tpu/models/transformer.py:56-62`); the full (T, T) mask is replicated
and each hop slices the (q_chunk, k_chunk) block it needs.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

NEG_INF = -1e9


def _ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: Optional[jnp.ndarray],
    axis_name: str,
    scale: float,
):
    """Per-shard body (inside shard_map). q/k/v: (b, t_local, h, d)."""
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    b, t_local, h, d = q.shape

    qf = q.astype(jnp.float32) * scale

    def fold_block(s, o, l, m, k_blk, v_blk):
        """Online-softmax update with the block currently held (origin
        device my_idx + s: ppermute sends block i -> i-1 each hop)."""
        src = jax.lax.rem(my_idx + s, axis_size)
        logits = jnp.einsum(
            "bshd,bthd->bhst", qf, k_blk.astype(jnp.float32)
        )  # (b, h, t_local, t_local)
        if mask is not None:
            blk = jax.lax.dynamic_slice(
                mask,
                (my_idx * t_local, src * t_local),
                (t_local, t_local),
            )
            logits = jnp.where(blk[None, None].astype(bool), logits, NEG_INF)

        m_blk = jnp.max(logits, axis=-1)  # (b, h, t_local)
        m_new = jnp.maximum(m, m_blk)
        # Rescale the running accumulator to the new max, fold in this block.
        p = jnp.exp(logits - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        o_new = o * alpha[..., None] + jnp.einsum(
            "bhst,bthd->bshd", p, v_blk.astype(jnp.float32)
        ).transpose(0, 2, 1, 3)  # -> (b, h, t_local, d)
        return o_new, l_new, m_new

    def hop(s, carry):
        o, l, m, k_blk, v_blk = carry
        o, l, m = fold_block(s, o, l, m, k_blk, v_blk)
        # Rotate K/V one hop around the ring (receive from the next device).
        perm = [(i, (i - 1) % axis_size) for i in range(axis_size)]
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return o, l, m, k_nxt, v_nxt

    o0 = jnp.zeros((b, h, t_local, d), jnp.float32)
    l0 = jnp.zeros((b, h, t_local), jnp.float32)
    m0 = jnp.full((b, h, t_local), -jnp.inf, jnp.float32)
    # Rotate on hops 0..S-2 only; the final block folds without the two
    # wasted ppermutes a full S-iteration loop would issue.
    o, l, m, k_last, v_last = jax.lax.fori_loop(
        0, axis_size - 1, hop, (o0, l0, m0, k, v)
    )
    o, l, m = fold_block(axis_size - 1, o, l, m, k_last, v_last)

    # Fully-masked rows (l == 0) produce 0 output rather than NaN.
    out = jnp.where(
        l[..., None] > 0, o / jnp.maximum(l, 1e-30)[..., None], 0.0
    )
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (b, t_local, h, d)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mesh: Mesh,
    mask: Optional[jnp.ndarray] = None,
    seq_axis: str = "seq",
    batch_axis: Optional[str] = "data",
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Exact multi-head attention with sequence sharded over `seq_axis`.

    Args:
      q, k, v: (b, t, h, d) global arrays; t must divide by the seq-axis size.
      mesh: the device mesh.
      mask: optional (t, t) mask, nonzero = attend (replicated).
      seq_axis: mesh axis to ring over.
      batch_axis: mesh axis the batch is sharded over (None = replicated).
      scale: logit scale; default 1/sqrt(d).
    Returns:
      (b, t, h, d) attention output, sharded like q.
    """
    t = q.shape[1]
    s = mesh.shape[seq_axis]
    if t % s != 0:
        raise ValueError(f"seq len {t} not divisible by {seq_axis}={s}")
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)

    qkv_spec = P(batch_axis, seq_axis, None, None)
    mask_spec = P(None, None)
    body = functools.partial(
        _ring_attention_local, axis_name=seq_axis, scale=scale
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, mask_spec if mask is not None else None),
        out_specs=qkv_spec,
        check_vma=False,
    )(q, k, v, mask)


def dense_attention_reference(q, k, v, mask=None, scale=None):
    """Single-device reference for testing parity."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    logits = jnp.einsum(
        "bshd,bthd->bhst", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None].astype(bool), logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)

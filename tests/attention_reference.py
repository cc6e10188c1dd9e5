"""Single-device attention reference that the attention tests compare against."""

import jax
import jax.numpy as jnp

NEG_INF = -1e9


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

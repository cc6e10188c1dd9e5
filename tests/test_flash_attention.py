"""Pallas fused-attention kernel parity tests (interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rt1_tpu.parallel.flash_attention import fused_attention
from tests.attention_reference import dense_attention_reference

B, S, H, D = 2, 66, 4, 16  # RT-1's actual window: 6 x (8 + 3) = 66 tokens


def _qkv(seed=0):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    return tuple(
        jax.random.normal(k, (B, S, H, D), jnp.float32) for k in ks
    )


def test_fused_matches_dense_no_mask():
    q, k, v = _qkv()
    out = fused_attention(q, k, v, interpret=True)
    ref = dense_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_matches_dense_rt1_mask():
    from rt1_tpu.models.rt1 import rt1_attention_mask

    mask = jnp.asarray(
        rt1_attention_mask(
            time_sequence_length=6, tokens_per_image=8, tokens_per_action=3
        )
    )
    assert mask.shape == (S, S)
    q, k, v = _qkv(1)
    out = fused_attention(q, k, v, mask=mask, interpret=True)
    ref = dense_attention_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_causal_mask():
    q, k, v = _qkv(2)
    mask = jnp.tril(jnp.ones((S, S), jnp.int32))
    out = fused_attention(q, k, v, mask=mask, interpret=True)
    ref = dense_attention_reference(q, k, v, mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_fused_bfloat16_io():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(3))
    out = fused_attention(q, k, v, interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = dense_attention_reference(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), atol=2e-2
    )


def test_fused_under_jit():
    q, k, v = _qkv(4)
    f = jax.jit(lambda q, k, v: fused_attention(q, k, v, interpret=True))
    out = f(q, k, v)
    ref = dense_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_rt1_policy_pallas_infer_matches_dense():
    """infer_step with the pallas kernel == dense attention, same params."""
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from rt1_tpu.specs import language_table_action_space, sample_space
    from test_rt1 import tiny_policy

    rng = jax.random.PRNGKey(0)
    obs_t = {
        "image": jax.random.uniform(rng, (1, 3, 16, 16, 3)),
        "natural_language_embedding": jax.random.normal(
            jax.random.fold_in(rng, 1), (1, 3, 8)
        ),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 2), (1, 3)
    )
    dense = tiny_policy()
    variables = dense.init(
        {"params": rng, "crop": rng}, obs_t, actions, train=False
    )
    pallas_model = tiny_policy(attention_impl="pallas", pallas_interpret=True)

    frame = {
        "image": obs_t["image"][:, 0],
        "natural_language_embedding": obs_t["natural_language_embedding"][:, 0],
    }
    out_d, _ = dense.apply(
        variables, frame, dense.initial_state(1), method=dense.infer_step
    )
    out_p, _ = pallas_model.apply(
        variables,
        frame,
        pallas_model.initial_state(1),
        method=pallas_model.infer_step,
    )
    np.testing.assert_array_equal(
        np.asarray(out_d["action_tokens"]), np.asarray(out_p["action_tokens"])
    )
    np.testing.assert_allclose(
        np.asarray(out_d["action_logits"]),
        np.asarray(out_p["action_logits"]),
        atol=1e-4,
    )


def test_pallas_off_tpu_without_interpret_raises():
    """attention_impl="pallas" must run the kernel or fail: off-TPU without
    pallas_interpret it raises instead of quietly running dense, while the
    train-time forward (the kernel has no autodiff rule) stays dense."""
    import pytest

    from rt1_tpu.models.transformer import TFMultiHeadAttention

    attn = TFMultiHeadAttention(
        key_dim=8, num_heads=2, d_model=16, attention_impl="pallas"
    )
    x = jnp.ones((1, 16, 16), jnp.float32)
    variables = attn.clone(attention_impl="dense").init(
        jax.random.PRNGKey(0), x
    )
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match='attention_impl="pallas" needs a TPU'):
        attn.apply(variables, x, train=False)
    out, _ = attn.apply(
        variables, x, train=True, rngs={"dropout": jax.random.PRNGKey(1)}
    )
    assert np.isfinite(np.asarray(out)).all()

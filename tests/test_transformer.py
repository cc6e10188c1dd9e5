"""Causal transformer tests (reference: transformer_test.py:34-52 + mask semantics)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rt1_tpu.models.rt1 import action_token_positions, rt1_attention_mask
from rt1_tpu.models.transformer import CausalTransformer, TFMultiHeadAttention
from tests.attention_reference import dense_attention_reference


def tiny_transformer(**kw):
    cfg = dict(num_layers=2, key_dim=8, num_heads=2, d_model=16, dropout_rate=0.1,
               vocab_size=16, max_seq_len=64)
    cfg.update(kw)
    return CausalTransformer(**cfg)


def test_output_shape(rng):
    model = tiny_transformer()
    x = jax.random.normal(rng, (2, 10, 12))
    mask = jnp.tril(jnp.ones((10, 10), jnp.uint8))
    params = model.init(rng, x, mask)
    out = model.apply(params, x, mask)
    assert out.shape == (2, 10, 16)


def test_attention_scores_flag(rng):
    model = tiny_transformer(return_attention_scores=True)
    x = jax.random.normal(rng, (1, 6, 12))
    mask = jnp.tril(jnp.ones((6, 6), jnp.uint8))
    params = model.init(rng, x, mask)
    out, scores = model.apply(params, x, mask)
    assert out.shape == (1, 6, 16)
    assert len(scores) == 2
    assert scores[0].shape == (1, 2, 6, 6)
    # Attention rows are softmax-normalized.
    np.testing.assert_allclose(np.asarray(scores[0].sum(-1)), 1.0, rtol=1e-5)


def test_batched_mask_and_seq_len_guard(rng):
    model = tiny_transformer(dropout_rate=0.0)
    x = jax.random.normal(rng, (2, 8, 12))
    mask2d = jnp.tril(jnp.ones((8, 8), jnp.uint8))
    params = model.init(rng, x, mask2d)
    out2d = model.apply(params, x, mask2d)
    # A (b, s, s) mask equal to the broadcasted 2-D mask gives identical results.
    mask3d = jnp.tile(mask2d[None], (2, 1, 1))
    out3d = model.apply(params, x, mask3d)
    np.testing.assert_allclose(np.asarray(out2d), np.asarray(out3d), atol=1e-6)
    # Sequences longer than max_seq_len are rejected, not silently clamped.
    long_x = jax.random.normal(rng, (1, 65, 12))
    with pytest.raises(ValueError, match="max_seq_len"):
        model.apply(params, long_x, jnp.tril(jnp.ones((65, 65), jnp.uint8)))


def test_causal_mask_blocks_future(rng):
    """Zeroing future inputs must not change past outputs under a tril mask."""
    model = tiny_transformer(dropout_rate=0.0)
    x = jax.random.normal(rng, (1, 8, 12))
    mask = jnp.tril(jnp.ones((8, 8), jnp.uint8))
    params = model.init(rng, x, mask)
    full = model.apply(params, x, mask)
    x_cut = x.at[:, 5:, :].set(0.0)
    cut = model.apply(params, x_cut, mask)
    np.testing.assert_allclose(np.asarray(full[:, :5]), np.asarray(cut[:, :5]), atol=1e-5)
    assert not np.allclose(np.asarray(full[:, 5:]), np.asarray(cut[:, 5:]))


_T = 32
_ATTENTION_MASKS = {
    "no_mask": lambda: None,
    "causal": lambda: jnp.tril(jnp.ones((_T, _T), jnp.int32)),
    # The action-blind causal mask, from the real generator: 2 frames of
    # 13 image + 3 action tokens.
    "rt1_mask": lambda: jnp.asarray(rt1_attention_mask(2, 13, 3)),
    # A fully-masked query row degenerates to a uniform average (the
    # additive mask is a finite NEG_INF): finite everywhere.
    "fully_masked_rows_finite": lambda: jnp.zeros((_T, _T), jnp.int32).at[1:, :].set(1),
}


@pytest.mark.parametrize("case", list(_ATTENTION_MASKS))
def test_dense_attention_matches_the_reference(rng, case):
    """The attention module against the single-device reference applied to
    the module's own projections, under every mask shape RT-1 meets."""
    b, t, h, d, d_model = 2, _T, 4, 16, 24
    mask = _ATTENTION_MASKS[case]()
    attn = TFMultiHeadAttention(num_heads=h, key_dim=d, d_model=d_model, dropout_rate=0.0)
    x = jax.random.normal(rng, (b, t, d_model))
    variables = attn.init(jax.random.fold_in(rng, 1), x, mask)
    out, _ = attn.apply(variables, x, mask)

    p = variables["params"]
    q, k, v = (
        (x @ p[name]["kernel"] + p[name]["bias"]).reshape(b, t, h, d)
        for name in ("query", "key", "value")
    )
    ref = dense_attention_reference(q, k, v, mask=mask).reshape(b, t, h * d)
    ref = ref @ p["out"]["kernel"] + p["out"]["bias"]
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# ---------------------------------------------------------------- RT-1 mask unit

def brute_force_reference_mask(t, i_tok, a_tok):
    """Independent re-derivation of _generate_masks (:156-192) for cross-checking."""
    step = i_tok + a_tok
    size = t * step

    def action_index(k):
        if k % step < i_tok:
            return -1
        return k // step

    tril = np.tril(np.ones((size, size), int))
    action_mask = np.zeros((size, size), int)
    for i in range(size):
        for j in range(size):
            ai, aj = action_index(i), action_index(j)
            if ai != -1 and aj != -1:
                if aj < ai or (aj == ai and j <= i):
                    action_mask[i, j] = 1
    return tril - action_mask


def test_rt1_mask_matches_reference_semantics():
    for (t, i_tok, a_tok) in [(1, 2, 1), (2, 3, 2), (6, 8, 3), (3, 2, 4)]:
        got = rt1_attention_mask(t, i_tok, a_tok)
        want = brute_force_reference_mask(t, i_tok, a_tok)
        np.testing.assert_array_equal(got, want, err_msg=f"cfg {(t, i_tok, a_tok)}")
        assert got.min() >= 0  # subtracting never goes negative


def test_rt1_mask_properties():
    t, i_tok, a_tok = 6, 8, 3
    m = rt1_attention_mask(t, i_tok, a_tok)
    pos = set(action_token_positions(t, i_tok, a_tok).tolist())
    size = t * (i_tok + a_tok)
    for q in range(size):
        for k in range(size):
            if k > q:
                assert m[q, k] == 0  # causal
            elif q in pos and k in pos:
                assert m[q, k] == 0  # action tokens never read action tokens (≤ time)
            elif k in pos and q not in pos:
                # image queries MAY read past action positions (inputs are zeroed
                # anyway); reference only subtracts the action→action entries.
                assert m[q, k] == (1 if k <= q else 0)
    # every action query can still attend its own step's image tokens.
    for q in sorted(pos):
        assert m[q].sum() >= i_tok


def test_action_token_positions_values():
    np.testing.assert_array_equal(
        action_token_positions(2, 3, 2), [3, 4, 8, 9]
    )

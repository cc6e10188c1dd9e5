"""Causal decoder-only transformer for RT-1.

Re-design of `pytorch_robotics_transformer/transformer.py`. Architectural parity
(verified by tests/test_transformer.py):

* token embedding: Dense(input_emb → d_model) (`transformer.py:171,181`);
* learned positional embedding over `max_seq_len=256` positions (`:172,183-186`);
* N pre-norm blocks (`_TransformerLayer:112-144`): LN → TF-Keras-style MHA where the
  per-head width `key_dim` is decoupled from `d_model` (`TF_MultiHeadAttention:29-79`)
  → residual; LN → a *single* Dense(d_model → d_model) with NO activation (a quirk of
  the reference, `:126,140-141` — kept for parity) → dropout → residual;
* output head: Dense(d_model → vocab) (`:173,197`).

Naming note carried over from the reference: `layer_size` is the per-head attention
width (key_dim) and `feed_forward_size` is d_model (`transformer.py:115-117`).

TPU-first details: attention is two einsums (MXU-shaped), the additive mask is
prepared once outside jit, softmax in fp32 even under bf16 compute, and dropout on
attention probabilities matches the reference's placement (`transformer.py:94-98`).

Incremental decode (docs/serving.md "Incremental inference"): every module
below also accepts ``kv_cache``/``cache_index`` kwargs. With a cache, the
input carries only the NEW sequence positions; each attention layer projects
their q/k/v, writes the new k/v into the cache at ``cache_index``, and
attends the new queries against the full cached key/value prefix under a
``(new_len, cache_len)`` mask. Position embeddings are looked up at the
absolute positions ``cache_index + arange(new_len)``, so a cached step is
numerically the same computation the full pass would do for those rows.
The cache pytree is a single ``(b, layers, 2, cache_len, heads, key_dim)``
array (k at index 0, v at index 1 of axis 2) so it can ride a serving
engine's donated state chain as one leaf. The default (``kv_cache=None``)
path is untouched — byte-identical to the pre-cache program.
"""

from __future__ import annotations

from typing import Optional, Tuple

import flax.linen as nn
import jax.numpy as jnp

from rt1_tpu.models.quant import QuantDense

NEG_INF = -1e9


class TFMultiHeadAttention(nn.Module):
    """tf.keras-style MHA: qkv project d_model → heads·key_dim, out back to d_model."""

    num_heads: int
    key_dim: int
    d_model: int
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    # "dense" | "pallas". "pallas" is the fused inference kernel: it runs
    # whenever train=False (it has no autodiff rule, so train=True takes the
    # dense math) and raises off-TPU unless `pallas_interpret` is set.
    attention_impl: str = "dense"
    # Run the pallas kernel in interpreter mode (tests off-TPU; orders of
    # magnitude slower than dense; never set in production).
    pallas_interpret: bool = False

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        mask: Optional[jnp.ndarray] = None,
        train: bool = False,
        kv_cache: Optional[jnp.ndarray] = None,
        cache_index: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
        b, s, _ = x.shape
        h, k = self.num_heads, self.key_dim
        # QuantDense == nn.Dense until an int8 serving tree arrives
        # (models/quant.py); qkv/out/ff are the int8 group in the quant
        # plan (parallel/plan.py rt1_quant_rules).
        q = QuantDense(h * k, dtype=self.dtype, name="query")(x).reshape(b, s, h, k)
        kk = QuantDense(h * k, dtype=self.dtype, name="key")(x).reshape(b, s, h, k)
        v = QuantDense(h * k, dtype=self.dtype, name="value")(x).reshape(b, s, h, k)

        import jax as _jax

        if kv_cache is not None:
            # Incremental decode: x holds only the NEW positions; write
            # their k/v into the cache at cache_index and attend the new
            # queries against the whole cached prefix. `mask` must be
            # (new_len, cache_len). Same dense einsum/fp32-softmax math as
            # the full pass (no prob dropout: decode is inference-only), so
            # while the cache holds position-correct entries the outputs
            # match the full pass row-for-row. Returns the updated
            # (b, 2, cache_len, h, k) cache in place of the scores.
            k_cache = _jax.lax.dynamic_update_slice_in_dim(
                kv_cache[:, 0], kk, cache_index, axis=1
            )
            v_cache = _jax.lax.dynamic_update_slice_in_dim(
                kv_cache[:, 1], v, cache_index, axis=1
            )
            logits = jnp.einsum(
                "bqhd,bshd->bhqs", q, k_cache,
                preferred_element_type=jnp.float32,
            )
            logits = logits / jnp.sqrt(jnp.asarray(k, jnp.float32))
            if mask is not None:
                logits = jnp.where(mask[None, None].astype(bool), logits, NEG_INF)
            probs = nn.softmax(logits.astype(jnp.float32), axis=-1)
            out = jnp.einsum(
                "bhqs,bshd->bqhd", probs.astype(self.dtype), v_cache
            )
            out = out.reshape(b, s, h * k)
            new_cache = jnp.stack([k_cache, v_cache], axis=1)
            return QuantDense(self.d_model, dtype=self.dtype, name="out")(out), new_cache

        # Forward-only kernel (no autodiff rule): train=True takes the
        # dense math below.
        if self.attention_impl == "pallas" and not train:
            # Fused VMEM kernel (rt1_tpu/parallel/flash_attention.py).
            from rt1_tpu.parallel.flash_attention import fused_attention

            if not self.pallas_interpret and _jax.default_backend() != "tpu":
                raise RuntimeError(
                    'attention_impl="pallas" needs a TPU backend (found '
                    f"{_jax.default_backend()!r}); set pallas_interpret=True "
                    "to run the kernel in interpreter mode"
                )
            if mask is not None and mask.ndim != 2:
                raise ValueError("pallas attention supports (s, s) masks only")
            out = fused_attention(
                q,
                kk,
                v,
                mask=mask,
                scale=1.0 / float(k) ** 0.5,
                interpret=self.pallas_interpret,
            )
            out = out.reshape(b, s, h * k)
            return QuantDense(self.d_model, dtype=self.dtype, name="out")(out), None

        # (b, h, sq, sk) attention logits; fp32 softmax for stability under bf16.
        logits = jnp.einsum("bshd,bthd->bhst", q, kk, preferred_element_type=jnp.float32)
        logits = logits / jnp.sqrt(jnp.asarray(k, jnp.float32))
        if mask is not None:
            # mask: (s, s) or (b, s, s); nonzero = attend, 0 = blocked (reference :89-92).
            if mask.ndim == 2:
                mask = mask[None, None]
            elif mask.ndim == 3:
                mask = mask[:, None]  # add head axis
            logits = jnp.where(mask.astype(bool), logits, NEG_INF)
        probs = nn.softmax(logits.astype(jnp.float32), axis=-1)
        probs = nn.Dropout(self.dropout_rate, deterministic=not train)(probs)
        out = jnp.einsum("bhst,bthd->bshd", probs.astype(self.dtype), v)
        out = out.reshape(b, s, h * k)
        return QuantDense(self.d_model, dtype=self.dtype, name="out")(out), probs


class TransformerLayer(nn.Module):
    """Pre-norm block: x + MHA(LN(x)); x + Dropout(FFN(LN(x))) (reference :130-144).

    The FFN is the reference-parity single square Dense.
    """

    key_dim: int
    num_heads: int
    d_model: int
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "dense"
    pallas_interpret: bool = False

    @nn.compact
    def __call__(
        self, x, mask=None, train: bool = False, kv_cache=None, cache_index=None
    ):
        y = nn.LayerNorm(dtype=self.dtype, name="norm_1")(x)
        # In decode mode (kv_cache given) the second element is the layer's
        # updated (b, 2, cache_len, h, k) cache instead of attention scores.
        attn_out, scores = TFMultiHeadAttention(
            num_heads=self.num_heads,
            key_dim=self.key_dim,
            d_model=self.d_model,
            dropout_rate=self.dropout_rate,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            pallas_interpret=self.pallas_interpret,
            name="attn",
        )(y, mask=mask, train=train, kv_cache=kv_cache, cache_index=cache_index)
        x = x + attn_out
        y = nn.LayerNorm(dtype=self.dtype, name="norm_2")(x)
        y = QuantDense(self.d_model, dtype=self.dtype, name="ff")(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        return x + y, scores


class CausalTransformer(nn.Module):
    """Token-in, vocab-logits-out decoder (reference `Transformer:146-198`)."""

    num_layers: int = 8
    key_dim: int = 128          # "layer_size" in the reference
    num_heads: int = 8
    d_model: int = 512          # "feed_forward_size" in the reference
    dropout_rate: float = 0.1
    vocab_size: int = 256
    max_seq_len: int = 256
    return_attention_scores: bool = False
    dtype: jnp.dtype = jnp.float32
    attention_impl: str = "dense"
    pallas_interpret: bool = False
    # jax.checkpoint each block: recompute activations in the backward pass
    # instead of storing them (O(layers)→O(1) activation memory, ~1/3 extra
    # FLOPs). Semantics-preserving; exactness pinned in tests/test_rt1.py.
    remat: bool = False

    @nn.compact
    def __call__(
        self,
        inputs: jnp.ndarray,
        attention_mask=None,
        train: bool = False,
        kv_cache=None,
        cache_index=None,
    ):
        """inputs: (b, s, input_emb) → logits (b, s, vocab_size).

        With ``kv_cache`` (b, num_layers, 2, cache_len, heads, key_dim) and
        a ``cache_index`` start position, `inputs` carries only the NEW
        positions: they are embedded at absolute positions
        ``cache_index + arange(s)``, each layer attends them against its
        cached prefix under the (s, cache_len) ``attention_mask``, and the
        call returns ``(logits, updated_kv_cache)``. Passing the full
        sequence with ``cache_index=0`` and the full square mask recomputes
        every cache row from scratch (the serving engine's invalidation
        rebuild) — identical math to the cache-free pass.
        """
        b, s, _ = inputs.shape
        if s > self.max_seq_len:
            raise ValueError(
                f"sequence length {s} exceeds max_seq_len={self.max_seq_len}"
            )
        if kv_cache is not None:
            x = nn.Dense(self.d_model, dtype=self.dtype, name="token_emb")(inputs)
            positions = cache_index + jnp.arange(s)
            pos_emb = nn.Embed(
                self.max_seq_len, self.d_model, dtype=self.dtype,
                name="position_emb",
            )(positions)
            x = x + pos_emb[None, :, :]
            new_caches = []
            for i in range(self.num_layers):
                x, layer_cache = TransformerLayer(
                    key_dim=self.key_dim,
                    num_heads=self.num_heads,
                    d_model=self.d_model,
                    dropout_rate=self.dropout_rate,
                    dtype=self.dtype,
                    # Decode always uses the dense einsum math: the
                    # pallas kernel is a full-sequence (square-mask)
                    # implementation and decode's prefix attention is a
                    # (s × cache_len) sliver that doesn't need it.
                    attention_impl="dense",
                    name=f"layer_{i}",
                )(x, attention_mask, False, kv_cache[:, i], cache_index)
                new_caches.append(layer_cache)
            logits = nn.Dense(
                self.vocab_size, dtype=self.dtype, name="output_tokens"
            )(x)
            return logits, jnp.stack(new_caches, axis=1)
        if self.return_attention_scores and self.attention_impl == "pallas":
            raise ValueError(
                "attention scores are not materialized under pallas "
                "attention; use attention_impl='dense' for score "
                "visualization"
            )
        x = nn.Dense(self.d_model, dtype=self.dtype, name="token_emb")(inputs)
        pos_emb = nn.Embed(self.max_seq_len, self.d_model, dtype=self.dtype, name="position_emb")(
            jnp.arange(s)
        )
        x = x + pos_emb[None, :, :]
        scores = []
        # static_argnums counts `self` as 0: (self, x, mask, train) → train=3.
        layer_cls = (
            nn.remat(TransformerLayer, static_argnums=(3,))
            if self.remat
            else TransformerLayer
        )
        for i in range(self.num_layers):
            x, sc = layer_cls(
                key_dim=self.key_dim,
                num_heads=self.num_heads,
                d_model=self.d_model,
                dropout_rate=self.dropout_rate,
                dtype=self.dtype,
                attention_impl=self.attention_impl,
                pallas_interpret=self.pallas_interpret,
                name=f"layer_{i}",
            )(x, attention_mask, train)
            if self.return_attention_scores:
                scores.append(sc)
        logits = nn.Dense(self.vocab_size, dtype=self.dtype, name="output_tokens")(x)
        if self.return_attention_scores:
            return logits, scores
        return logits

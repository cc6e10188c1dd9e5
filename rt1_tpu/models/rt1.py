"""RT-1 policy network: tokenizers + causal transformer, train & inference paths.

Re-design of `pytorch_robotics_transformer/transformer_network.py` (`TransformerNetwork`,
`:35-532`). Same semantics, TPU-native structure:

* **Masks** (`rt1_attention_mask`, reference `_generate_masks:156-192`): causal tril
  minus an action mask — an action-token query may never attend to action-token keys
  of the same or earlier timestep (including itself); image-token queries are only
  causally masked. Action tokens are additionally **zeroed at input assembly**
  (reference `:378-390`, comment at `:383`), so logits never depend on action values.
* **Training** (`__call__`): ONE transformer pass over the T·(I+A) sequence; CE loss
  on the logits at position (action position − 1) (the transformer's shift-by-one,
  reference `:237,304-322`), with the reference's `/ (b·t·(I+A))` scaling reproduced
  under `loss_scale='reference'` (`:314-319` — the LR schedule was tuned against it).
* **Inference** (`infer_step`): the reference runs `tokens_per_action` FULL transformer
  passes per control step, argmaxing one token at a time (`:246-268`). Because action
  inputs are zeroed and masked out, those passes are *identical*, so all action tokens
  can be read from a SINGLE pass — a ~`tokens_per_action`× inference speedup with
  bit-identical results (proved in tests/test_rt1.py::test_single_pass_equals_autoregressive).
  The rolling `network_state` window (context_image_tokens, action_tokens, seq_idx;
  reference `:105-123,462-492`) becomes a static-shape pytree updated with
  `dynamic_update_slice` + `jnp.where`-gated rolls, fully jittable.
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rt1_tpu.models import action_tokenizer
from rt1_tpu.models.image_tokenizer import RT1ImageTokenizer
from rt1_tpu.models.transformer import CausalTransformer
from rt1_tpu.ops import image as image_ops


def rt1_attention_mask(
    time_sequence_length: int, tokens_per_image: int, tokens_per_action: int
) -> np.ndarray:
    """The RT-1 custom attention mask (reference `_generate_masks:156-192`).

    Returns (S, S) uint8, S = T·(I+A); 1 = may attend, 0 = blocked. Row = query
    position, column = key position.
    """
    step = tokens_per_image + tokens_per_action
    size = time_sequence_length * step

    def action_time(k: int) -> int:
        # Timestep index if k is an action token, else -1 (reference :131-150).
        return k // step if (k % step) >= tokens_per_image else -1

    mask = np.tril(np.ones((size, size), np.uint8))
    for i in range(size):
        ti = action_time(i)
        if ti < 0:
            continue
        for j in range(i + 1):
            tj = action_time(j)
            if tj < 0:
                continue
            if tj < ti or (tj == ti and j <= i):
                mask[i, j] = 0
    return mask


def action_token_positions(
    time_sequence_length: int, tokens_per_image: int, tokens_per_action: int
) -> np.ndarray:
    """Sequence indices of the action tokens (reference `_action_tokens_mask:166-169`)."""
    step = tokens_per_image + tokens_per_action
    return np.array(
        [
            t * step + tokens_per_image + x
            for t in range(time_sequence_length)
            for x in range(tokens_per_action)
        ],
        np.int32,
    )


class RT1Policy(nn.Module):
    """The RT-1 actor network (reference `TransformerNetwork:35-123`)."""

    action_space: Any                 # Mapping[str, Spec] — static metadata
    vocab_size: int = 256
    token_embedding_size: int = 512
    num_layers: int = 8
    layer_size: int = 128             # per-head attention width (key_dim)
    num_heads: int = 8
    feed_forward_size: int = 512      # d_model
    dropout_rate: float = 0.1
    time_sequence_length: int = 6
    use_token_learner: bool = True
    num_image_tokens: int = 8
    crop_ratio: float = 0.07          # pad-and-random-shift ratio (preprocessors.py:37)
    photometric_augmentation: bool = False  # on-device color jitter (train only)
    loss_scale: str = "reference"     # 'reference' (:314-319) or 'mean'
    # Focal modulation of the action-token CE (Lin et al. 2017): ce *=
    # (1 - p_label)^gamma. 0 disables (reference parity). BC on smooth
    # scripted demos concentrates labels on a few near-center buckets, so a
    # near-constant policy already scores low CE (the "copycat" collapse
    # diagnosed in round 2); gamma > 0 down-weights those easy
    # marginal tokens and shifts gradient onto the rare directional ones.
    focal_gamma: float = 0.0
    # Soft-argmax auxiliary regression: loss += w * MSE(E[a], a_true) where
    # E[a] = sum_v softmax(logits)[v] * bin_value[v] over the Box action
    # tokens (action_tokenizer.box_bin_values). Parameter-free (no new
    # weights — checkpoints unaffected) and differentiable, it supplies a
    # dense regression gradient through the whole network while the token
    # CE sits on its marginal-entropy plateau — the round-3 diagnosis: CE
    # alone spends its first many epochs fitting the marginal (measured
    # 2.508 nats on the oracle corpus) with ~zero input-dependence.
    # 0 disables (reference parity).
    aux_mse_weight: float = 0.0
    # Inference action decode: "argmax" (reference parity,
    # transformer_network.py:262) or "expected" — E[a] under the token
    # softmax for Box dims (action_tokenizer.detokenize_expected), smoother
    # when distribution mass straddles a bin edge and consistent with the
    # aux_mse training objective. The rolling state always stores argmax
    # tokens either way (the reference's state semantics).
    action_decode: str = "argmax"
    return_attention_scores: bool = False
    dtype: jnp.dtype = jnp.float32
    # "dense" (default) or "pallas". "pallas" fuses inference attention
    # into one VMEM kernel on TPU (training takes the dense math: the kernel
    # is forward-only; off-TPU it raises unless `pallas_interpret`).
    attention_impl: str = "dense"
    mesh: Optional[Any] = None
    pallas_interpret: bool = False  # test-only: run the kernel off-TPU
    # Pipeline parallelism: when `mesh` has a >1 "stage" axis, the decoder's
    # layer stack runs GPipe-pipelined over it (parallel/pipeline.py) with
    # this many microbatches per step; per-(layer, microbatch) dropout rngs
    # are folded from the "dropout" stream. Param layout is unchanged
    # (checkpoints are stage-count-portable); parameters stay replicated —
    # PP here scales *compute* across chips, which at RT-1 size (decoder
    # ~17M params) is the binding constraint, not parameter memory.
    pipeline_microbatches: int = 4
    # Rematerialize transformer blocks AND MBConv blocks in the backward
    # pass (jax.checkpoint): O(depth)→O(1) activation memory for ~1/3 extra
    # FLOPs — batch-size headroom on HBM-bound flagship configs.
    # Semantics-preserving (loss/grads unchanged; pinned in tests).
    remat: bool = False
    # Optional custom image tokenizer module (must map (b,t,H,W,3), (b,t,D) →
    # (b,t,num_image_tokens,token_embedding_size)); used by tests to swap the
    # EfficientNet-B3 backbone for a tiny one.
    image_tokenizer_def: Optional[Any] = None

    @property
    def tokens_per_action(self) -> int:
        return action_tokenizer.tokens_per_action(self.action_space)

    @property
    def tokens_per_image(self) -> int:
        if not self.use_token_learner and self.image_tokenizer_def is None:
            raise ValueError("token count is input-resolution-dependent without TokenLearner")
        return self.num_image_tokens

    @property
    def single_step_tokens(self) -> int:
        return self.tokens_per_image + self.tokens_per_action

    @property
    def sequence_tokens(self) -> int:
        return self.time_sequence_length * self.single_step_tokens

    def setup(self):
        if self.action_decode not in ("argmax", "expected"):
            raise ValueError(
                f"action_decode must be 'argmax' or 'expected', got "
                f"{self.action_decode!r}"
            )
        if self.action_decode == "expected" and not any(
            isinstance(s, action_tokenizer.BoxSpec)
            for s in self.action_space.values()
        ):
            # box_bin_values (the E[a] bin table) would raise at trace time
            # with a message about the aux-MSE objective; fail at
            # construction with the real reason instead.
            raise ValueError(
                "action_decode='expected' needs at least one Box action "
                "entry (soft decode only differs from argmax for Box); "
                "this action space is all-Discrete — use 'argmax'"
            )
        if self.image_tokenizer_def is not None:
            self.image_tokenizer = self.image_tokenizer_def
        else:
            self.image_tokenizer = RT1ImageTokenizer(
                embedding_output_dim=self.token_embedding_size,
                use_token_learner=self.use_token_learner,
                num_tokens=self.num_image_tokens,
                dtype=self.dtype,
                remat=self.remat,
            )
        self.transformer = CausalTransformer(
            num_layers=self.num_layers,
            key_dim=self.layer_size,
            num_heads=self.num_heads,
            d_model=self.feed_forward_size,
            dropout_rate=self.dropout_rate,
            vocab_size=self.vocab_size,
            # Reference fixes 256 (transformer.py:156); grow if the configured
            # window needs more so positions never clamp silently.
            max_seq_len=max(256, self.sequence_tokens),
            return_attention_scores=self.return_attention_scores,
            dtype=self.dtype,
            attention_impl=self.attention_impl,
            pallas_interpret=self.pallas_interpret,
            remat=self.remat,
        )
        self._mask = rt1_attention_mask(
            self.time_sequence_length, self.tokens_per_image, self.tokens_per_action
        )
        self._action_positions = action_token_positions(
            self.time_sequence_length, self.tokens_per_image, self.tokens_per_action
        )

    # ------------------------------------------------------------------ helpers

    def _preprocess_images(self, image: jnp.ndarray, train: bool) -> jnp.ndarray:
        """uint8→[0,1] plus train-time pad/random-shift crop (preprocessors.py:37-56).

        Deviation from the reference (documented): the reference random-crops in
        *every* forward, inference included (`transformer_network.py:445` has no
        train gate). We crop only when `train=True` — deterministic eval.
        """
        do_crop = train and self.crop_ratio > 0
        image = image_ops.convert_dtype_and_crop_images(
            image,
            rng=self.make_rng("crop") if do_crop else None,
            ratio=self.crop_ratio,
            train=do_crop,
        )
        if train and self.photometric_augmentation:
            # On-device color jitter (Stack B's PhotometricDistortions,
            # `input_pipeline_rlds.py:391-457`), fused into the forward so
            # the host pipeline stays augmentation-free. Dedicated "augment"
            # stream so color randomness is independent of the crop offsets
            # ("crop" fallback keeps old callers working).
            from rt1_tpu.ops.augment import photometric_distortions

            aug_rng = (
                self.make_rng("augment")
                if self.has_rng("augment")
                else self.make_rng("crop")
            )
            image = photometric_distortions(image, aug_rng)
        return image

    def _tokenize_images(
        self, image: jnp.ndarray, context: Optional[jnp.ndarray], train: bool
    ) -> jnp.ndarray:
        """image (b, t, H, W, 3), context (b, t, D) or (b, D) → tokens (b, t, I, E)."""
        if context is not None and context.ndim == 2:
            context = jnp.tile(context[:, None, :], (1, image.shape[1], 1))
        # Device scopes (metadata of the ops, read from a profile): Flax
        # names every module's ops; what runs outside a module is named here.
        with jax.named_scope("preprocess"):
            image = self._preprocess_images(image, train)
        return self.image_tokenizer(image, context=context, train=train)

    def _assemble(self, context_image_tokens: jnp.ndarray) -> jnp.ndarray:
        """(b, t, I, E) → (b, t·(I+A), E) with zeroed action slots (reference :378-390)."""
        b, t, _, e = context_image_tokens.shape
        action_slots = jnp.zeros((b, t, self.tokens_per_action, e), context_image_tokens.dtype)
        seq = jnp.concatenate([context_image_tokens, action_slots], axis=2)
        return seq.reshape(b, t * self.single_step_tokens, e)

    def _pipeline_enabled(self) -> bool:
        return (
            self.mesh is not None
            and getattr(self.mesh, "shape", {}).get("stage", 1) > 1
        )

    def _transformer_logits(self, context_image_tokens: jnp.ndarray, train: bool):
        seq = self._assemble(context_image_tokens)
        mask = jnp.asarray(self._mask)
        if self._pipeline_enabled() and not self.is_initializing():
            # GPipe path: same params, layer stack pipelined over the mesh's
            # "stage" axis. Init still runs the sequential module (below) so
            # the param tree is identical either way.
            if self.return_attention_scores:
                raise ValueError(
                    "attention scores are not materialized under pipeline "
                    "parallelism; use a stage=1 mesh for score visualization"
                )
            from rt1_tpu.parallel.pipeline import pp_causal_transformer_apply

            use_dropout = train and self.dropout_rate > 0
            logits = pp_causal_transformer_apply(
                self.transformer,
                {"params": self.transformer.variables["params"]},
                seq,
                mesh=self.mesh,
                num_microbatches=self.pipeline_microbatches,
                attention_mask=mask,
                train=train,
                dropout_rng=self.make_rng("dropout") if use_dropout else None,
            )
            return logits, None
        out = self.transformer(seq, attention_mask=mask, train=train)
        if self.return_attention_scores:
            return out  # (logits, scores)
        return out, None

    # ------------------------------------------------------------------ training

    def __call__(
        self,
        observations: Dict[str, jnp.ndarray],
        actions: Dict[str, jnp.ndarray],
        train: bool = False,
    ) -> Dict[str, jnp.ndarray]:
        """Training forward (reference `forward` else-branch `:294-332`).

        observations: {'image': (b, t, H, W, 3), 'natural_language_embedding':
        (b, t, D) or (b, D)}; actions: per-key (b, t, ...) labels.

        Returns aux dict mirroring the reference's `get_aux_info` (`:531`):
        loss (scalar), action_loss (b, t), action_predictions (b, t, A),
        action_labels (b, t, A), action_logits (b, t, A, vocab).
        """
        image = observations["image"]
        context = observations.get("natural_language_embedding")
        b, t = image.shape[0], image.shape[1]
        assert t == self.time_sequence_length, (t, self.time_sequence_length)

        context_image_tokens = self._tokenize_images(image, context, train)
        logits, scores = self._transformer_logits(context_image_tokens, train)

        with jax.named_scope("loss"):
            labels = action_tokenizer.tokenize(self.action_space, actions, self.vocab_size)

            # Transformer predicts next token: read logits one position early (:237,304).
            pred_positions = jnp.asarray(self._action_positions - 1)
            action_logits = jnp.take(logits, pred_positions, axis=1)
            action_logits = action_logits.reshape(b, t, self.tokens_per_action, self.vocab_size)

            ce = _softmax_ce_int(action_logits.astype(jnp.float32), labels)  # (b, t, A)
            loss_terms = ce
            if self.focal_gamma > 0:
                # ce = -log p_label, so 1 - p_label = -expm1(-ce); gradients flow
                # through the modulating factor too (the standard focal-loss
                # form). The floor keeps the power branch differentiable at
                # ce == 0 for fractional gamma (x**g has an infinite slope at 0
                # when g < 1, and saturated easy tokens do reach ce == 0 in fp32).
                # Only the optimized loss is modulated; the "cross_entropy" aux
                # output stays raw CE so it remains comparable across gammas.
                base = jnp.maximum(-jnp.expm1(-ce), 1e-12)
                loss_terms = base ** self.focal_gamma * ce
            if self.loss_scale == "reference":
                num_items = float(b * t) * self.single_step_tokens
                action_loss = jnp.mean(loss_terms, axis=-1) / num_items  # (b, t), reference :314-320
            else:
                action_loss = jnp.mean(loss_terms, axis=-1)
            loss = jnp.mean(action_loss)  # harness loss_fn (distribute_train.py:112-118)

            out = {
                "loss": loss,
                "action_loss": action_loss,
                "cross_entropy": ce,
                "action_labels": labels,
                "action_logits": action_logits,
                "action_predictions": jnp.argmax(action_logits, axis=-1),
            }
            if self.aux_mse_weight > 0:
                bins, box_mask = action_tokenizer.box_bin_values(
                    self.action_space, self.vocab_size
                )
                probs = jax.nn.softmax(
                    action_logits.astype(jnp.float32), axis=-1
                )  # (b, t, A, V)
                expected = jnp.einsum("btav,av->bta", probs, jnp.asarray(bins))
                target = action_tokenizer.continuous_targets(
                    self.action_space, actions
                )  # (b, t, A)
                mask = jnp.asarray(box_mask)  # (A,)
                mse = jnp.sum(
                    jnp.square(expected - target) * mask
                ) / (jnp.sum(mask) * b * t)
                # Under 'reference' scaling the CE part is ∝ 1/(b·t·(I+A));
                # giving the aux term the same normalizer keeps (a) gradient
                # accumulation exact (the trainer's extra /accum correction
                # assumes the WHOLE loss is inversely proportional to runtime
                # batch) and (b) the CE/aux balance independent of batch size
                # and sequence length. The reported "aux_mse" metric stays the
                # raw, unit-interpretable mean-squared error.
                if self.loss_scale == "reference":
                    loss = loss + self.aux_mse_weight * mse / num_items
                else:
                    loss = loss + self.aux_mse_weight * mse
                out["loss"] = loss
                out["aux_mse"] = mse
        if scores is not None:
            out["attention_scores"] = scores
        return out

    # ------------------------------------------------------------------ inference

    def initial_state(
        self, batch_size: int, cached: bool = False
    ) -> Dict[str, jnp.ndarray]:
        """Zeroed rolling window state (reference `_state_space:105-123`).

        ``cached=True`` adds the per-layer transformer K/V cache consumed by
        `infer_step_cached` — one (b, layers, 2, sequence_tokens, heads,
        key_dim) leaf at the compute dtype. Default off: the state schema
        (and therefore every existing serving/eval program) is byte-
        identical to the pre-cache layout.
        """
        state = {
            "context_image_tokens": jnp.zeros(
                (batch_size, self.time_sequence_length, self.tokens_per_image,
                 self.token_embedding_size),
                jnp.float32,
            ),
            "action_tokens": jnp.zeros(
                (batch_size, self.time_sequence_length, self.tokens_per_action), jnp.int32
            ),
            "seq_idx": jnp.zeros((), jnp.int32),
        }
        if cached:
            state["kv_cache"] = jnp.zeros(
                (batch_size, self.num_layers, 2, self.sequence_tokens,
                 self.num_heads, self.layer_size),
                self.dtype,
            )
        return state

    def _advance_window(self, observation, state):
        """Shared inference prologue: roll-if-full, tokenize frame, insert (reference
        `_tokenize_images:462-482` / `_tokenize_actions:487-492`)."""
        seq_idx = state["seq_idx"]
        t_max = self.time_sequence_length
        time_step = jnp.minimum(seq_idx, t_max - 1)

        img_state = state["context_image_tokens"]
        act_state = state["action_tokens"]
        full = seq_idx == t_max
        img_state = jnp.where(full, jnp.roll(img_state, -1, axis=1), img_state)
        act_state = jnp.where(full, jnp.roll(act_state, -1, axis=1), act_state)

        image = observation["image"][:, None]  # (b, 1, H, W, 3)
        context = observation.get("natural_language_embedding")
        new_tokens = self._tokenize_images(image, context, train=False)  # (b, 1, I, E)
        img_state = jax.lax.dynamic_update_slice_in_dim(
            img_state, new_tokens.astype(img_state.dtype), time_step, axis=1
        )
        return img_state, act_state, time_step, seq_idx

    def infer_step(
        self, observation: Dict[str, jnp.ndarray], state: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """One control step, SINGLE transformer pass (vs reference's A passes :246-268).

        observation: {'image': (b, H, W, 3), 'natural_language_embedding': (b, D)}.
        Returns ({'action_tokens', 'action_logits', <detokenized action>}, new_state).
        """
        img_state, act_state, time_step, seq_idx = self._advance_window(observation, state)

        logits, _ = self._transformer_logits(img_state, train=False)
        start = time_step * self.single_step_tokens + self.tokens_per_image - 1
        step_logits = jax.lax.dynamic_slice_in_dim(
            logits, start, self.tokens_per_action, axis=1
        )  # (b, A, vocab)
        tokens = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)  # (b, A)

        act_state = jax.lax.dynamic_update_slice_in_dim(
            act_state, tokens[:, None, :], time_step, axis=1
        )
        new_state = {
            "context_image_tokens": img_state,
            "action_tokens": act_state,
            "seq_idx": jnp.minimum(seq_idx + 1, self.time_sequence_length),
        }
        output = {"action_tokens": tokens, "action_logits": step_logits}
        output.update(self._decode_action(tokens, step_logits))
        return output, new_state

    def infer_step_cached(
        self, observation: Dict[str, jnp.ndarray], state: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """One control step against the per-session K/V cache: tokenize the
        incoming frame, run the transformer over ONLY its
        `single_step_tokens` new positions, attend them against the cached
        prefix, and roll the cache in place.

        Same observation/state/output contract as `infer_step`, plus a
        `kv_cache` state leaf (`initial_state(..., cached=True)`). While the
        window is filling the cached prefix is position-exact, so the step
        logits equal the full-window pass to float tolerance (pinned in
        tests/test_rt1_cache.py). Once the window is full, each step shifts
        the cache down by `single_step_tokens` (the ISSUE's shift layout):
        surviving entries keep the K/V they were computed with — their
        learned absolute position rows and their insertion-time context go
        stale by one frame per roll — while the new frame's queries stay
        position-exact. That staleness is the cached path's only deviation
        from `infer_step`; `serve/parity.check_cached_parity` gates it at
        the same ≥0.99 action-token-agreement contract as the quant gate,
        and `PolicyEngine` bounds it by rebuilding caches (`rebuild_cache`)
        on every invalidation event.
        """
        seq_idx = state["seq_idx"]
        t_max = self.time_sequence_length
        step = self.single_step_tokens
        time_step = jnp.minimum(seq_idx, t_max - 1)

        img_state = state["context_image_tokens"]
        act_state = state["action_tokens"]
        kv = state["kv_cache"]
        full = seq_idx == t_max
        img_state = jnp.where(full, jnp.roll(img_state, -1, axis=1), img_state)
        act_state = jnp.where(full, jnp.roll(act_state, -1, axis=1), act_state)
        kv = jnp.where(full, jnp.roll(kv, -step, axis=3), kv)

        image = observation["image"][:, None]  # (b, 1, H, W, 3)
        context = observation.get("natural_language_embedding")
        new_tokens = self._tokenize_images(image, context, train=False)  # (b, 1, I, E)
        img_state = jax.lax.dynamic_update_slice_in_dim(
            img_state, new_tokens.astype(img_state.dtype), time_step, axis=1
        )

        # The new frame's step block: image tokens + zeroed action slots,
        # exactly one row of `_assemble`'s layout (f32 like the stored
        # window so the transformer's input cast matches the full pass).
        frame = new_tokens[:, 0].astype(img_state.dtype)  # (b, I, E)
        b = frame.shape[0]
        step_inputs = jnp.concatenate(
            [frame, jnp.zeros((b, self.tokens_per_action, frame.shape[-1]), frame.dtype)],
            axis=1,
        )  # (b, I+A, E)
        q_start = time_step * step
        # Decode mask = this step block's rows of the full (S, S) RT-1 mask;
        # causal zeros past q_start+len already exclude the unwritten tail
        # of a filling cache.
        dec_mask = jax.lax.dynamic_slice_in_dim(
            jnp.asarray(self._mask), q_start, step, axis=0
        )  # (I+A, S)
        logits, new_kv = self.transformer(
            step_inputs,
            attention_mask=dec_mask,
            train=False,
            kv_cache=kv,
            cache_index=q_start,
        )  # (b, I+A, vocab)

        # Within the block, action logits sit one position early
        # (the shift-by-one read, same as infer_step's `start`).
        i0 = self.tokens_per_image - 1
        step_logits = jax.lax.slice_in_dim(
            logits, i0, i0 + self.tokens_per_action, axis=1
        )  # (b, A, vocab)
        tokens = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)

        act_state = jax.lax.dynamic_update_slice_in_dim(
            act_state, tokens[:, None, :], time_step, axis=1
        )
        new_state = {
            "context_image_tokens": img_state,
            "action_tokens": act_state,
            "seq_idx": jnp.minimum(seq_idx + 1, self.time_sequence_length),
            "kv_cache": new_kv,
        }
        output = {"action_tokens": tokens, "action_logits": step_logits}
        output.update(self._decode_action(tokens, step_logits))
        return output, new_state

    def rebuild_cache(self, state: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Recompute every K/V cache row from the stored per-frame image
        tokens — one full-window transformer pass, identical math to
        `infer_step`'s `_transformer_logits`.

        This is the cache invalidation primitive: after a params hot-swap
        (or any event that makes cached K/V stale relative to the window's
        image tokens) the serving engine runs this once per slot instead of
        serving poisoned caches. The rebuilt rows are position-exact AND
        context-exact for the current window, so the next cached step
        matches the full-window pass bit-for-bit-close again.
        """
        seq = self._assemble(state["context_image_tokens"])  # (b, S, E)
        mask = jnp.asarray(self._mask)  # (S, S)
        _, new_kv = self.transformer(
            seq,
            attention_mask=mask,
            train=False,
            kv_cache=jnp.zeros_like(state["kv_cache"]),
            cache_index=jnp.zeros((), jnp.int32),
        )
        return dict(state, kv_cache=new_kv)

    def _decode_action(self, tokens, step_logits):
        """Token→action decode shared by both inference paths
        (`action_decode`: hard argmax detokenize vs soft E[a])."""
        if self.action_decode == "expected":
            return action_tokenizer.detokenize_expected(
                self.action_space, step_logits, self.vocab_size
            )
        return action_tokenizer.detokenize(
            self.action_space, tokens, self.vocab_size
        )

    def infer_step_autoregressive(
        self, observation: Dict[str, jnp.ndarray], state: Dict[str, jnp.ndarray]
    ) -> Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]:
        """Literal port of the reference's token-by-token loop (`:246-268`): A full
        transformer passes, argmaxing one position each. Exists to prove equivalence
        with `infer_step` (action inputs are zeroed, so the passes are identical) and
        for benchmark comparison; not used in production."""
        img_state, act_state, time_step, seq_idx = self._advance_window(observation, state)

        start = time_step * self.single_step_tokens + self.tokens_per_image - 1
        toks = []
        logit_slices = []
        for k in range(self.tokens_per_action):
            logits, _ = self._transformer_logits(img_state, train=False)
            sl = jax.lax.dynamic_slice_in_dim(logits, start + k, 1, axis=1)  # (b, 1, V)
            tok = jnp.argmax(sl, axis=-1).astype(jnp.int32)  # (b, 1)
            toks.append(tok)
            logit_slices.append(sl)
            # The reference writes the predicted token back into action_tokens
            # (:261-268); it cannot affect later passes (inputs zeroed) but we
            # mirror the state update.
            act_state = jax.lax.dynamic_update_slice(
                act_state, tok[:, None, :], (0, time_step, k)
            )
        tokens = jnp.concatenate(toks, axis=1)
        step_logits = jnp.concatenate(logit_slices, axis=1)

        new_state = {
            "context_image_tokens": img_state,
            "action_tokens": act_state,
            "seq_idx": jnp.minimum(seq_idx + 1, self.time_sequence_length),
        }
        output = {"action_tokens": tokens, "action_logits": step_logits}
        output.update(self._decode_action(tokens, step_logits))
        return output, new_state


def _softmax_ce_int(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """Cross-entropy with integer labels (optax-equivalent, kept dependency-light)."""
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    label_logits = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return logz - label_logits

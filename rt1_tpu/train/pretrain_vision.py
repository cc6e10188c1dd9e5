"""Hermetic vision pretraining: block-state regression from sim frames.

The reference initializes its image tower from ImageNet-pretrained
EfficientNet-B3 weights
(`/root/reference/pytorch_robotics_transformer/film_efficientnet/
film_efficientnet_encoder.py:376-425`); this image carries no pretrained
blobs and no network, so every arm so far trained vision from scratch —
and round 4 concluded the learning failure is perception-limited.
This module is the in-image substitute (VERDICT r4 next #3):
the simulator generates unlimited (frame, block/effector position) pairs
for free, so the encoder can be pretrained on *state regression* — exactly
the visual competence the policy needs — and then grafted into the RT-1
tokenizer as its initialization.

It doubles as a **perception-capacity probe**: the attainable position
error of a given (encoder, resolution) on this task is a direct measure of
what the policy's vision can resolve, independent of BC/DAgger dynamics —
the measured answer to round 4's "capacity, initialization, or both?"
confound (VERDICT r4 weak #4).

The encoder module tree is identical to the one inside
`RT1ImageTokenizer` (``EfficientNetEncoder`` under name ``"encoder"``), so
`graft_encoder_into_policy` is a pure subtree transplant with shape
validation — no porting, no renaming.
"""

from __future__ import annotations

import json
import os
from typing import Optional

import flax
import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from rt1_tpu.models.encoder import EfficientNetEncoder


def generate_state_regression_dataset(
    num_frames: int,
    block_mode: str = "BLOCK_4",
    seed: int = 0,
    image_hw: tuple[int, int] = (64, 96),
    random_steps: int = 8,
    reward_name: str = "block2block",
):
    """Render `num_frames` frames with ground-truth block/effector targets.

    Each sample: reset to a randomized board, take `U[0, random_steps]`
    uniform random effector actions (diversifying effector pose and block
    contact states), then record (resized rgb, [effector_xy, block_xy...]).
    Labels are free — the sim knows its own state — which is what makes
    this pretraining hermetic.

    Returns (images uint8 (N,H,W,3), targets float32 (N,D), target_names).
    """
    import cv2

    from rt1_tpu.envs import blocks, rewards
    from rt1_tpu.envs.language_table import LanguageTable

    env = LanguageTable(
        block_mode=blocks.BlockMode(block_mode),
        reward_factory=rewards.get_reward_factory(reward_name),
        seed=seed,
    )
    rng = np.random.default_rng(seed)
    images, targets = [], []
    target_names: Optional[list[str]] = None
    while len(images) < num_frames:
        env.reset()
        for _ in range(int(rng.integers(0, random_steps + 1))):
            env.step(rng.uniform(-0.03, 0.03, size=2).astype(np.float32))
        state = env.compute_state(request_task_update=False)
        block_keys = sorted(
            k for k in state if k.startswith("block_")
            and k.endswith("_translation")
        )
        if target_names is None:
            target_names = ["effector_x", "effector_y"] + [
                f"{k}_{ax}" for k in block_keys for ax in ("x", "y")
            ]
        vec = np.concatenate(
            [np.asarray(state["effector_translation"], np.float32)]
            + [np.asarray(state[k], np.float32) for k in block_keys]
        )
        rgb = cv2.resize(
            np.asarray(state["rgb"]), (image_hw[1], image_hw[0]),
            interpolation=cv2.INTER_LINEAR,
        )
        images.append(rgb.astype(np.uint8))
        targets.append(vec)
    return np.stack(images), np.stack(targets), target_names


class VisionPretrainModel(nn.Module):
    """EfficientNetEncoder (the exact RT1ImageTokenizer submodule) + a
    regression head. FiLM context is zeros during pretraining — the FiLM
    projections are zero-initialized (models/film.py), so the grafted
    encoder behaves identically until language conditioning trains."""

    target_dim: int
    token_embedding_size: int = 512
    width_coefficient: float = 0.35
    depth_coefficient: float = 0.35
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, images: jnp.ndarray, train: bool = False):
        x = images.astype(jnp.float32) / 255.0  # ops/image.py convention
        context = jnp.zeros((x.shape[0], 512), self.dtype)
        feats = EfficientNetEncoder(
            token_embedding_size=self.token_embedding_size,
            early_film=True,
            pooling=True,
            dtype=self.dtype,
            width_coefficient=self.width_coefficient,
            depth_coefficient=self.depth_coefficient,
            name="encoder",
        )(x, context=context, train=train)
        return nn.Dense(self.target_dim, name="head")(feats)


def pretrain_encoder(
    images: np.ndarray,
    targets: np.ndarray,
    *,
    num_steps: int = 3000,
    batch_size: int = 32,
    learning_rate: float = 1e-3,
    val_fraction: float = 0.1,
    seed: int = 0,
    width_coefficient: float = 0.35,
    depth_coefficient: float = 0.35,
    token_embedding_size: int = 512,
    eval_every: int = 500,
    log=print,
):
    """Train the probe; return (variables, metrics).

    Targets are standardized per-dimension (mean/std recorded in metrics);
    the reported `val_rmse` is de-standardized — board units (meters for
    Language-Table translations), directly comparable across encoders and
    resolutions.
    """
    import optax

    n_val = max(1, int(len(images) * val_fraction))
    train_x, val_x = images[n_val:], images[:n_val]
    train_y, val_y = targets[n_val:], targets[:n_val]
    mu = train_y.mean(axis=0)
    sd = train_y.std(axis=0) + 1e-8
    train_yn = (train_y - mu) / sd
    val_yn = (val_y - mu) / sd

    model = VisionPretrainModel(
        target_dim=targets.shape[1],
        width_coefficient=width_coefficient,
        depth_coefficient=depth_coefficient,
        token_embedding_size=token_embedding_size,
    )
    rng = jax.random.PRNGKey(seed)
    variables = model.init(rng, jnp.asarray(train_x[:2]), train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    tx = optax.adam(learning_rate)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state, bx, by, dropout_rng):
        def loss_fn(p):
            out, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                bx, train=True,
                mutable=["batch_stats"],
                rngs={"dropout": dropout_rng},
            )
            return jnp.mean((out - by) ** 2), mut["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_stats, opt_state, loss

    @jax.jit
    def eval_err(params, batch_stats, bx):
        return model.apply(
            {"params": params, "batch_stats": batch_stats}, bx, train=False
        )

    def val_rmse(params, batch_stats):
        preds = []
        for i in range(0, len(val_x), batch_size):
            preds.append(np.asarray(eval_err(
                params, batch_stats, jnp.asarray(val_x[i:i + batch_size])
            )))
        preds = np.concatenate(preds) * sd + mu
        return float(np.sqrt(np.mean((preds - val_y) ** 2)))

    data_rng = np.random.default_rng(seed)
    history = []
    for step in range(num_steps):
        idx = data_rng.integers(0, len(train_x), batch_size)
        rng, dropout_rng = jax.random.split(rng)
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state,
            jnp.asarray(train_x[idx]), jnp.asarray(train_yn[idx]),
            dropout_rng,
        )
        if step % eval_every == 0 or step == num_steps - 1:
            rmse = val_rmse(params, batch_stats)
            history.append({"step": step, "train_loss": float(loss),
                            "val_rmse": rmse})
            log(f"pretrain step {step}: loss {float(loss):.4f} "
                f"val_rmse {rmse * 1000:.2f} mm")
    variables = {"params": params, "batch_stats": batch_stats}
    metrics = {
        "val_rmse": history[-1]["val_rmse"],
        "val_rmse_mm": history[-1]["val_rmse"] * 1000.0,
        "history": history,
        "target_mean": mu.tolist(),
        "target_std": sd.tolist(),
        "num_train_frames": int(len(train_x)),
        "num_val_frames": int(len(val_x)),
    }
    return variables, metrics


def save_encoder(variables, metrics, path: str) -> None:
    """Serialize the ENCODER subtree (+ metrics sidecar JSON) to `path`."""
    enc = {
        "params": variables["params"]["encoder"],
        "batch_stats": variables.get("batch_stats", {}).get("encoder", {}),
    }
    with open(path, "wb") as f:
        f.write(flax.serialization.to_bytes(enc))
    with open(path + ".json", "w") as f:
        json.dump({k: v for k, v in metrics.items() if k != "history"}
                  | {"history": metrics.get("history", [])}, f, indent=2)


def load_encoder(path: str):
    """Inverse of `save_encoder` (structure restored from the bytes)."""
    with open(path, "rb") as f:
        return flax.serialization.msgpack_restore(f.read())


def graft_encoder_into_policy(policy_variables, encoder,
                              tokenizer_name: str | None = None):
    """Transplant pretrained encoder leaves into the policy's variables.

    Validates leaf-by-leaf shape equality (a resolution change is fine —
    the encoder is fully convolutional — but a width/depth-coefficient
    mismatch is a hard error, not a silent partial graft). Returns new
    variables; input unmodified.

    `tokenizer_name` defaults to auto-detection: the policy's tokenizer
    tree is named "image_tokenizer_def" when the module was passed into
    `RT1Policy` (Flax names passed-in submodules by field name — the
    `build_model` path) and "image_tokenizer" when constructed in setup.
    """
    if tokenizer_name is None:
        candidates = [
            k for k, v in policy_variables["params"].items()
            if isinstance(v, dict) and "encoder" in v
        ]
        if len(candidates) != 1:
            raise ValueError(
                f"could not locate the image-tokenizer subtree (top-level "
                f"keys with an 'encoder' child: {candidates}); pass "
                f"tokenizer_name explicitly"
            )
        tokenizer_name = candidates[0]
    def check_and_cast(dst_tree, src_tree, scope):
        dst_flat = flax.traverse_util.flatten_dict(dst_tree)
        src_flat = flax.traverse_util.flatten_dict(src_tree)
        if set(dst_flat) != set(src_flat):
            missing = set(dst_flat) ^ set(src_flat)
            raise ValueError(
                f"pretrained encoder {scope} tree mismatch "
                f"(differing keys: {sorted(missing)[:4]}...): was it trained "
                f"with the same width/depth coefficients?"
            )
        out = {}
        for k, dst in dst_flat.items():
            src = src_flat[k]
            if tuple(dst.shape) != tuple(np.shape(src)):
                raise ValueError(
                    f"pretrained encoder {scope} shape mismatch at "
                    f"{'/'.join(k)}: checkpoint {np.shape(src)} vs model "
                    f"{tuple(dst.shape)}"
                )
            out[k] = jnp.asarray(src, dst.dtype)
        return flax.traverse_util.unflatten_dict(out)

    params = flax.core.unfreeze(policy_variables["params"])
    params[tokenizer_name]["encoder"] = check_and_cast(
        params[tokenizer_name]["encoder"], encoder["params"], "params"
    )
    out = dict(policy_variables)
    out["params"] = params
    stats = flax.core.unfreeze(policy_variables.get("batch_stats", {}))
    if stats and encoder.get("batch_stats"):
        stats[tokenizer_name]["encoder"] = check_and_cast(
            stats[tokenizer_name]["encoder"], encoder["batch_stats"],
            "batch_stats",
        )
        out["batch_stats"] = stats
    return out

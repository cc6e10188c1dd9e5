"""One record per model family: what the train loop asks of a family.

``train_and_evaluate`` is one loop for every family; a family is its model
constructor, its init signature, its loss closure, the host feed it trains on
when no dataset is configured, the shapes of one batch, and whether
``parallel/plan.py``'s rules describe its parameter paths.  ``FAMILIES`` maps
``config.model.family`` to that record; a new decoder built from a block
description (rt1_tpu/models/lm) is one more name for ``_DECODER_LM`` and a base
config of its own (rt1_tpu/train/configs), not a branch in the loop.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class Family:
    # (model_config, mesh) -> (model, init_fn, loss_fn); None for the two hooks
    # means the trainer's own (RT1Policy's init signature and loss)
    build: Callable[[Any, Any], Tuple[Any, Optional[Callable], Optional[Callable]]]
    # (config, seed) -> iterator of host batches when ``data.data_dir`` is unset
    host_feed: Callable[[Any, int], Iterator]
    # config -> abstract (observations, actions) of one host batch, where the
    # configuration alone gives them (an ahead-of-time compile needs no feed)
    batch_spec: Optional[Callable[[Any], Tuple[Dict[str, Any], Dict[str, Any]]]] = None
    planned: bool = False       # parallel/plan.py's rules describe its parameter paths
    pipelined: bool = False     # a mesh "stage" axis > 1 pipelines its decoder
    task_ids: bool = False      # its health pack reads the feeder's per-example task ids


# ------------------------------------------------------------------- RT-1

def _build_rt1(model_config, mesh):
    from rt1_tpu.train.train import build_model

    return build_model(model_config, mesh=mesh), None, None


def synthetic_batches(config, seed=0) -> Iterator:
    """Random fixed batches when no dataset is configured (smoke/bench)."""
    rng = np.random.default_rng(seed)
    b = config.per_host_batch_size
    t = config.model.time_sequence_length
    h, w = config.data.height, config.data.width
    while True:
        obs = {
            "image": rng.random((b, t, h, w, 3), dtype=np.float32),
            "natural_language_embedding": rng.standard_normal(
                (b, t, 512), dtype=np.float32
            ),
        }
        actions = {
            "terminate_episode": rng.integers(
                0, 2, (b, t), dtype=np.int32
            ),
            "action": rng.uniform(-0.1, 0.1, (b, t, 2)).astype(np.float32),
        }
        yield {"observations": obs, "actions": actions}


# ------------------------------------------------------------------- LAVA

def _build_lava(model_config, mesh):
    del mesh
    from rt1_tpu.models.lava import SequenceLAVMSE
    from rt1_tpu.trainer.bc import adapt_obs_for_lava, make_bc_step_loss_fn

    lv = model_config.lava
    text_encoder_def = None
    if lv.lang_encoder == "clip":
        from rt1_tpu.models.lava.clip_text import CLIPTextEncoder

        text_encoder_def = CLIPTextEncoder(
            vocab_size=lv.get("text_vocab", 514),
            context_length=lv.get("text_context", 77),
            width=lv.get("text_width", 512),
            num_layers=lv.get("text_layers", 12),
            num_heads=lv.get("text_heads", 8),
            embed_dim=lv.get("text_embed_dim", 512),
        )
    model = SequenceLAVMSE(
        action_size=lv.action_size,
        dense_resnet_width=lv.dense_resnet_width,
        dense_resnet_num_blocks=lv.dense_resnet_num_blocks,
        lava_num_layers=lv.num_layers,
        lava_sequence_length=model_config.time_sequence_length,
        lava_temporal_transformer_num_layers=lv.temporal_num_layers,
        lava_d_model=lv.d_model,
        lava_num_heads=lv.num_heads,
        lava_pyramid_fuse_layers=tuple(lv.pyramid_fuse_layers),
        lava_image_encoder=lv.image_encoder,
        lava_lang_encoder=lv.lang_encoder,
        text_encoder_def=text_encoder_def,
    )

    def init_fn(model, rng, obs, actions):
        return model.init(
            {"params": rng}, adapt_obs_for_lava(obs), train=False
        )

    return model, init_fn, make_bc_step_loss_fn(model)


# ------------------------------------- decoder LMs from a block description

def _build_decoder_lm(model_config, mesh):
    """``DecoderLM`` over ``LMSpec.from_config(model.lm)``: every family of
    rt1_tpu/models/lm is this builder under its own base config
    (docs/lm_family.md)."""
    del mesh
    from rt1_tpu.models.lm import DecoderLM, LMSpec, make_lm_step_loss_fn

    model = DecoderLM(LMSpec.from_config(
        model_config.lm, jnp.dtype(model_config.get("dtype", "float32"))))

    def init_fn(model, rng, obs, actions):
        return model.init({"params": rng}, obs, actions, train=False)

    return model, init_fn, make_lm_step_loss_fn(model)


def _token_feed(config, seed):
    from rt1_tpu.data.tokens import feed_from_config

    return feed_from_config(config, seed)


def _token_batch_spec(config):
    shape = (int(config.per_host_batch_size), int(config.model.lm.seq_len))
    return ({"tokens": jax.ShapeDtypeStruct(shape, jnp.int32)},
            {"targets": jax.ShapeDtypeStruct(shape, jnp.int32)})


# every decoder built from a block description: one builder, one feed; what
# tells two of them apart is their base config (rt1_tpu/train/configs)
_DECODER_LM = Family(build=_build_decoder_lm, host_feed=_token_feed,
                     batch_spec=_token_batch_spec, planned=True)


FAMILIES: Dict[str, Family] = {
    "rt1": Family(build=_build_rt1, host_feed=synthetic_batches,
                  planned=True, pipelined=True, task_ids=True),
    "lava": Family(build=_build_lava, host_feed=synthetic_batches),
    # configs/lfm2_moe.py: gated short convolutions + full attention, sigmoid router
    "lfm2_moe": _DECODER_LM,
    # configs/mellum.py: sliding and full attention 3:1, softmax router, untied head
    "mellum": _DECODER_LM,
    # configs/xing4_0.py: latent attention, four residual streams mixed by maps a
    # token, sigmoid router beside a shared expert, a multi-token-prediction module
    "xing4_0": _DECODER_LM,
}


def family_of(model_config) -> Family:
    name = model_config.get("family", "rt1")
    if name not in FAMILIES:
        raise ValueError(f"Unknown model family: {name!r}")
    return FAMILIES[name]

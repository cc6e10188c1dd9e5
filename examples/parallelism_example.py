"""One train step under each parallelism mode on an 8-device virtual mesh.

The reference's only parallelism is data-parallel DDP
(`distribute_train.py:235`); this framework's mesh covers three modes, all
reachable from the train config (`config.parallel.*`). This
example runs ONE optimizer step of a tiny RT-1 under each, hermetically on
CPU (`--xla_force_host_platform_device_count=8` — the same GSPMD
partitioner and collectives XLA uses on a real TPU slice).

Run:
  JAX_PLATFORMS=cpu \
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/parallelism_example.py
"""

import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

os.environ.setdefault("JAX_PLATFORMS", "cpu")
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

from rt1_tpu.models.rt1 import RT1Policy
from rt1_tpu.models.tiny_tokenizer import TinyImageTokenizer
from rt1_tpu.parallel import MeshConfig, make_mesh
from rt1_tpu.specs import language_table_action_space, sample_space
from rt1_tpu.trainer import (
    create_train_state,
    make_optimizer,
    make_train_step_fns,
)

T, EMB = 2, 16


def tiny(**kw):
    cfg = dict(
        action_space=language_table_action_space(),
        vocab_size=32,
        token_embedding_size=EMB,
        num_layers=4,
        layer_size=8,
        num_heads=2,
        feed_forward_size=16,
        dropout_rate=0.0,
        time_sequence_length=T,
        num_image_tokens=2,
        image_tokenizer_def=TinyImageTokenizer(num_tokens=2, emb=EMB),
    )
    cfg.update(kw)
    return RT1Policy(**cfg)


def batch(rng, b=8):
    obs = {
        "image": jax.random.uniform(rng, (b, T, 16, 16, 3)),
        "natural_language_embedding": jax.random.normal(
            jax.random.fold_in(rng, 1), (b, T, 8)
        ),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 2), (b, T)
    )
    return obs, actions


def main():
    rng = jax.random.PRNGKey(0)
    obs, actions = batch(rng)
    tx = make_optimizer(learning_rate=1e-3)

    modes = [
        # (label, mesh config, model kwargs)
        ("dp  (data parallel, DDP equivalent)", MeshConfig(), {}),
        ("tp  (tensor parallel heads/FFN)", MeshConfig(data=2, model=4), {}),
        ("pp  (GPipe over decoder layers)", MeshConfig(data=2, stage=4),
         dict(pipeline_microbatches=2)),
    ]
    for label, mesh_cfg, model_kw in modes:
        mesh = make_mesh(mesh_cfg)
        kw = dict(model_kw)
        if mesh.shape["stage"] > 1:
            kw.update(mesh=mesh)
        model = tiny(**kw)
        state = create_train_state(model, rng, (obs, actions), tx)
        fns = make_train_step_fns(model, mesh, state, donate=False)
        s = fns.shard_state(state)
        b = fns.shard_batch((obs, actions))
        s, metrics = fns.train_step(s, b, jax.random.PRNGKey(1))
        print(
            f"{label:40s} mesh={dict(mesh.shape)} "
            f"loss={float(metrics['loss']):.5f} "
            f"grad_norm={float(metrics['grad_norm']):.4f}"
        )


if __name__ == "__main__":
    main()

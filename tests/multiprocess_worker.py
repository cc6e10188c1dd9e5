"""Worker body for the FAST 2-process scale-out smoke (ISSUE 14).

Launched (twice) by tests/test_multiprocess.py with:
  python tests/multiprocess_worker.py <process_id> <coordinator_port> <workdir>

2 processes x 2 forced host devices = a 4-device slice, small enough for
tier-1 (the heavyweight 2x4 topology with the full Orbax matrix stays in
the slow tests/test_distributed.py). Covers the ISSUE 14 surfaces end to
end on a REAL multi-process backend:

* `parallel/distributed.py initialize_from_config` via the RT1_* env
  fallbacks (the config block carries only `enabled`);
* `config.parallel.auto` resolving against the GLOBAL device set with the
  host-contiguous rebalance (4 global / 2 local -> (2, 2, 1): dp crosses
  hosts, fsdp stays intra-host);
* per-host packed-feeder slices (disjoint stripes written for the parent
  to verify) feeding `device_feeder`'s
  `jax.make_array_from_process_local_data` path;
* 3 REAL train steps on the dp x fsdp mesh through
  `make_train_step_fns(plan=)` — losses written for the parent's
  single-process parity check;
* multi-process Orbax save through our CheckpointManager (provenance
  marker from process 0 only), `latest_step` tolerating another host's
  in-progress tmp dirs, and a plan-migrating restore verified on-mesh.

The parent (and only the parent) asserts cross-process properties; each
worker writes `ok_<pid>` exactly when every local assertion passed.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def setup_worker_runtime():
    """Worker-process-only runtime knobs — called from __main__ BEFORE any
    device access, never on import (the parent test imports this module
    for `train_losses` and must keep its own single-process backend)."""
    from rt1_tpu.parallel.distributed import force_cpu_multiprocess_runtime

    force_cpu_multiprocess_runtime(2)

SEED = 7
LOCAL_BATCH = 2  # x2 processes = global batch 4
WINDOW = 2
STEPS = 3
H, W = 16, 24


def tiny_model():
    """The same inline tiny RT-1 the slow distributed smoke trains —
    param paths match the declarative plan's rules."""
    from rt1_tpu.models.rt1 import RT1Policy
    from rt1_tpu.models.tiny_tokenizer import TinyImageTokenizer
    from rt1_tpu.specs import language_table_action_space

    return RT1Policy(
        action_space=language_table_action_space(),
        vocab_size=32,
        token_embedding_size=16,
        num_layers=2,
        layer_size=8,
        num_heads=2,
        feed_forward_size=16,
        dropout_rate=0.0,
        time_sequence_length=WINDOW,
        num_image_tokens=2,
        image_tokenizer_def=TinyImageTokenizer(num_tokens=2, emb=16),
    )


def build_corpus(data_dir: str) -> str:
    """4 synthetic episodes packed without crop augmentation (crop parity
    across host slices has its own in-process test, test_feeder.py)."""
    import numpy as np

    from rt1_tpu.data import episodes as ep_lib
    from rt1_tpu.data import pack as pack_lib

    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        p = os.path.join(data_dir, f"episode_{i}.npz")
        ep_lib.save_episode(
            p,
            ep_lib.generate_synthetic_episode(
                rng, num_steps=6, height=H, width=W
            ),
        )
        paths.append(p)
    pack_dir = os.path.join(data_dir, "packed")
    pack_lib.pack_episodes(paths, pack_dir, H, W, None)
    return pack_dir


def train_losses(pack_dir, plan, process_index, process_count, local_batch):
    """(losses, final_state, fns): `STEPS` train steps of the tiny policy
    over the packed feeder's host slice, batches laid out by
    `device_feeder` (the make_array_from_process_local_data path on
    multi-process runs). Pure fn of (corpus, plan geometry, SEED) — the
    parent reruns it single-process for the parity check."""
    import jax
    import numpy as np

    from rt1_tpu.data import pack as pack_lib
    from rt1_tpu.data.feeder import SampleAheadFeeder
    from rt1_tpu.data.pipeline import device_feeder
    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )

    cache = pack_lib.PackedEpisodeCache(pack_dir, window=WINDOW)
    feeder = SampleAheadFeeder(
        cache,
        local_batch,
        seed=SEED,
        num_epochs=2,
        process_index=process_index,
        process_count=process_count,
    )
    model = tiny_model()
    first = next(iter(feeder))
    example = (first["observations"], first["actions"])
    rng = jax.random.PRNGKey(SEED)
    host_state = create_train_state(
        model, rng, example, make_optimizer(steps_per_epoch=10)
    )
    fns = make_train_step_fns(
        model, plan.mesh, host_state, plan=plan, donate=False
    )
    state = fns.shard_state(host_state)
    dev_iter = device_feeder(
        iter([first] + [next(feeder) for _ in range(STEPS - 1)]),
        fns.batch_sharding,
    )
    losses = []
    for i, batch in enumerate(dev_iter):
        state, metrics = fns.train_step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(np.asarray(jax.device_get(metrics["loss"]))))
    feeder.close()
    return losses, state, fns, feeder


def main():
    process_id = int(sys.argv[1])
    port = sys.argv[2]
    workdir = sys.argv[3]

    # Distributed init through the CONFIG seam with env fallbacks — the
    # exact path a pod launcher uses (one config file, per-host env).
    os.environ["RT1_COORDINATOR"] = f"127.0.0.1:{port}"
    os.environ["RT1_PROCESS_ID"] = str(process_id)
    os.environ["RT1_NUM_PROCESSES"] = "2"

    from rt1_tpu.parallel import ShardingPlan, initialize_from_config

    config = {"parallel": {"auto": True, "distributed": {"enabled": True}}}
    assert initialize_from_config(config)
    assert not initialize_from_config(config)  # idempotent

    import jax

    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 2
    assert jax.device_count() == 4

    import numpy as np

    # --- plan resolution against the GLOBAL device set: 4 devices, 2 per
    # host -> the auto table's (2, 2, 1) with dp crossing hosts (outermost
    # mesh axis over the host-major device list) and fsdp intra-host.
    plan = ShardingPlan.from_config(config)
    assert dict(plan.mesh.shape) == {
        "data": 2, "stage": 1, "fsdp": 2, "model": 1
    }, dict(plan.mesh.shape)
    mesh_devs = plan.mesh.devices  # (data, stage, fsdp, model)
    for d in range(2):
        hosts = {
            dev.process_index for dev in mesh_devs[d].reshape(-1)
        }
        assert len(hosts) == 1, f"fsdp block {d} spans hosts {hosts}"

    # --- shared packed corpus (process 0 writes, 1 waits on the marker).
    data_dir = os.path.join(workdir, "data")
    ready = os.path.join(workdir, "data_ready")
    if process_id == 0:
        build_corpus(data_dir)
        open(ready, "w").close()
    else:
        import time

        for _ in range(600):
            if os.path.exists(ready):
                break
            time.sleep(0.05)
        else:
            raise TimeoutError(f"corpus marker {ready} never appeared")
    pack_dir = os.path.join(data_dir, "packed")

    # --- train: per-host feeder slice -> global arrays -> dp x fsdp step.
    losses, state, fns, feeder = train_losses(
        pack_dir, plan, jax.process_index(), jax.process_count(), LOCAL_BATCH
    )
    assert np.isfinite(losses).all(), losses
    with open(os.path.join(workdir, f"windows_{process_id}.txt"), "w") as f:
        f.write(",".join(map(str, feeder.host_order(0).tolist())))
    with open(os.path.join(workdir, f"losses_{process_id}.txt"), "w") as f:
        f.write(",".join(f"{x:.8f}" for x in losses))

    # --- multi-process checkpointing through our manager: every process
    # participates in the save; the provenance marker comes from process 0
    # only; latest_step ignores a foreign in-progress tmp dir; and the
    # restore is plan-migrating (template = abstract target shardings).
    from rt1_tpu.trainer import checkpoints as ckpt_lib
    from rt1_tpu.trainer.checkpoints import CheckpointConfig, CheckpointManager

    ckpt_dir = os.path.join(workdir, "ckpt")
    mgr = CheckpointManager(
        CheckpointConfig(directory=ckpt_dir, save_interval_steps=1)
    )
    assert mgr.save(STEPS, state)
    mgr.wait_until_finished()
    if process_id == 1:
        # Another host's write-in-flight must not look like a checkpoint.
        os.makedirs(
            os.path.join(ckpt_dir, "9.orbax-checkpoint-tmp-1699999999"),
            exist_ok=True,
        )
        os.makedirs(os.path.join(ckpt_dir, "11"), exist_ok=True)
        open(os.path.join(ckpt_dir, "tmp_ready"), "w").close()
    else:
        import time

        for _ in range(600):
            if os.path.exists(os.path.join(ckpt_dir, "tmp_ready")):
                break
            time.sleep(0.05)
    assert ckpt_lib.latest_step(ckpt_dir) == STEPS
    prov = os.path.join(ckpt_dir, "saved_under.json")
    assert os.path.exists(prov)
    if process_id == 0:
        import json

        with open(prov) as f:
            assert json.load(f)["process_count"] == 2

    import jax.numpy as jnp

    from rt1_tpu.trainer.train import optax_global_norm

    template = jax.tree.map(
        lambda x: np.zeros(x.shape, x.dtype), jax.eval_shape(lambda s: s, state)
    )
    restored = mgr.restore(template, step=STEPS, plan=plan)
    diff = jax.jit(
        lambda a, b: optax_global_norm(
            jax.tree.map(lambda x, y: (x - y).astype(jnp.float32), a, b)
        ),
        out_shardings=jax.sharding.NamedSharding(
            plan.mesh, jax.sharding.PartitionSpec()
        ),
    )(restored.params, state.params)
    assert float(np.asarray(jax.device_get(diff))) == 0.0
    mgr.close()

    with open(os.path.join(workdir, f"ok_{process_id}"), "w") as f:
        f.write("ok")
    print(f"worker {process_id}: ok", flush=True)


if __name__ == "__main__":
    setup_worker_runtime()
    main()

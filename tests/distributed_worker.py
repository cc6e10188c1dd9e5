"""Worker body for the 2-process jax.distributed smoke test.

Launched (twice) by tests/test_distributed.py with:
  python tests/distributed_worker.py <process_id> <coordinator_port> <workdir>

Covers the multihost surface the reference exercises in anger
(`language_table/train/train.py:124-140`: per-host data sharding + multihost
checkpointing) on two CPU processes with 4 virtual devices each.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

def main():
    # Forced-CPU multi-device platform + gloo collectives, the shared
    # scale-out bootstrap.
    from rt1_tpu.parallel.distributed import force_cpu_multiprocess_runtime

    force_cpu_multiprocess_runtime(4)
    process_id = int(sys.argv[1])
    port = sys.argv[2]
    workdir = sys.argv[3]

    import jax

    jax.distributed.initialize(
        coordinator_address=f"127.0.0.1:{port}",
        num_processes=2,
        process_id=process_id,
    )
    assert jax.process_count() == 2, jax.process_count()
    assert jax.local_device_count() == 4
    assert jax.device_count() == 8

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    # --- per-host data sharding: each host loads a disjoint window stripe.
    from rt1_tpu.data.episodes import generate_synthetic_episode, save_episode
    from rt1_tpu.data.pipeline import WindowedEpisodeDataset

    data_dir = os.path.join(workdir, "data")
    if process_id == 0:
        os.makedirs(data_dir, exist_ok=True)
        rng = np.random.default_rng(0)
        for i in range(3):
            save_episode(
                os.path.join(data_dir, f"episode_{i}.npz"),
                generate_synthetic_episode(rng, num_steps=6, height=16, width=24),
            )
        open(os.path.join(workdir, "data_ready"), "w").close()
    else:
        import time

        for _ in range(600):
            if os.path.exists(os.path.join(workdir, "data_ready")):
                break
            time.sleep(0.05)

    paths = sorted(
        os.path.join(data_dir, f) for f in os.listdir(data_dir)
        if f.endswith(".npz")
    )
    ds = WindowedEpisodeDataset(paths, window=2, height=16, width=24)
    my_windows = [
        i
        for i in range(len(ds.index))
        if i % jax.process_count() == jax.process_index()
    ]
    # The two hosts see disjoint halves covering everything.
    with open(os.path.join(workdir, f"windows_{process_id}.txt"), "w") as f:
        f.write(",".join(map(str, my_windows)))

    # --- global mesh over both hosts' devices + a multihost jax.Array.
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    global_shape = (8, 3)
    local = np.arange(8 * 3, dtype=np.float32).reshape(global_shape)[
        jax.process_index() * 4 : (jax.process_index() + 1) * 4
    ]
    arr = jax.make_array_from_process_local_data(sharding, local, global_shape)
    assert arr.shape == global_shape

    # --- Orbax multihost save/restore of the sharded array.
    from rt1_tpu.trainer.checkpoints import CheckpointConfig, CheckpointManager

    mgr = CheckpointManager(
        CheckpointConfig(
            directory=os.path.join(workdir, "ckpt"), save_interval_steps=1
        )
    )
    state = {"w": arr, "step": np.asarray(3, np.int32)}
    assert mgr.save(1, state)
    mgr.wait_until_finished()

    zeros_local = np.zeros_like(local)
    template = {
        "w": jax.make_array_from_process_local_data(
            sharding, zeros_local, global_shape
        ),
        "step": np.asarray(0, np.int32),
    }
    restored, step = mgr.restore_or_initialize(template)
    assert step == 1
    got_local = np.concatenate(
        [np.asarray(s.data) for s in restored["w"].addressable_shards]
    )
    np.testing.assert_array_equal(got_local, local)
    mgr.close()

    # --- a REAL multihost train step: tiny RT-1, batch sharded over both
    # hosts' devices, gradient reduction = GSPMD collectives over the global
    # mesh (what NCCL allreduce does in the reference's DDP loop).
    import jax.numpy as jnp

    from rt1_tpu.specs import language_table_action_space, sample_space
    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )
    from rt1_tpu.trainer.state import TrainState
    from rt1_tpu.models.rt1 import RT1Policy
    from rt1_tpu.models.tiny_tokenizer import TinyImageTokenizer

    model = RT1Policy(
        action_space=language_table_action_space(),
        vocab_size=32,
        token_embedding_size=16,
        num_layers=2,
        layer_size=8,
        num_heads=2,
        feed_forward_size=16,
        dropout_rate=0.0,
        time_sequence_length=2,
        num_image_tokens=2,
        image_tokenizer_def=TinyImageTokenizer(num_tokens=2, emb=16),
    )
    rng = jax.random.PRNGKey(0)
    b_local, t = 4, 2  # global batch 8 over the 8-device data axis
    rng_np = np.random.default_rng(7)  # same on both hosts
    obs_g = {
        "image": rng_np.random((8, t, 16, 24, 3), np.float32),
        "natural_language_embedding": rng_np.standard_normal(
            (8, t, 512)
        ).astype(np.float32),
    }
    actions_g = jax.tree.map(
        np.asarray,
        sample_space(language_table_action_space(), rng, (8, t)),
    )
    # Full 5-axis mesh over both hosts' devices (the declarative plan's
    # rules name 'fsdp'/'model'; size-1 axes are free).
    from rt1_tpu.parallel import MeshConfig, make_mesh

    train_mesh = make_mesh(MeshConfig(data=8))
    repl = NamedSharding(train_mesh, P())
    batch_sh = NamedSharding(train_mesh, P("data"))

    # Initialize replicated global params via jit (host-local init would
    # produce non-addressable placements under a multihost mesh).
    obs_l = jax.tree.map(lambda x: x[:2], obs_g)
    act_l = jax.tree.map(lambda x: x[:2], actions_g)
    init = jax.jit(
        lambda r: model.init({"params": r, "crop": r}, obs_l, act_l, train=False),
        out_shardings=repl,
    )
    variables = init(rng)
    tx = make_optimizer(steps_per_epoch=10)
    opt_state = jax.jit(tx.init, out_shardings=repl)(variables["params"])
    state = TrainState(
        step=jax.jit(lambda: jnp.zeros((), jnp.int32), out_shardings=repl)(),
        params=variables["params"],
        batch_stats={},
        opt_state=opt_state,
        tx=tx,
    )
    fns = make_train_step_fns(model, train_mesh, state, donate=False)

    def global_batch():
        lo = jax.process_index() * b_local
        return jax.tree.map(
            lambda x: jax.make_array_from_process_local_data(
                batch_sh, np.asarray(x[lo : lo + b_local]), x.shape
            ),
            (obs_g, actions_g),
        )

    losses = []
    for i in range(2):
        state, metrics = fns.train_step(
            state, global_batch(), jax.random.fold_in(rng, i)
        )
        losses.append(float(np.asarray(jax.device_get(metrics["loss"]))))
    assert np.isfinite(losses).all()
    with open(os.path.join(workdir, f"loss_{process_id}.txt"), "w") as f:
        f.write(",".join(f"{x:.8f}" for x in losses))

    with open(os.path.join(workdir, f"ok_{process_id}"), "w") as f:
        f.write("ok")
    print(f"worker {process_id}: ok", flush=True)


if __name__ == "__main__":
    main()

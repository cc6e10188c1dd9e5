"""SPMD trainer tests on the 8-device virtual CPU mesh.

What the reference never had (SURVEY.md §4 "Distributed testing: none"): multi-
device parity tests asserting the sharded pjit loss/updates equal single-device
ones — run here on `--xla_force_host_platform_device_count=8`, which exercises the
same GSPMD partitioner and collective lowering as a real TPU mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rt1_tpu.parallel import MeshConfig, make_mesh, rt1_parameter_rules, shard_pytree
from rt1_tpu.trainer import create_train_state, make_optimizer, make_train_step_fns, multistep_lr

from test_rt1 import tiny_policy, make_batch, T


def _setup(mesh, accum_steps=1, batch=8):
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=batch)
    tx = make_optimizer(learning_rate=1e-3)
    state = create_train_state(model, rng, (obs, actions), tx)
    fns = make_train_step_fns(model, mesh, state, accum_steps=accum_steps)
    return model, fns, fns.shard_state(state), fns.shard_batch((obs, actions))


def test_multistep_lr_schedule():
    sched = multistep_lr(5e-4, milestones=[50, 75, 90], gamma=0.1, steps_per_epoch=10)
    assert np.isclose(sched(0), 5e-4)
    assert np.isclose(sched(499), 5e-4)
    assert np.isclose(sched(500), 5e-5)
    assert np.isclose(sched(750), 5e-6)
    assert np.isclose(sched(900), 5e-7)


def test_mesh_shapes():
    mesh = make_mesh(MeshConfig())
    assert mesh.shape == {
        "data": 8, "stage": 1, "fsdp": 1, "model": 1
    }
    mesh = make_mesh(MeshConfig(model=4))
    assert mesh.shape == {
        "data": 2, "stage": 1, "fsdp": 1, "model": 4
    }
    mesh = make_mesh(MeshConfig(fsdp=2, model=2))
    assert mesh.shape == {
        "data": 2, "stage": 1, "fsdp": 2, "model": 2
    }
    with pytest.raises(ValueError):
        make_mesh(MeshConfig(data=3, model=3))


def test_train_step_runs_and_learns():
    mesh = make_mesh(MeshConfig())  # pure DP over 8 devices
    model, fns, state, batch = _setup(mesh)
    rng = jax.random.PRNGKey(1)
    losses = []
    for i in range(5):
        state, metrics = fns.train_step(state, batch, jax.random.fold_in(rng, i))
        losses.append(float(metrics["loss"]))
    assert int(state.step) == 5
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]  # overfits a fixed batch


def test_dp_loss_equals_single_device():
    """8-way sharded loss == single-device loss on the same batch/params."""
    mesh8 = make_mesh(MeshConfig())
    mesh1 = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])

    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    tx = make_optimizer()
    state = create_train_state(model, rng, (obs, actions), tx)

    out = {}
    for name, mesh in [("dp8", mesh8), ("single", mesh1)]:
        fns = make_train_step_fns(model, mesh, state, donate=False)
        s = fns.shard_state(state)
        b = fns.shard_batch((obs, actions))
        new_state, metrics = fns.train_step(s, b, jax.random.PRNGKey(7))
        out[name] = (float(metrics["loss"]), new_state)

    np.testing.assert_allclose(out["dp8"][0], out["single"][0], rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        out["dp8"][1].params,
        out["single"][1].params,
    )


def test_tp_loss_equals_dp():
    """data=2 × model=4 tensor-parallel step == pure-DP step (same math, new layout)."""
    mesh_tp = make_mesh(MeshConfig(data=2, model=4))
    mesh_dp = make_mesh(MeshConfig())

    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    tx = make_optimizer()
    state = create_train_state(model, rng, (obs, actions), tx)

    results = {}
    for name, mesh in [("tp", mesh_tp), ("dp", mesh_dp)]:
        fns = make_train_step_fns(model, mesh, state, donate=False)
        s = fns.shard_state(state)
        b = fns.shard_batch((obs, actions))
        _, metrics = fns.train_step(s, b, jax.random.PRNGKey(3))
        results[name] = float(metrics["loss"])
    np.testing.assert_allclose(results["tp"], results["dp"], rtol=1e-5)


def test_param_sharding_rules_hit_transformer():
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=2)
    variables = model.init({"params": rng, "crop": rng}, obs, actions, train=False)
    mesh = make_mesh(MeshConfig(data=2, model=4))
    sh = shard_pytree(variables["params"], mesh, rt1_parameter_rules())
    qk = sh["transformer"]["layer_0"]["attn"]["query"]["kernel"]
    assert qk.spec == jax.sharding.PartitionSpec("fsdp", "model")
    # The plan covers the WHOLE tree: every weight matrix (rank >= 2)
    # matches an explicit rule — nothing falls through to silent
    # replication (the plan-coverage guarantee, parallel/plan.py).
    from rt1_tpu.parallel import ShardingPlan

    plan = ShardingPlan(mesh=mesh)
    assert plan.coverage(variables["params"]) == []


def test_grad_accumulation_matches_full_batch():
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    # crop_ratio=0 → fully deterministic forward; with augmentation on, micro-
    # batches draw different crop rngs than the full batch and exact equality
    # cannot hold (nor does it need to).
    model = tiny_policy(crop_ratio=0.0)
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    tx = make_optimizer()
    state = create_train_state(model, rng, (obs, actions), tx)

    fns1 = make_train_step_fns(model, mesh, state, accum_steps=1, donate=False)
    fns4 = make_train_step_fns(model, mesh, state, accum_steps=4, donate=False)
    s1 = fns1.shard_state(state)
    s4 = fns4.shard_state(state)
    b = fns1.shard_batch((obs, actions))
    ns1, m1 = fns1.train_step(s1, b, jax.random.PRNGKey(5))
    ns4, m4 = fns4.train_step(s4, b, jax.random.PRNGKey(5))

    # Deterministic forward + loss a mean over independent examples → identical
    # update (incl. the reference-loss-scaling /accum correction, train.py).
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        ns1.params,
        ns4.params,
    )


def test_grad_accumulation_exact_with_aux_mse():
    """Accumulation exactness must survive aux_mse_weight > 0: the aux term
    shares the reference CE normalizer (∝ 1/(b·t·(I+A))), so the trainer's
    /accum correction applies to the whole loss, and the aux_mse metric is
    reported from the accumulated path too."""
    mesh = make_mesh(MeshConfig(data=1), devices=jax.devices()[:1])
    model = tiny_policy(crop_ratio=0.0, aux_mse_weight=5.0)
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    tx = make_optimizer()
    state = create_train_state(model, rng, (obs, actions), tx)

    fns1 = make_train_step_fns(model, mesh, state, accum_steps=1, donate=False)
    fns4 = make_train_step_fns(model, mesh, state, accum_steps=4, donate=False)
    b = fns1.shard_batch((obs, actions))
    ns1, m1 = fns1.train_step(fns1.shard_state(state), b, jax.random.PRNGKey(5))
    ns4, m4 = fns4.train_step(fns4.shard_state(state), b, jax.random.PRNGKey(5))

    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]), rtol=1e-5)
    assert "aux_mse" in m1 and "aux_mse" in m4
    np.testing.assert_allclose(
        float(m1["aux_mse"]), float(m4["aux_mse"]), rtol=1e-4
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        ns1.params,
        ns4.params,
    )


def test_eval_step_metrics():
    mesh = make_mesh(MeshConfig())
    model, fns, state, batch = _setup(mesh)
    metrics = fns.eval_step(state, batch)
    assert set(metrics) >= {"loss", "token_accuracy"}
    assert 0.0 <= float(metrics["token_accuracy"]) <= 1.0


def test_write_hparams_flattens_nested_configs():
    """Regression: nested config blocks (config.data, config.obs, ...) were
    silently dropped by the top-level scalar filter — the TB hparams table
    lost everything an operator actually tunes. Nested dicts now flatten to
    dotted keys; non-scalar leaves (tuples, None placeholders) still skip."""
    from rt1_tpu.trainer.metrics import flatten_hparams, write_hparams

    config = {
        "learning_rate": 5e-4,
        "seed": 42,
        "lr_milestones": (50, 75, 90),  # non-scalar: skipped
        "data": {
            "height": 256,
            "packed_cache": True,
            "packed_cache_dir": None,  # placeholder: skipped
        },
        "obs": {"model_health": True, "prometheus_host": "127.0.0.1"},
        "model": {"lava": {"d_model": 128}},
    }
    flat = flatten_hparams(config)
    assert flat == {
        "learning_rate": 5e-4,
        "seed": 42,
        "data.height": 256,
        "data.packed_cache": True,
        "obs.model_health": True,
        "obs.prometheus_host": "127.0.0.1",
        "model.lava.d_model": 128,
    }

    class FakeWriter:
        def write_hparams(self, hparams):
            self.hparams = hparams

    writer = FakeWriter()
    write_hparams(writer, config)
    assert writer.hparams == flat


def test_jitted_init_equals_eager_init(monkeypatch):
    """On an accelerator `create_train_state` runs init as one jitted
    program (a cold start on the chip otherwise pays ~1,600 tiny compiles);
    on CPU it stays op by op. Same state either way."""
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    batch = make_batch(rng, b=8)
    state = create_train_state(model, rng, batch, make_optimizer())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jitted = create_train_state(model, rng, batch, make_optimizer())
    for eager, jit in zip(
        jax.tree.leaves((state.params, state.batch_stats, state.opt_state)),
        jax.tree.leaves((jitted.params, jitted.batch_stats, jitted.opt_state)),
    ):
        np.testing.assert_array_equal(np.asarray(eager), np.asarray(jit))

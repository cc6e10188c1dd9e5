"""Pipeline parallelism: pipelined == sequential, forward and backward.

The semantic spec: `pipeline_apply` over S stages must be *exact* vs folding
the same stacked layers sequentially on one device — the rotation schedule
only changes where compute happens, never what is computed. Beyond reference
parity (SURVEY.md §2.6: the reference has no PP), so the tests are the spec.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rt1_tpu.models.transformer import CausalTransformer
from rt1_tpu.parallel import MeshConfig, make_mesh
from rt1_tpu.parallel.pipeline import (
    pipeline_apply,
    pp_causal_transformer_apply,
    stack_layer_params,
    unstack_layer_params,
)


def _dense_stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _stacked_dense_params(rng, num_layers, width):
    keys = jax.random.split(rng, 2)
    return {
        "w": jax.random.normal(keys[0], (num_layers, width, width)) * 0.3,
        "b": jax.random.normal(keys[1], (num_layers, width)) * 0.1,
    }


def _sequential(stacked, x):
    def fold(x, p):
        return _dense_stage_fn(p, x), None

    out, _ = jax.lax.scan(fold, x, stacked)
    return out


@pytest.mark.parametrize("stages,microbatches", [(2, 4), (4, 2), (4, 4)])
def test_pipeline_matches_sequential(stages, microbatches):
    mesh = make_mesh(
        MeshConfig(data=1, stage=stages), devices=jax.devices()[:stages]
    )
    rng = jax.random.PRNGKey(0)
    stacked = _stacked_dense_params(rng, num_layers=8, width=16)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (8, 16))

    got = jax.jit(
        lambda p, x: pipeline_apply(
            _dense_stage_fn, p, x, mesh=mesh, num_microbatches=microbatches
        )
    )(stacked, x)
    want = _sequential(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pipeline_with_data_parallel_axis():
    """dp × pp grid: each data row pipelines its own batch shard."""
    mesh = make_mesh(MeshConfig(data=2, stage=4))
    rng = jax.random.PRNGKey(2)
    stacked = _stacked_dense_params(rng, num_layers=4, width=8)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (8, 8))

    got = jax.jit(
        lambda p, x: pipeline_apply(
            _dense_stage_fn, p, x, mesh=mesh, num_microbatches=2
        )
    )(stacked, x)
    want = _sequential(stacked, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_pipeline_grads_match_sequential():
    """Autodiff pipelines the backward pass: grads exact vs sequential."""
    mesh = make_mesh(
        MeshConfig(data=1, stage=4), devices=jax.devices()[:4]
    )
    rng = jax.random.PRNGKey(3)
    stacked = _stacked_dense_params(rng, num_layers=4, width=8)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (4, 8))

    def loss_pp(p):
        return jnp.sum(
            pipeline_apply(
                _dense_stage_fn, p, x, mesh=mesh, num_microbatches=2
            )
            ** 2
        )

    def loss_seq(p):
        return jnp.sum(_sequential(p, x) ** 2)

    g_pp = jax.jit(jax.grad(loss_pp))(stacked)
    g_seq = jax.grad(loss_seq)(stacked)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        ),
        g_pp,
        g_seq,
    )


def test_single_stage_degenerates_to_scan():
    mesh = make_mesh(MeshConfig(data=8, stage=1))
    rng = jax.random.PRNGKey(4)
    stacked = _stacked_dense_params(rng, num_layers=3, width=8)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (8, 8))
    got = pipeline_apply(
        _dense_stage_fn, stacked, x, mesh=mesh, num_microbatches=1
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_sequential(stacked, x)), atol=1e-6
    )


def test_stack_unstack_roundtrip():
    rng = jax.random.PRNGKey(5)
    t = CausalTransformer(num_layers=2, key_dim=4, num_heads=2, d_model=8,
                          vocab_size=16)
    params = t.init(rng, jnp.ones((1, 3, 8)))["params"]
    stacked = stack_layer_params(params, 2)
    back = unstack_layer_params(stacked)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        {k: params[k] for k in ("layer_0", "layer_1")},
        back,
    )


def test_pp_rejects_nondense_attention():
    mesh = make_mesh(
        MeshConfig(data=1, stage=2), devices=jax.devices()[:2]
    )
    t = CausalTransformer(
        num_layers=2, key_dim=8, num_heads=2, d_model=16, vocab_size=32,
        attention_impl="pallas",
    )
    x = jnp.ones((2, 4, 16))
    variables = CausalTransformer(
        num_layers=2, key_dim=8, num_heads=2, d_model=16, vocab_size=32
    ).init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="dense"):
        pp_causal_transformer_apply(
            t, variables, x, mesh=mesh, num_microbatches=2
        )


def test_pp_train_step_equals_dense():
    """TRAINER integration: a data=2 × stage=4 pipelined train step produces
    the same loss and parameter update as the plain dense step (dropout 0 →
    exact schedule-invariance, the PP analogue of test_tp_loss_equals_dp)."""
    import sys

    sys.path.insert(0, "tests")
    from test_rt1 import make_batch, tiny_policy

    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )

    import optax

    mesh_pp = make_mesh(MeshConfig(data=2, stage=4))
    mesh_dp = make_mesh(MeshConfig())

    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    # SGD, not Adam: the first Adam step is ~sign(g), which amplifies the
    # benign 1e-12-scale float reassociation between the pipelined and
    # sequential schedules into visible param deltas wherever g ≈ 0. Under
    # SGD the param delta IS the gradient (scaled), so this asserts true
    # gradient parity.
    tx = optax.sgd(1e-2)

    results = {}
    for name, mesh, model in [
        ("pp", mesh_pp,
         tiny_policy(num_layers=4, mesh=mesh_pp, pipeline_microbatches=2)),
        ("dense", mesh_dp, tiny_policy(num_layers=4)),
    ]:
        state = create_train_state(model, rng, (obs, actions), tx)
        fns = make_train_step_fns(model, mesh, state, donate=False)
        s = fns.shard_state(state)
        b = fns.shard_batch((obs, actions))
        new_state, metrics = fns.train_step(s, b, jax.random.PRNGKey(5))
        results[name] = (float(metrics["loss"]), new_state)

    np.testing.assert_allclose(results["pp"][0], results["dense"][0], rtol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        results["pp"][1].params,
        results["dense"][1].params,
    )


@pytest.mark.slow
def test_pp_train_step_with_dropout_runs():
    """Dropout under PP: per-(layer, microbatch) rngs fold inside the stage;
    the step must run and stay finite (bitwise parity with the sequential
    dropout bitstream is not defined — see pp_causal_transformer_apply)."""
    import sys

    sys.path.insert(0, "tests")
    from test_rt1 import make_batch, tiny_policy

    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )

    mesh = make_mesh(MeshConfig(data=2, stage=4))
    model = tiny_policy(
        num_layers=4, dropout_rate=0.2, mesh=mesh, pipeline_microbatches=2
    )
    rng = jax.random.PRNGKey(1)
    obs, actions = make_batch(rng, b=8)
    state = create_train_state(model, rng, (obs, actions), make_optimizer())
    fns = make_train_step_fns(model, mesh, state, donate=False)
    s = fns.shard_state(state)
    b = fns.shard_batch((obs, actions))
    s, metrics = fns.train_step(s, b, jax.random.PRNGKey(2))
    assert np.isfinite(float(metrics["loss"]))
    assert int(s.step) == 1


def test_pp_causal_transformer_matches_module():
    """Full decoder: pipelined apply ≡ the sequential Flax module."""
    mesh = make_mesh(
        MeshConfig(data=1, stage=4), devices=jax.devices()[:4]
    )
    t = CausalTransformer(
        num_layers=4, key_dim=8, num_heads=2, d_model=16, vocab_size=32,
        dropout_rate=0.0,
    )
    rng = jax.random.PRNGKey(6)
    x = jax.random.normal(jax.random.fold_in(rng, 1), (4, 6, 16))
    mask = jnp.tril(jnp.ones((6, 6), jnp.int32))
    variables = t.init(rng, x, attention_mask=mask)

    want = t.apply(variables, x, attention_mask=mask, train=False)
    got = jax.jit(
        lambda v, x: pp_causal_transformer_apply(
            t, v, x, mesh=mesh, num_microbatches=2, attention_mask=mask
        )
    )(variables, x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4
    )

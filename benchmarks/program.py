"""The system under test, built from a configuration file the way
``rt1_tpu.train.train.train_and_evaluate`` builds it (train.py:540-720):
``ShardingPlan.from_config`` -> ``build_family`` -> ``make_optimizer`` ->
``TrainState`` -> ``make_train_step_fns`` with the guard and the health pack
as the configuration says -> ``fns.shard_state``.  Orbax, the goodput
ledger, Prometheus and eval are left out: they are not in the window.

Only the weights differ: the benchmark makes them (benchmarks/weights.py).
"""

from __future__ import annotations

import dataclasses
import importlib
import json
from typing import Any, Dict, Tuple


def load_config_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def weight_gains(config_file: Dict[str, Any]) -> Dict[str, float]:
    return config_file.get("weights", {}).get("gains", {})


def program_config(config_file: Dict[str, Any]):
    """ml_collections config: the named base with every override applied."""
    config = importlib.import_module(config_file["base"]).get_config()
    for dotted, value in config_file["overrides"].items():
        node = config
        *parents, leaf = dotted.split(".")
        for p in parents:
            node = node[p]
        if isinstance(value, list):
            value = tuple(value)
        node[leaf] = value
    return config


def batch_spec(config) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Abstract (observations, actions) of one batch, as the feeder emits it."""
    import jax
    import jax.numpy as jnp

    b = config.per_host_batch_size
    t = config.model.time_sequence_length
    h, w = config.data.height, config.data.width
    obs = {
        "image": jax.ShapeDtypeStruct((b, t, h, w, 3), jnp.uint8),
        "natural_language_embedding": jax.ShapeDtypeStruct((b, t, 512), jnp.float32),
    }
    actions = {
        "terminate_episode": jax.ShapeDtypeStruct((b, t), jnp.int32),
        "action": jax.ShapeDtypeStruct((b, t, 2), jnp.float32),
    }
    return obs, actions


@dataclasses.dataclass
class Program:
    config: Any
    model: Any
    fns: Any
    state: Any
    skips: Any            # guard-skip counter, None when the guard is off
    abstract_params: Any
    abstract_batch_stats: Any
    gains: Dict[str, float]

    def reset(self, seed: int) -> None:
        """The compiled step stays; its state is made anew from ``seed``."""
        self.state = self.fns.shard_state(initial_state(
            self.abstract_params, self.abstract_batch_stats, self.state.tx, seed, self.gains))
        if self.skips is not None:
            self.skips = self.fns.init_guard_skips()

    def step(self, batch, rng):
        """One call of the timed entry; returns the step's metrics."""
        if self.fns.guarded:
            self.state, self.skips, metrics = self.fns.train_step(
                self.state, self.skips, batch, rng
            )
        else:
            self.state, metrics = self.fns.train_step(self.state, batch, rng)
        return metrics


def abstract_state(config, model, init_fn, tx):
    import jax
    import jax.numpy as jnp

    from rt1_tpu.trainer import create_train_state

    obs, actions = batch_spec(config)
    return jax.eval_shape(
        lambda r, o, a: create_train_state(model, r, (o, a), tx, init_fn=init_fn),
        jax.ShapeDtypeStruct((2,), jnp.uint32), obs, actions,
    )


def initial_state(abstract_params, abstract_batch_stats, tx, seed: int, gains):
    """TrainState at step 0 with the benchmark's weights of ``seed``."""
    import jax
    import jax.numpy as jnp

    from rt1_tpu.trainer.state import TrainState

    from benchmarks import weights

    params, batch_stats = weights.make_weights(
        abstract_params, abstract_batch_stats, seed, gains)
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats=batch_stats,
        opt_state=jax.jit(tx.init)(params), tx=tx,
    )


def build_model(config, devices=None):
    """(plan, model, init_fn, loss_fn, tx) — shared with the FLOP walker."""
    from rt1_tpu.parallel import ShardingPlan
    from rt1_tpu.train.train import build_family
    from rt1_tpu.trainer import make_optimizer

    plan = (
        ShardingPlan.from_config(config)
        if devices is None
        else ShardingPlan.from_config(config, devices=devices)
    )
    model, init_fn, loss_fn = build_family(config.model, mesh=plan.mesh)
    tx = make_optimizer(
        learning_rate=config.learning_rate,
        milestones=config.lr_milestones,
        gamma=config.lr_gamma,
        steps_per_epoch=config.steps_per_epoch,
        grad_clip_norm=config.grad_clip_norm or None,
    )
    return plan, model, init_fn, loss_fn, tx


def wants_task_ids(config) -> bool:
    """The trainer's rule (train.py::_packed_batches): task ids ride in the
    batch exactly when the step's health pack will consume them."""
    from rt1_tpu import obs

    return bool(
        obs.ObsOptions.from_config(config, "").model_health
        and config.model.get("family", "rt1") == "rt1"
    )


def build(config_file: Dict[str, Any], seed: int, health_task_names=()) -> Program:
    from rt1_tpu import obs, resilience
    from rt1_tpu.parallel import mixed_precision_from_config
    from rt1_tpu.trainer import make_train_step_fns

    config = program_config(config_file)
    plan, model, init_fn, loss_fn, tx = build_model(config)
    shapes = abstract_state(config, model, init_fn, tx)
    gains = weight_gains(config_file)
    state = initial_state(shapes.params, shapes.batch_stats, tx, seed, gains)
    res_opts = resilience.ResilienceOptions.from_config(config)
    obs_opts = obs.ObsOptions.from_config(config, "")
    family = config.model.get("family", "rt1")
    fns = make_train_step_fns(
        model, plan.mesh, state, accum_steps=config.accum_steps, loss_fn=loss_fn,
        guard_nonfinite=res_opts.guard,
        guard_grad_norm_max=res_opts.guard_grad_norm_max,
        model_health=obs_opts.model_health,
        health_group_depth=obs_opts.health_group_depth,
        health_task_names=tuple(health_task_names),
        plan=plan,
        mixed_precision=mixed_precision_from_config(config),
        check_coverage=family == "rt1",
    )
    state = fns.shard_state(state)
    return Program(
        config=config, model=model, fns=fns, state=state,
        skips=fns.init_guard_skips() if fns.guarded else None,
        abstract_params=shapes.params, abstract_batch_stats=shapes.batch_stats,
        gains=gains,
    )

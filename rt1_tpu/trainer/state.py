"""Functional train state.

Replaces the mutable module + optimizer of `RT1_Lightning` (`distribute_train.py:
19-110`) and Stack B's `TrainState` flax struct (`language_table/train/bc.py:33-40`:
step/params/opt_state/batch_stats/norm_info). Ours carries step, params,
batch_stats (EfficientNet BatchNorm running stats — SURVEY.md §7 hard-part 2), and
opt_state. Under pjit/GSPMD, BatchNorm's batch-mean over the sharded batch axis is
itself a global collective, so no explicit cross-replica `merge_batch_stats`
(`train.py:258-266`) is needed — stats are identical on every shard by
construction.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import flax.struct
import jax
import jax.numpy as jnp
import optax

from rt1_tpu.obs import startup


@flax.struct.dataclass
class TrainState:
    step: jnp.ndarray                    # scalar int32
    params: Any
    batch_stats: Any                     # {} when the model has no BatchNorm
    opt_state: Any
    tx: optax.GradientTransformation = flax.struct.field(pytree_node=False)

    def apply_gradients(
        self,
        grads: Any,
        new_batch_stats: Optional[Any] = None,
        return_updates: bool = False,
    ) -> Any:
        """One optimizer step; with ``return_updates`` also returns the
        applied update tree (``new_params = params + updates``) — consumed
        by the model-health pack (rt1_tpu/obs/health.py), which must not
        read the pre-update params (that would pin the donated input
        buffers past the in-place optimizer write)."""
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        new_state = self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            batch_stats=self.batch_stats if new_batch_stats is None else new_batch_stats,
            opt_state=new_opt_state,
        )
        return (new_state, updates) if return_updates else new_state


@startup.phased("init_state")
def create_train_state(
    model: Any,
    rng: jax.Array,
    example_batch: Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]],
    tx: optax.GradientTransformation,
    init_fn: Optional[Callable] = None,
) -> TrainState:
    """Initialize params (+ batch_stats) from an example (observations, actions).

    On an accelerator init runs as ONE jitted program: eagerly, the
    flagship's ~1,600 initializer and `zeros_like` calls each compile a tiny
    program of their own — minutes of a cold start on the chip, and none of
    them reaches the persistent cache's compile-time floor, so every
    relaunch paid them again (PERF.md, PR 21). The CPU backend dispatches
    those ops in microseconds and reuses them across calls, while one big
    program would be compiled anew on every call (the test suite makes
    hundreds): there init stays op by op. Same values either way
    (tests/test_trainer.py).
    """
    obs, actions = example_batch

    def init(rng, obs, actions):
        if init_fn is None:
            variables = model.init(
                {"params": rng, "crop": rng}, obs, actions, train=False
            )
        else:
            variables = init_fn(model, rng, obs, actions)
        params = variables["params"]
        return params, variables.get("batch_stats", {}), tx.init(params)

    if jax.default_backend() != "cpu":
        init = jax.jit(init)
    params, batch_stats, opt_state = init(rng, obs, actions)
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=opt_state,
        tx=tx,
    )

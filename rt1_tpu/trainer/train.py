"""The jitted SPMD train/eval step.

Replaces (SURVEY.md §3.1/§3.4):
* `RT1_Lightning.training_step` + Lightning/DDP backward with NCCL bucket
  allreduce (`distribute_train.py:59-73` + `:235`) — here the gradient reduction
  over the batch axis is a GSPMD-inserted `psum` over ICI, emitted because the
  batch is sharded over the mesh's ``data`` axis while params are replicated (or
  sharded over ``model`` for tensor parallelism).
* Stack B's `p_train_step = pmap(multi_train_step)` with explicit
  `lax.pmean(grad)` (`language_table/train/train.py:143-151`, `bc.py:189-191`) —
  no per-device leading axis, no explicit collectives, one global program.

Gradient accumulation generalizes Stack B's `num_steps_per_train_iter` fori_loop
(`train.py:36-57`): with ``accum_steps > 1`` the global batch is split into
microbatches scanned on-device, gradients averaged, ONE optimizer update — the
standard way to grow effective batch beyond HBM.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rt1_tpu.obs import startup
from rt1_tpu.parallel import plan as planlib
from rt1_tpu.parallel import sharding as shardlib
from rt1_tpu.trainer.state import TrainState

Batch = Tuple[Dict[str, jnp.ndarray], Dict[str, jnp.ndarray]]


@dataclasses.dataclass
class TrainStepFns:
    """Compiled step functions + the shardings they expect.

    With ``guarded=True`` the train step takes and returns an extra
    replicated device scalar — the cumulative guard-skip counter::

        state, skips, metrics = fns.train_step(state, skips, batch, rng)

    (initialize `skips` with :meth:`init_guard_skips`). The unguarded
    signature stays ``(state, batch, rng) -> (state, metrics)``.
    """

    train_step: Callable[..., Tuple]
    eval_step: Callable[[TrainState, Batch], Dict[str, jnp.ndarray]]
    state_sharding: Any
    batch_sharding: NamedSharding
    mesh: Mesh
    guarded: bool = False
    # True when the step casts f32 master params to bf16 for fwd/bwd
    # (optimizer state and the stored params stay f32).
    mixed_precision: bool = False
    # Entry names of the model-health pack vector riding in the metrics
    # under obs.health.PACK_KEY (empty when model_health is off). The host
    # unpacks the fetched vector against these at log steps.
    health_names: Tuple[str, ...] = ()

    @startup.phased("shard_state")
    def shard_state(self, state: TrainState) -> TrainState:
        """Place the state per the plan. Multi-process meshes cannot
        `device_put` host values onto non-addressable devices; there the
        state round-trips through host numpy into a jitted identity with
        the plan's out_shardings — every process passes the same
        deterministic init (or the same restored globals), and XLA lays
        each leaf out on the global mesh."""
        if jax.process_count() > 1:
            def host_or_global(x):
                # Leaves already laid out on the global mesh (a
                # plan-migrating restore) pass straight through; local
                # leaves (fresh deterministic init) go via host numpy.
                if isinstance(x, jax.Array) and not x.is_fully_addressable:
                    return x
                return jax.device_get(x)

            state = jax.tree.map(host_or_global, state)
            return jax.jit(lambda s: s, out_shardings=self.state_sharding)(
                state
            )
        return jax.device_put(state, self.state_sharding)

    def shard_batch(self, batch: Batch) -> Batch:
        from rt1_tpu.data.pipeline import put_global

        return put_global(batch, self.batch_sharding)

    def init_guard_skips(self) -> jax.Array:
        """Replicated int32 zero: the cumulative skip counter's seed value."""
        repl = NamedSharding(self.mesh, P())
        if jax.process_count() > 1:
            return jax.jit(
                lambda: jnp.zeros((), jnp.int32), out_shardings=repl
            )()
        return jax.device_put(jnp.zeros((), jnp.int32), repl)


def _loss_fn(model, params, batch_stats, batch: Batch, rng: jax.Array, train: bool):
    obs, actions = batch
    variables = {"params": params}
    if batch_stats:
        variables["batch_stats"] = batch_stats
    rngs = {
        "crop": jax.random.fold_in(rng, 0),
        "dropout": jax.random.fold_in(rng, 1),
        "augment": jax.random.fold_in(rng, 2),
    }
    if train and batch_stats:
        out, mutated = model.apply(
            variables,
            obs,
            actions,
            train=train,
            rngs=rngs,
            mutable=["batch_stats"],
        )
        new_bs = mutated.get("batch_stats", batch_stats)
    else:
        out = model.apply(
            variables, obs, actions, train=train, rngs=rngs if train else None
        )
        new_bs = batch_stats
    return out["loss"], (out, new_bs)


@startup.phased("make_step_fns")
def make_train_step_fns(
    model: Any,
    mesh: Mesh,
    state: TrainState,
    param_rules: Optional[Sequence[shardlib.Rule]] = None,
    accum_steps: int = 1,
    batch_axes: Optional[Tuple[str, ...]] = None,
    donate: bool = True,
    loss_fn: Optional[Callable] = None,
    guard_nonfinite: bool = False,
    guard_grad_norm_max: float = 0.0,
    model_health: bool = False,
    health_group_depth: int = 2,
    health_task_names: Sequence[str] = (),
    plan: Optional[planlib.ShardingPlan] = None,
    mixed_precision: bool = False,
    check_coverage: bool = True,
) -> TrainStepFns:
    """Build jitted train/eval steps with explicit in/out shardings.

    `state` is only used to derive the sharding pytree (its structure, not its
    values); call `fns.shard_state(state)` afterwards to place it on the mesh.

    `loss_fn(params, batch_stats, batch, rng, train) -> (loss, (out, new_bs))`
    overrides the default RT-1 token-CE closure — the hook that lets the same
    SPMD step machinery train other model families (LAVA BC MSE via
    `trainer.bc.make_bc_step_loss_fn`, reference Stack B `train.py:105-116`).
    `out` must contain "loss"; extra keys become metrics where recognized.

    ``guard_nonfinite=True`` is the device half of the resilience step guard
    (rt1_tpu/resilience/guard.py): when the step's loss or grad-norm is
    non-finite — or the grad-norm exceeds ``guard_grad_norm_max`` (> 0) —
    the whole state update is dropped (`jnp.where` select against the input
    state; a skipped step leaves params, opt_state, batch_stats, and
    `state.step` untouched). A cumulative skip counter is threaded through
    the step as a replicated device scalar and surfaced as the
    ``guard_skips_cum`` metric, so the host learns the exact skip count at
    log steps without ever syncing per step. When the step is healthy the
    select is the identity — the guarded step is numerically identical to
    the unguarded one (pinned in tests/test_resilience_guard.py).

    ``model_health=True`` packs per-layer-group gradient norms, post-
    optimizer update/param ratios, global param norm, action-logit entropy,
    and per-action-dimension token accuracy into ONE replicated float32
    vector under ``metrics[obs.health.PACK_KEY]`` (rt1_tpu/obs/health.py)
    — computed inside the traced step, fetched only when the host fetches
    metrics, unpacked against ``fns.health_names``. Same guard discipline
    as ``guard_nonfinite``: a Python-level gate, so the ``False`` path
    traces the exact pre-change program (pinned bit-identical in
    tests/test_obs_health.py).

    ``health_task_names`` (with ``model_health=True`` and batches whose
    observations carry ``obs.health.TASK_ID_KEY`` — the sample-ahead
    feeder's ``emit_task_ids``) extends the pack with per-task loss /
    token accuracy / batch share via a one-hot segment reduction inside
    the step (``health/task_*``). The task-id member is stripped from the
    observations before the model forward; batches without it trace the
    exact task-free program.

    Layout comes from the declarative ``plan`` (parallel/plan.py) — the same
    object train, eval, and serve resolve once from ``config.parallel``.
    ``param_rules`` remains as an explicit override; when neither is given
    the default RT-1 plan applies. The plan's coverage check runs on
    ``state.params`` here, so a param group the plan forgot warns loudly
    (or raises in strict mode) at step-build time, not after silently
    replicating for a whole run.

    ``mixed_precision=True`` is TRUE mixed precision, not a compute-dtype
    flag: the TrainState keeps float32 master params + optimizer state
    (restore/checkpoint dtypes unchanged); inside the jitted step the f32
    masters are cast ONCE to bfloat16 and the fwd/bwd runs on the bf16
    copy (activations follow the model's bf16 compute dtype; softmax/CE
    stay f32 — models/rt1.py upcasts logits before the loss). Gradient of
    the cast is a cast back, so grads arrive f32 and the optimizer update
    is pure f32 master arithmetic. Donation-safe: the bf16 copy is a fresh
    buffer read from the donated input before the in-place master update.
    With ``mixed_precision=False`` the traced program is the exact
    pre-change program (Python-level gate, same discipline as
    ``guard_nonfinite``/``model_health``; pinned in tests/test_plan.py).
    """
    if plan is None:
        plan = planlib.ShardingPlan(
            mesh=mesh,
            rules=(
                param_rules if param_rules is not None
                else planlib.rt1_sharding_plan()
            ),
        )
    if batch_axes is None:
        # Batch shards over every data-parallel axis the mesh carries;
        # meshes built before the fsdp axis existed keep ("data",).
        batch_axes = tuple(
            a for a in plan.batch_axes if a in mesh.shape
        ) or ("data",)
    default_rt1_loss = loss_fn is None
    if loss_fn is None:
        def loss_fn(params, batch_stats, batch, rng, train):
            return _loss_fn(model, params, batch_stats, batch, rng, train)

    if mesh.shape.get("fsdp", 1) > 1:
        # FSDP schedule: weights are STORED sharded over `fsdp` between
        # steps (master params + optimizer moments — the ZeRO memory win)
        # and gathered ONCE here for fwd/bwd; the update reshards back at
        # the step's out_shardings boundary (a reduce-scatter). One clean
        # all-gather per step beats per-use resharding, and sidesteps the
        # XLA:CPU partitioner miscompiles on dp×fsdp meshes (plan.py,
        # strip_fsdp_axis). Placed INSIDE the loss closure so the bf16
        # mixed-precision cast below lands before the gather — gathering
        # half the bytes.
        gather_sh = plan.gather_shardings(state.params)
        fsdp_loss_fn = loss_fn

        def loss_fn(params, batch_stats, batch, rng, train):  # noqa: F811
            params = jax.lax.with_sharding_constraint(params, gather_sh)
            return fsdp_loss_fn(params, batch_stats, batch, rng, train)

    if mixed_precision:
        task_loss_fn = loss_fn

        def loss_fn(params, batch_stats, batch, rng, train):  # noqa: F811
            return task_loss_fn(
                _bf16_compute_copy(params), batch_stats, batch, rng, train
            )

    from rt1_tpu.obs import health as health_lib

    health_names: Tuple[str, ...] = ()
    health_action_dims = 0
    health_tasks: Tuple[str, ...] = ()
    if model_health:
        # Action-logit statistics exist only when the default RT-1 token-CE
        # closure runs unaccumulated (the accum scan keeps only the loss;
        # family-override losses have no token logits). The pack layout is
        # decided here, statically, so host names and traced order agree.
        if (
            default_rt1_loss
            and accum_steps == 1
            and hasattr(model, "tokens_per_action")
        ):
            health_action_dims = int(model.tokens_per_action)
            # Per-task loss/accuracy shares the same action-stat gate: the
            # one-hot reduction consumes the per-example action_loss only
            # the unaccumulated RT-1 closure exposes.
            health_tasks = tuple(health_task_names or ())
        health_names = health_lib.pack_names(
            state.params,
            depth=health_group_depth,
            action_dims=health_action_dims,
            task_names=health_tasks,
        )

    # Strip the feeder's per-example task ids from the observations BEFORE
    # the model forward — the model contract never includes them — and
    # stash them into the loss aux for the health pack's per-task segment
    # reduction. Batches without the key (synthetic, tf.data, pre-task
    # corpora) take the untouched path: the Python-level membership check
    # runs at trace time, so the traced program is the exact pre-task one.
    strip_loss_fn = loss_fn

    def loss_fn(params, batch_stats, batch, rng, train):  # noqa: F811
        obs, actions = batch
        if isinstance(obs, dict) and health_lib.TASK_ID_KEY in obs:
            obs = dict(obs)
            task_ids = obs.pop(health_lib.TASK_ID_KEY)
            loss, (out, new_bs) = strip_loss_fn(
                params, batch_stats, (obs, actions), rng, train
            )
            if health_tasks:
                out = dict(out, task_ids=task_ids)
            return loss, (out, new_bs)
        return strip_loss_fn(params, batch_stats, batch, rng, train)
    if check_coverage:
        # The default rules are the RT-1 plan; callers training another
        # family (whose param paths the plan does not describe) pass
        # check_coverage=False rather than getting false "would silently
        # replicate" warnings — or a strict-mode abort — for a model that
        # is correctly replicated.
        plan.check_coverage(state.params)
    state_sharding = plan.tree_shardings(state)
    batch_sh = NamedSharding(mesh, P(batch_axes))
    repl = NamedSharding(mesh, P())

    def train_step(state: TrainState, batch: Batch, rng: jax.Array):
        grad_fn = jax.value_and_grad(
            lambda p, bs, b, r: loss_fn(p, bs, b, r, train=True), has_aux=True
        )

        if accum_steps == 1:
            (loss, (out, new_bs)), grads = grad_fn(state.params, state.batch_stats, batch, rng)
        else:
            # Under the reference loss scaling (mean CE / (b·t·(I+A)),
            # transformer_network.py:314-319) the loss is inversely proportional
            # to the *runtime* batch size, so a microbatch of b/accum yields
            # accum× the full-batch loss/grads; one extra /accum makes
            # accumulation exact (proof in tests/test_trainer.py).
            ref_scale = getattr(model, "loss_scale", "mean") == "reference"
            extra = float(accum_steps) if ref_scale else 1.0

            def micro(carry, xs):
                grads_acc, loss_acc, mse_acc, bs = carry
                mb, r = xs
                (l, (mb_out, bs)), g = grad_fn(state.params, bs, mb, r)
                # Metric only: the aux term's gradient already flows via l.
                mse_acc = mse_acc + mb_out.get("aux_mse", jnp.zeros(()))
                return (
                    jax.tree.map(jnp.add, grads_acc, g),
                    loss_acc + l,
                    mse_acc,
                    bs,
                ), None

            def split(x):
                return x.reshape((accum_steps, x.shape[0] // accum_steps) + x.shape[1:])

            micro_batches = jax.tree.map(split, batch)
            rngs = jax.random.split(rng, accum_steps)
            zero_grads = jax.tree.map(jnp.zeros_like, state.params)
            (grads, loss, mse, new_bs), _ = jax.lax.scan(
                micro,
                (zero_grads, jnp.zeros(()), jnp.zeros(()), state.batch_stats),
                (micro_batches, rngs),
            )
            grads = jax.tree.map(lambda g: g / (accum_steps * extra), grads)
            loss = loss / (accum_steps * extra)
            out = {"loss": loss}
            if getattr(model, "aux_mse_weight", 0.0) > 0:
                out["aux_mse"] = mse / accum_steps  # mean over micros

        # Device scopes for what no module names (rt1.py names the model's).
        with jax.named_scope("optimizer"):
            if model_health:
                new_state, updates = state.apply_gradients(
                    grads, new_batch_stats=new_bs, return_updates=True
                )
            else:
                new_state = state.apply_gradients(grads, new_batch_stats=new_bs)
            grad_norm = optax_global_norm(grads)
        metrics = {
            "loss": loss,
            "grad_norm": grad_norm,
        }
        if "action_loss" in out:
            metrics["action_loss_mean"] = jnp.mean(out["action_loss"])
        if "aux_mse" in out:  # soft-argmax regression monitor
            metrics["aux_mse"] = out["aux_mse"]
        # A family's own per-step counters (the routed layers' rows).
        metrics.update(out.get("counters", {}))
        if model_health:
            # One small replicated vector; like every other metric it is
            # dispatched with the step and fetched only at log steps. Fed
            # from the optimizer's update tree, NOT (old, new) params —
            # reading pre-update params would pin the donated buffers.
            with jax.named_scope("health"):
                metrics[health_lib.PACK_KEY] = health_lib.compute_pack(
                    updates=updates,
                    new_params=new_state.params,
                    grads=grads,
                    out=out,
                    depth=health_group_depth,
                    action_dims=health_action_dims,
                    task_names=health_tasks,
                )
        return new_state, metrics

    def eval_step(state: TrainState, batch: Batch):
        loss, (out, _) = loss_fn(
            state.params, state.batch_stats, batch, jax.random.PRNGKey(0), train=False
        )
        metrics = {"loss": loss}
        if "action_labels" in out and "action_predictions" in out:
            labels = out["action_labels"]
            preds = out["action_predictions"]
            metrics["token_accuracy"] = jnp.mean(
                (preds == labels).astype(jnp.float32)
            )
        return metrics

    def train_step_guarded(
        state: TrainState, skips: jnp.ndarray, batch: Batch, rng: jax.Array
    ):
        new_state, metrics = train_step(state, batch, rng)
        ok = jnp.isfinite(metrics["loss"]) & jnp.isfinite(metrics["grad_norm"])
        if guard_grad_norm_max > 0:
            ok &= metrics["grad_norm"] <= guard_grad_norm_max
        # Dropped update = pass the INPUT state through unchanged (including
        # `step`: an update that never happened should not count as one).
        with jax.named_scope("optimizer"):
            new_state = jax.tree.map(
                lambda n, o: jnp.where(ok, n, o), new_state, state
            )
        skips = skips + jnp.where(ok, 0, 1).astype(jnp.int32)
        metrics = dict(metrics, guard_skips_cum=skips)
        return new_state, skips, metrics

    # The start-up log reads the step's trace, lowering and compile by role.
    startup.mark_role(
        "train_step", (train_step_guarded if guard_nonfinite else train_step).__name__)
    startup.mark_role("eval_step", eval_step.__name__)
    with mesh:
        if guard_nonfinite:
            train_jit = jax.jit(
                train_step_guarded,
                in_shardings=(state_sharding, repl, batch_sh, repl),
                out_shardings=(state_sharding, repl, repl),
                donate_argnums=(0, 1) if donate else (),
            )
        else:
            train_jit = jax.jit(
                train_step,
                in_shardings=(state_sharding, batch_sh, repl),
                out_shardings=(state_sharding, repl),
                donate_argnums=(0,) if donate else (),
            )
        eval_jit = jax.jit(
            eval_step,
            in_shardings=(state_sharding, batch_sh),
            out_shardings=repl,
        )

    return TrainStepFns(
        train_step=train_jit,
        eval_step=eval_jit,
        state_sharding=state_sharding,
        batch_sharding=batch_sh,
        mesh=mesh,
        guarded=guard_nonfinite,
        mixed_precision=mixed_precision,
        health_names=health_names,
    )


def _bf16_compute_copy(tree: Any) -> Any:
    """bf16 copy of the f32 leaves (masters untouched; non-float leaves
    pass through). The single cast site of the mixed-precision step."""
    with jax.named_scope("cast_bf16"):
        return jax.tree.map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.asarray(x).dtype == jnp.float32
            else x,
            tree,
        )


def optax_global_norm(tree: Any) -> jnp.ndarray:
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(x)) for x in jax.tree_util.tree_leaves(tree))
    )

"""On-device model-health pack: is the model *learning*, not just stepping.

`obs/steps.py` answers "where does the step's wall time go"; nothing
answered "is the optimization healthy" — per-layer-group gradient norms,
update/param ratios, logit entropy — the signals that show divergence,
dead layers, or a collapsing policy long before the loss curve admits it.

The pack is computed *inside* the jitted train step (`trainer/train.py`,
behind ``config.obs.model_health``) so it inherits the step's contracts:

* **Zero host sync.** Every statistic is packed into ONE small replicated
  float32 vector returned alongside the step metrics; like `loss`, it is
  only fetched at log steps. No per-step D2H, no dispatch stall.
* **Donation-safe.** The pack never reads the *pre-update* params — that
  would keep every donated input buffer alive past the optimizer write
  and break the in-place-update aliasing. It consumes the optimizer's
  update tree instead (``TrainState.apply_gradients(return_updates=True)``;
  ``new = old + updates`` exactly, so nothing is lost).
* **Bit-identical when off.** The gate is a Python-level ``if`` in the
  step builder (the same discipline as the resilience guard): with
  ``model_health=False`` the traced program is exactly the pre-change one.

Layout is static per (param tree, depth, action_dims): :func:`pack_names`
computed on the host template and :func:`compute_pack` traced in the step
derive the same ordering from the same pure function, so the host can
unpack the fetched vector by position. Entries:

* ``health/grad_norm/<group>``     — L2 norm of the (averaged) gradients
  per layer group (param-tree path truncated to `depth` segments).
* ``health/update_ratio/<group>``  — ||params_new - params_old|| /
  (||params_new|| + eps), *post-optimizer* (LR schedule, Adam precond,
  and clipping included). The classic healthy band is ~1e-4..1e-2.
  The denominator is the post-update norm — within ~ratio² of the
  pre-update one, and it saves a whole extra param-tree reduction pass
  (the pack's cost budget is 2% of a *tiny* CPU step, bench --health).
* ``health/param_norm_global``     — global L2 of the updated params.
* ``health/update_norm_global``    — global L2 of the applied update.
* ``health/logit_entropy``         — mean action-token softmax entropy in
  nats (0 = deterministic collapse, log(vocab) = uniform; the copycat
  collapse diagnosed in round 2 shows up here first).
* ``health/token_acc/dim<k>``      — per-action-dimension token accuracy
  of the argmax prediction against the label, one entry per action token.
* ``health/task_loss/<task>`` / ``health/task_acc/<task>`` /
  ``health/task_frac/<task>`` — per-task mean loss, token accuracy, and
  batch share, present only when the feeder emits per-example task ids
  (:data:`TASK_ID_KEY`; ``SampleAheadFeeder(emit_task_ids=True)``).
  Computed by a one-hot segment reduction inside the step — the
  multi-task quality signal (which reward families the policy is
  actually learning) at zero extra host syncs. A task absent from a
  batch reports loss/acc 0 with frac 0; read frac first.

Import-light by contract: jax only inside functions (pinned by
tests/test_obs_imports.py).
"""

from __future__ import annotations

from typing import Any, Dict, List, Mapping, Sequence, Tuple

#: Key under which the packed vector rides in the step metrics dict. The
#: train loop pops it before `scalars_from_metrics` (a vector has no
#: meaningful scalar mean) and unpacks it against `TrainStepFns.health_names`.
PACK_KEY = "health_pack"

#: Observation key carrying the per-example int32 task ids the feeder
#: emits (`SampleAheadFeeder(emit_task_ids=True)`). The step builder
#: strips it from the observations BEFORE the model forward and threads
#: it to `compute_pack` for the per-task one-hot segment reduction — the
#: model never sees it.
TASK_ID_KEY = "task_id"

#: Guard against division by a zero param norm (fresh zeros-init leaves).
_EPS = 1e-12

#: Default group depth: 2 path segments gives per-layer granularity on the
#: RT-1 tree (``transformer/layer_3``) without per-kernel explosion.
DEFAULT_GROUP_DEPTH = 2


def _path_str(path: Sequence[Any]) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def param_groups(params: Any, depth: int = DEFAULT_GROUP_DEPTH) -> List[str]:
    """Sorted group names: param-tree paths truncated to `depth` segments.

    Pure function of the tree *structure* — callable on the host template
    state and inside a trace with identical results, which is what keeps
    the packed layout and the host-side names in lockstep.
    """
    import jax

    if depth < 1:
        raise ValueError(f"depth must be >= 1, got {depth}")
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    return sorted({_path_str(path[:depth]) for path, _ in leaves})


def pack_names(
    params: Any,
    depth: int = DEFAULT_GROUP_DEPTH,
    action_dims: int = 0,
    prefix: str = "health/",
    task_names: Sequence[str] = (),
) -> Tuple[str, ...]:
    """The pack's entry names, in pack order (host-side contract).

    `task_names` (non-empty only when the data stream carries per-example
    task ids AND the step produces action statistics) appends the
    per-task telemetry block: ``task_loss/<t>``, ``task_acc/<t>``,
    ``task_frac/<t>`` per task, in `task_names` order — the model-quality
    signals the eval matrix reads live as ``rt1_train_health_task_*``.
    """
    groups = param_groups(params, depth)
    names = [f"{prefix}grad_norm/{g}" for g in groups]
    names += [f"{prefix}update_ratio/{g}" for g in groups]
    names += [f"{prefix}param_norm_global", f"{prefix}update_norm_global"]
    if action_dims > 0:
        names.append(f"{prefix}logit_entropy")
        names += [f"{prefix}token_acc/dim{k}" for k in range(action_dims)]
        names += [f"{prefix}task_loss/{t}" for t in task_names]
        names += [f"{prefix}task_acc/{t}" for t in task_names]
        names += [f"{prefix}task_frac/{t}" for t in task_names]
    return tuple(names)


def _grouped_sumsq(tree: Any, depth: int) -> Dict[str, Any]:
    """{group: sum of squares} over the tree's leaves (traced).

    One ``sum(square(leaf))`` per leaf, in the same form as
    `trainer.train.optax_global_norm`. Over the gradients XLA's CSE merges
    it with the ``grad_norm`` metric the step already emits; over the trees
    the optimizer has just written (the updates, the new parameters) it
    becomes one more scalar output of the fusion that makes the leaf. No
    pass re-reads the state and no flat copy of it is built: a concatenate
    + vdot per group cost 1.5 % of the flagship's step on a TPU v5e
    (PERF.md section 6, PR 26).

    Spelt in `lax`: the same `square`, `reduce_sum` and `add` equations as
    the `jnp` calls give, without tracing a jitted wrapper for each (three
    a leaf over three trees of 570 leaves was ~2 s of every start-up).
    """
    import jax
    from jax import lax

    out: Dict[str, Any] = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        group = _path_str(path[:depth])
        x = lax.convert_element_type(leaf, "float32")
        sq = lax.reduce_sum(lax.square(x), axes=tuple(range(x.ndim)))
        out[group] = lax.add(out[group], sq) if group in out else sq
    return out


def compute_pack(
    updates: Any,
    new_params: Any,
    grads: Any,
    out: Mapping[str, Any],
    depth: int = DEFAULT_GROUP_DEPTH,
    action_dims: int = 0,
    task_names: Sequence[str] = (),
):
    """Build the packed health vector inside the traced train step.

    `updates` is the optimizer's applied update tree (``new = old +
    updates``) — taking it instead of (old, new) params matters beyond
    convenience: a pack that reads the *pre-update* params would force
    XLA to keep every donated input param buffer alive past the optimizer
    write, breaking the in-place-update aliasing the donated-state
    contract exists for.

    `out` is the loss closure's aux dict; action-logit statistics are read
    from it only when ``action_dims > 0`` (the builder decides that
    statically — RT-1 loss with accum_steps == 1). Returns a float32
    vector whose entries line up with :func:`pack_names` called with the
    same (tree, depth, action_dims).
    """
    import jax
    import jax.numpy as jnp

    groups = param_groups(new_params, depth)
    grad_sq = _grouped_sumsq(grads, depth)
    upd_sq = _grouped_sumsq(updates, depth)
    new_sq = _grouped_sumsq(new_params, depth)

    parts = [
        jnp.stack([jnp.sqrt(grad_sq[g]) for g in groups]),
        jnp.stack(
            [
                jnp.sqrt(upd_sq[g]) / (jnp.sqrt(new_sq[g]) + _EPS)
                for g in groups
            ]
        ),
        jnp.sqrt(sum(new_sq[g] for g in groups))[None],
        jnp.sqrt(sum(upd_sq[g] for g in groups))[None],
    ]

    if action_dims > 0:
        logits = jnp.asarray(out["action_logits"], jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        parts.append(-jnp.mean(jnp.sum(jnp.exp(logp) * logp, axis=-1))[None])
        correct = (out["action_predictions"] == out["action_labels"]).astype(
            jnp.float32
        )  # (b, t, A)
        per_dim = jnp.mean(correct, axis=(0, 1))  # (A,)
        if per_dim.shape[0] != action_dims:
            raise ValueError(
                f"action_dims={action_dims} but the step produced "
                f"{per_dim.shape[0]} action token dims"
            )
        parts.append(per_dim)
        if task_names:
            # Per-task loss / token accuracy / batch share via ONE one-hot
            # segment reduction (K = len(task_names) matmuls fused by XLA):
            # the multi-task training signal, still zero host sync — it
            # rides the same replicated pack vector. Tasks absent from
            # this batch report 0 with frac 0 (readable as "no data", not
            # "perfectly learned": dashboards gate on task_frac).
            task_ids = jnp.asarray(out["task_ids"], jnp.int32)  # (b,)
            per_ex_loss = jnp.mean(
                jnp.asarray(out["action_loss"], jnp.float32), axis=-1
            )  # (b,)
            per_ex_acc = jnp.mean(correct, axis=(1, 2))  # (b,)
            onehot = jax.nn.one_hot(
                task_ids, len(task_names), dtype=jnp.float32
            )  # (b, K)
            counts = jnp.sum(onehot, axis=0)  # (K,)
            denom = jnp.maximum(counts, 1.0)
            parts.append(onehot.T @ per_ex_loss / denom)
            parts.append(onehot.T @ per_ex_acc / denom)
            parts.append(counts / task_ids.shape[0])
    return jnp.concatenate(parts).astype(jnp.float32)


def unpack(names: Sequence[str], vector: Any) -> Dict[str, float]:
    """Fetched pack vector -> {name: float} for the scalar stream.

    The names come out as e.g. ``health/grad_norm/transformer/layer_0`` —
    the clu writer takes them as-is, and the train Prometheus listener's
    sanitizer renders them as ``rt1_train_health_grad_norm_...`` gauges.
    """
    import numpy as np

    values = np.asarray(vector, dtype=np.float64).reshape(-1)
    if values.shape[0] != len(names):
        raise ValueError(
            f"health pack length {values.shape[0]} != {len(names)} names — "
            f"the step builder and the host disagree on the layout"
        )
    return {name: float(v) for name, v in zip(names, values)}

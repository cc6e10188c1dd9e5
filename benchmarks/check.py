"""What decides ``correct`` for a training cell.

The program's first three steps (driven through the window's own call and
feed) against the plain reference following the same three batches from the
same weights: each step's loss, the first gradient as Adam received it
(first moment after one step / (1 - b1)), and the parameters' change after
three steps.  Norms are compared leaf by leaf: the gap between the program's
norm and the reference's, over the reference's norm of that leaf or of the
median leaf, whichever is larger; the worst leaf is the number.  Leaves whose
reference gradient is under a thousandth of the median leaf's move under Adam
by round-off alone and are left out of the change.

The limits are in the configuration's file, set from readings as PERF.md
section 2 records.
"""

from __future__ import annotations

import importlib
import json
import time
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
DEAD_GRADIENT = 1e-3      # of the median leaf's gradient norm


def adam_mu(opt_state):
    """First-moment tree of an optax Adam state, wherever the chain holds it."""
    import jax

    found = [s for s in jax.tree.leaves(
        opt_state, is_leaf=lambda s: hasattr(s, "mu") and hasattr(s, "nu"))
        if hasattr(s, "mu")]
    if len(found) != 1:
        raise ValueError(f"expected one Adam state in the optimizer, found {len(found)}")
    return found[0].mu


def leaf_norms(tree) -> Dict[str, float]:
    import flax

    flat = flax.traverse_util.flatten_dict(tree)
    return {"/".join(map(str, k)): float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in flat.items()}


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              keep: Sequence[str]) -> Dict[str, float]:
    median = float(np.median([reference[k] for k in keep]))
    return {k: abs(program[k] - reference[k]) / max(reference[k], median, 1e-30)
            for k in keep}


def load_reference(name: str):
    return importlib.import_module(f"benchmarks.references.{name}")


_STEPS: Dict[Any, Any] = {}


def _reference_step(ref, sz, lr: float, prec: str):
    """One jitted Adam step of the reference, built once per process for a
    (reference, sizes, precision): a tool that reads many seeds compiles it
    once."""
    import jax
    import jax.numpy as jnp

    key = (ref.__name__, json.dumps(sz, sort_keys=True, default=str), lr, prec)
    if key in _STEPS:
        return _STEPS[key]

    def step(params, batch_stats, mu, nu, batch, rng, count):
        (loss, batch_stats), grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, batch_stats, batch, rng, sz, prec), has_aux=True
        )(params)
        mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
        c1, c2 = 1 - ADAM_B1 ** count, 1 - ADAM_B2 ** count
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
            params, mu, nu,
        )
        return params, batch_stats, mu, nu, loss, grads

    _STEPS[key] = jax.jit(step, donate_argnums=(2, 3))
    return _STEPS[key]


def follow(ref, sz, params, batch_stats, batches, keys, lr: float, prec: str):
    """Three plain Adam steps of the reference.  Returns the losses, the
    first gradient and the change of the parameters (all as numpy trees)."""
    import jax
    import jax.numpy as jnp

    step = _reference_step(ref, sz, lr, prec)
    start = jax.device_get(params)
    # one program, not a zeros_like per leaf shape
    mu, nu = jax.jit(lambda t: (jax.tree.map(jnp.zeros_like, t),) * 2)(params)
    losses, grad1 = [], None
    for i, (batch, key) in enumerate(zip(batches, keys)):
        params, batch_stats, mu, nu, loss, grads = step(
            params, batch_stats, mu, nu, batch, key, jnp.float32(i + 1)
        )
        losses.append(float(loss))
        if i == 0:
            grad1 = jax.device_get(grads)
        del grads
    delta = jax.tree.map(np.subtract, jax.device_get(params), start)
    return losses, grad1, delta


def model_batch(host_batch) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(observations, actions) as the model sees them: the feeder's
    per-example task ids are telemetry, not input."""
    obs = {k: v for k, v in host_batch["observations"].items()
           if k in ("image", "natural_language_embedding")}
    return obs, dict(host_batch["actions"])


def reference_readings(config_file, abstract, seed, batches, prec: str, log) -> Dict[str, Any]:
    import jax

    from benchmarks import program, weights

    ref = load_reference(config_file["reference"])
    sz = ref.sizes(config_file["overrides"])
    params, batch_stats = weights.make_weights(
        abstract[0], abstract[1], seed, program.weight_gains(config_file))
    base = weights.seed_key(seed)
    keys = [jax.random.fold_in(base, i) for i in range(len(batches))]
    t0 = time.perf_counter()
    losses, grad1, delta = follow(
        ref, sz, params, batch_stats, [model_batch(b) for b in batches], keys,
        float(config_file["overrides"]["learning_rate"]), prec,
    )
    log(f"reference ({prec}): {len(batches)} steps in {time.perf_counter() - t0:.1f}s")
    return {"losses": losses, "grad1": leaf_norms(grad1), "delta": leaf_norms(delta)}


NUMBERS = ("loss_1", "loss_2", "loss_3", "grad_1", "grad_1_median_leaf", "grad_1_p90_leaf",
           "change_3", "change_3_median_leaf", "change_3_p90_leaf", "change_3_worst_ratio")


def numbers(program: Dict[str, Any], reference: Dict[str, Any]) -> Dict[str, Tuple[float, str]]:
    """The numbers compared, each with the leaf it was worst at (or '')."""
    out = {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"])):
        out[f"loss_{i + 1}"] = (abs(a - b) / max(abs(b), 1e-30), "")
    leaves = sorted(reference["grad1"])
    median = float(np.median([reference["grad1"][k] for k in leaves]))
    alive = [k for k in leaves if reference["grad1"][k] >= DEAD_GRADIENT * median]
    for name, tree, keep in (("grad_1", "grad1", leaves), ("change_3", "delta", alive)):
        gaps = leaf_gaps(program[tree], reference[tree], keep)
        at = max(gaps, key=gaps.get)
        values = np.array(list(gaps.values()))
        out[name] = (gaps[at], at)
        out[name + "_median_leaf"] = (float(np.median(values)), "")
        out[name + "_p90_leaf"] = (float(np.percentile(values, 90)), "")
    # a single leaf that has not moved: under Adam a sound leaf's change is a
    # few learning rates an element whatever its gradient's size, so the ratio
    # of its norm to the reference's stays near 1 however small the leaf; the
    # number is the ratio farthest from 1, taken the way that is over 1
    tiny = 1e-30
    ratios = {k: max(program["delta"][k], reference["delta"][k], tiny)
              / max(min(program["delta"][k], reference["delta"][k]), tiny) for k in alive}
    at = max(ratios, key=ratios.get)
    out["change_3_worst_ratio"] = (min(ratios[at], 1e30), at)
    out["_left_out"] = (float(len(leaves) - len(alive)), "")
    return out


def program_readings(abstract, seed, config_file, losses, mu1, params3) -> Dict[str, Any]:
    """Norms of what the timed path produced.  The start of the change is the
    seed's weights, which the benchmark made and can make again."""
    import jax

    from benchmarks import program, weights

    params0, _ = weights.make_weights(
        abstract[0], abstract[1], seed, program.weight_gains(config_file))
    params0 = jax.device_get(params0)
    grad1 = jax.tree.map(lambda m: np.asarray(m, np.float64) / (1 - ADAM_B1), mu1)
    delta = jax.tree.map(
        lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64),
        params3, params0,
    )
    return {"losses": list(losses), "grad1": leaf_norms(grad1), "delta": leaf_norms(delta)}


def judge(nums: Dict[str, Tuple[float, str]], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    checks = []
    for name, limit in limits.items():
        value, at = nums[name]
        ok = bool(np.isfinite(value) and value <= limit)
        checks.append({"name": name, "value": value, "limit": limit, "ok": ok, "at": at})
    return checks


def compare_training(*, config_file, config, abstract, seed, batches, losses, mu1,
                     params3, log) -> List[Dict[str, Any]]:
    program = program_readings(abstract, seed, config_file, losses, mu1, params3)
    reference = reference_readings(config_file, abstract, seed, batches, "highest", log)
    nums = numbers(program, reference)
    log(f"leaves left out of the change (reference gradient under "
        f"{DEAD_GRADIENT} of the median leaf's): {int(nums['_left_out'][0])}")
    limits = config_file["limits"]
    log("read and not compared (PERF.md section 2 says why): " + ", ".join(
        f"{k} {v[0]:.4g}" for k, v in nums.items() if k not in limits and k != "_left_out"))
    return judge(nums, limits)

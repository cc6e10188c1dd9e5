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

The reference's three steps hold four copies of the parameters' size on the
device, the trained program's own 16 B a parameter, beside the loss's
temporaries: a step is two programs, the loss's gradient (the masters read,
one gradient written) and Adam's update, to which masters and both moments
are donated, so it runs in place.  (One program with the masters
donated to it holds more: the compiler copies what the reference's loops read
before it lets the update overwrite it; PERF.md section 2.)  On the host every
norm is taken a leaf at a time.
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


def leaf_norms(tree, *more, of=None) -> Dict[str, float]:
    """Norm, in float64, of every leaf of ``tree``; with ``of``, of
    ``of(leaf, *the same leaf of each further tree)``.  A leaf at a time: no
    float64 copy of a whole tree is ever held."""
    import flax

    flats = [flax.traverse_util.flatten_dict(t) for t in (tree, *more)]
    for flat in flats:      # a device's leaves start for the host together
        for v in flat.values():
            if hasattr(v, "copy_to_host_async"):
                v.copy_to_host_async()
    out = {}
    for k, v in flats[0].items():
        leaf = v if of is None else of(v, *(f[k] for f in flats[1:]))
        out["/".join(map(str, k))] = float(np.linalg.norm(np.asarray(leaf, np.float64)))
    return out


def leaf_gaps(program: Dict[str, float], reference: Dict[str, float],
              keep: Sequence[str]) -> Dict[str, float]:
    median = float(np.median([reference[k] for k in keep]))
    return {k: abs(program[k] - reference[k]) / max(reference[k], median, 1e-30)
            for k in keep}


def load_reference(name: str):
    return importlib.import_module(f"benchmarks.references.{name}")


_STEPS: Dict[Any, Any] = {}


def _reference_step(ref, sz, lr: float, prec: str):
    """(gradient, update): the two jitted programs of one Adam step of the
    reference, built once per process for a (reference, sizes, precision): a
    tool that reads many seeds compiles them once."""
    import jax
    import jax.numpy as jnp

    key = (ref.__name__, json.dumps(sz, sort_keys=True, default=str), lr, prec)
    if key in _STEPS:
        return _STEPS[key]

    def gradient(params, batch_stats, batch, rng):
        (loss, batch_stats), grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, batch_stats, batch, rng, sz, prec), has_aux=True
        )(params)
        return loss, batch_stats, grads

    def update(params, mu, nu, grads, count):
        mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g, nu, grads)
        c1, c2 = 1 - ADAM_B1 ** count, 1 - ADAM_B2 ** count
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
            params, mu, nu,
        )
        return params, mu, nu

    _STEPS[key] = (jax.jit(gradient), jax.jit(update, donate_argnums=(0, 1, 2)))
    return _STEPS[key]


def follow(ref, sz, params, batch_stats, batches, keys, lr: float, prec: str):
    """Three plain Adam steps of the reference.  Returns the losses, the leaf
    norms of the first gradient and of the parameters' change, and the seconds
    spent waiting for the steps and reading and reducing trees on the host.
    ``params`` is donated to the first update: a caller that follows twice
    makes the weights twice (they come from the seed)."""
    import jax
    import jax.numpy as jnp

    gradient, update = _reference_step(ref, sz, lr, prec)
    clock = {"steps": 0.0, "host": 0.0}

    def timed(part, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        clock[part] += time.perf_counter() - t0
        return out

    start = timed("host", jax.device_get, params)
    # one program, not a zeros_like per leaf shape
    mu, nu = jax.jit(lambda t: (jax.tree.map(jnp.zeros_like, t),) * 2)(params)
    losses, grad1 = [], None
    for i, (batch, key) in enumerate(zip(batches, keys)):
        loss, batch_stats, grads = gradient(params, batch_stats, batch, key)
        losses.append(timed("steps", float, loss))
        if i == 0:
            grad1 = timed("host", leaf_norms, grads)
        params, mu, nu = update(params, mu, nu, grads, jnp.float32(i + 1))
        del grads
    timed("steps", jax.block_until_ready, params)
    delta = timed("host", lambda: leaf_norms(params, start, of=np.subtract))
    return losses, grad1, delta, clock


def model_batch(host_batch) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """(observations, actions) as the model sees them: the feeder's
    per-example task ids are telemetry, not input."""
    obs = {k: v for k, v in host_batch["observations"].items()
           if k in ("image", "natural_language_embedding")}
    return obs, dict(host_batch["actions"])


def reference_readings(config_file, abstract, seed, batches, prec: str, log,
                       as_model=model_batch) -> Dict[str, Any]:
    """``as_model`` turns a batch of the feed into what the reference takes."""
    import jax

    from benchmarks import program, weights

    ref = load_reference(config_file["reference"])
    sz = ref.sizes(config_file["overrides"])
    params, batch_stats = weights.make_weights(
        abstract[0], abstract[1], seed, program.weight_gains(config_file))
    base = weights.seed_key(seed)
    keys = [jax.random.fold_in(base, i) for i in range(len(batches))]
    t0 = time.perf_counter()
    losses, grad1, delta, clock = follow(
        ref, sz, params, batch_stats, [as_model(b) for b in batches], keys,
        float(config_file["overrides"]["learning_rate"]), prec,
    )
    log(f"reference ({prec}): {len(batches)} steps in {time.perf_counter() - t0:.1f}s "
        f"(waiting for the steps {clock['steps']:.1f}s, reading trees to the host and their "
        f"norms {clock['host']:.1f}s)")
    return {"losses": losses, "grad1": grad1, "delta": delta}


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
    params0 = jax.device_get(params0)     # and the device's copy is dropped
    delta = leaf_norms(
        params3, params0,
        of=lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64))
    grad1 = leaf_norms(mu1, of=lambda m: np.asarray(m, np.float64) / (1 - ADAM_B1))
    return {"losses": list(losses), "grad1": grad1, "delta": delta}


def judge(nums: Dict[str, Tuple[float, str]], limits: Dict[str, float]) -> List[Dict[str, Any]]:
    checks = []
    for name, limit in limits.items():
        value, at = nums[name]
        ok = bool(np.isfinite(value) and value <= limit)
        checks.append({"name": name, "value": value, "limit": limit, "ok": ok, "at": at})
    return checks


def compare_training(*, config_file, abstract, seed, batches, losses, mu1, params3, log,
                     as_model=model_batch) -> List[Dict[str, Any]]:
    import jax

    from benchmarks import devices

    program = program_readings(abstract, seed, config_file, losses, mu1, params3)
    reference = reference_readings(
        config_file, abstract, seed, batches, "highest", log, as_model)
    nums = numbers(program, reference)
    # a process's peak never falls: this is the larger of the window's and the reference's
    mem = devices.memory(jax.local_devices())
    log(f"memory after the comparison: peak_bytes_in_use {mem['peak_bytes_in_use']}, "
        f"peak_bytes_reserved {mem['peak_bytes_reserved']}")
    log(f"losses: program {['%.6f' % x for x in losses]}, reference "
        f"{['%.6f' % x for x in reference['losses']]}")
    log(f"leaves left out of the change (reference gradient under "
        f"{DEAD_GRADIENT} of the median leaf's): {int(nums['_left_out'][0])}")
    limits = config_file["limits"]
    log("read and not compared (PERF.md section 2 says why): " + ", ".join(
        f"{k} {v[0]:.4g}" for k, v in nums.items() if k not in limits and k != "_left_out"))
    return judge(nums, limits)

"""Weights made by the benchmark from ``--seed``, in one jitted call.

The program's own initialisers are not used: the benchmark makes every leaf
itself, so that the timed program and the plain reference (which regenerates
the same leaves from the same seed) start from values neither of them made.
The rule is by the leaf's last name, the way any flax tree spells them:

* ``kernel``: normal / sqrt(fan_in) x gain, fan_in = product of all but the
  last axis (a depthwise (k, k, 1, C) kernel has fan_in k*k);
* ``embedding``: normal x 0.02;  ``bias``: normal x 0.02;
* ``scale``: 1 + normal x 0.02;  batch statistics: ``mean`` 0, ``var`` 1.

``gains`` (from the configuration's file) maps a path component to a gain for
the kernels under it: FiLM projections are zero in the program's own init, and
a full-size draw there would multiply 27 times through the trunk.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Mapping, Tuple

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """PRNG key of a seed of any size the driver sends (a little over 2**31)."""
    return jax.random.PRNGKey(int(seed) & 0x7FFFFFFF)


def _flat(tree: Any) -> Dict[Tuple[str, ...], Any]:
    import flax

    return flax.traverse_util.flatten_dict(tree) if tree else {}


def _leaf(draw, path: Tuple[str, ...], shape, gains: Mapping[str, float]):
    name = path[-1]
    if name == "kernel":
        gain = 1.0
        for part in path:
            gain *= float(gains.get(part, 1.0))
        fan_in = max(1, math.prod(shape[:-1]))
        return draw * (gain / math.sqrt(fan_in))
    if name in ("bias", "embedding"):
        return draw * 0.02
    if name == "scale":
        return 1.0 + draw * 0.02
    if name == "mean":
        return jnp.zeros(shape, jnp.float32)
    if name == "var":
        return jnp.ones(shape, jnp.float32)
    raise ValueError(f"no rule for a leaf named {name!r} at {'/'.join(path)}")


def make_weights(abstract_params, abstract_batch_stats, seed: int,
                 gains: Mapping[str, float]):
    """(params, batch_stats) as float32 trees shaped like the abstract ones.

    One normal draw for the whole model, cut into the leaves in the sorted
    order of their paths: a draw per leaf is some hundreds of generators to
    compile."""
    import flax

    trees = (_flat(abstract_params), _flat(abstract_batch_stats))
    leaves = sorted(
        (tuple(map(str, path)), t, path, tuple(a.shape))
        for t, tree in enumerate(trees) for path, a in tree.items()
    )

    def build(key):
        draw = jax.random.normal(
            key, (sum(math.prod(shape) for *_, shape in leaves),), jnp.float32)
        flats, offset = ({}, {}), 0
        for names, t, path, shape in leaves:
            n = math.prod(shape)
            flats[t][path] = _leaf(draw[offset:offset + n].reshape(shape), names, shape, gains)
            offset += n
        return tuple(flax.traverse_util.unflatten_dict(f) if f else {} for f in flats)

    return jax.jit(build)(seed_key(seed))

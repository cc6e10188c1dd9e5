"""Sharding mechanics: map parameter paths / batch pytrees to `NamedSharding`s.

Replaces the reference's implicit "replicate everything" layout (DDP keeps a full
model copy per GPU, `distribute_train.py:235`; `flax_utils.replicate` in Stack B,
`language_table/train/train.py:140`). Here layout is explicit and rule-driven: a
list of (path-regex, PartitionSpec) pairs decides where each parameter lives, and
GSPMD propagates everything else.

The rules themselves live in ONE place — `rt1_tpu/parallel/plan.py`'s
declarative plan, which covers every RT-1 param group over the
``('data', 'stage', 'fsdp', 'model')`` mesh and carries the coverage
check that keeps a renamed module from silently replicating. The historical
entry point below (`rt1_parameter_rules`) is a thin
view into that plan; this module keeps the pure mechanics: path
stringification, first-match-wins resolution, pytree mapping.

With every plan axis at size 1 the specs all degenerate to pure data
parallelism at zero cost, which is the reference-parity configuration.
"""

from __future__ import annotations

import re
from typing import Any, List, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Rule = Tuple[str, P]


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, axis: str = "data") -> NamedSharding:
    """Shard the leading (batch) dim over `axis`, replicate the rest."""
    return NamedSharding(mesh, P(axis))


def per_device_bytes(tree: Any) -> dict:
    """Bytes each addressable device actually holds of `tree`'s arrays.

    Read off `addressable_shards` — what the runtime placed, not what a
    plan asked for. Compare with `sum(leaf.nbytes)`: a sharded tree holds
    less than that on every device, a replicated one holds all of it.
    """
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = out.get(shard.device.id, 0) + shard.data.nbytes
    return out


def rt1_parameter_rules() -> List[Rule]:
    """Path-regex → PartitionSpec for RT1Policy parameters: the full
    declarative plan (plan.py), one rule list for every param group.

    Paths are '/'-joined flax param paths, e.g.
    ``transformer/layer_0/attn/query/kernel``. First match wins; no match →
    replicated (but see `plan.ShardingPlan.coverage` — weight matrices are
    not allowed to fall through silently). Kernel layouts: Dense kernels
    are (in, out).
    """
    from rt1_tpu.parallel import plan as planlib

    return planlib.rt1_sharding_plan()


def _path_str(path: Tuple[Any, ...]) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "name"):       # GetAttrKey (dataclass fields, e.g. TrainState)
            parts.append(str(p.name))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def sharding_for_path(
    path: Tuple[Any, ...], mesh: Mesh, rules: Sequence[Rule]
) -> NamedSharding:
    s = _path_str(path)
    for pattern, spec in rules:
        if re.search(pattern, s):
            return NamedSharding(mesh, spec)
    return NamedSharding(mesh, P())


def spec_for_shape(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """`spec` with any axis entry dropped (that dim replicated) when the
    mesh-axes product does not divide the dim.

    The plan's rules are written for the large-config shapes; small
    instantiations hit indivisible dims (EfficientNet SE bottlenecks have
    cout as small as 6, FiLM channels as small as 8) which XLA refuses to
    shard. Replicating such a dim is the intended degradation — the
    tensors for which divisibility fails are precisely the ones too small
    for sharding to matter — and keeps dense/fsdp/tp config switches from
    crashing at placement on any model size.
    """
    if not spec:
        return spec
    dims = []
    changed = False
    for i, entry in enumerate(spec):
        if entry is None or i >= len(shape):
            dims.append(entry)
            continue
        axes = entry if isinstance(entry, (tuple, list)) else (entry,)
        ways = 1
        for a in axes:
            ways *= mesh.shape.get(a, 1)
        if ways > 1 and shape[i] % ways != 0:
            dims.append(None)
            changed = True
        else:
            dims.append(entry)
    if not changed:
        return spec
    while dims and dims[-1] is None:  # P(None, ..., None) ≡ P()
        dims.pop()
    return P(*dims)


def shard_pytree(tree: Any, mesh: Mesh, rules: Sequence[Rule]) -> Any:
    """A pytree of NamedShardings matching `tree`'s structure, per the rules
    (indivisible dims fall back per `spec_for_shape`)."""

    def one(path, leaf):
        sh = sharding_for_path(path, mesh, rules)
        shape = getattr(leaf, "shape", None)
        if shape is None:
            return sh
        safe = spec_for_shape(sh.spec, shape, mesh)
        return sh if safe is sh.spec else NamedSharding(mesh, safe)

    return jax.tree_util.tree_map_with_path(one, tree)

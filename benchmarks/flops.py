"""The yardstick of the step: FLOPs and least bytes, derived from shapes.

    python -m benchmarks.flops <config-name>

walks the jaxpr of the model's FORWARD pass at the configuration's shapes
(``jax.make_jaxpr`` on abstract inputs: nothing runs), sums the
multiply-adds of ``dot_general`` and ``conv_general_dilated`` as 2 FLOPs
each, and counts forward + backward as three times that.  Element-wise work,
normalisation, softmax, the optimizer, the health pack and anything the
program recomputes are not counted.  The numbers are frozen in the
configuration's file (``flops_per_sample``, ``min_bytes_per_step``); the run
reads them, and tests/benchmark recomputes them.
"""

from __future__ import annotations

import json
import math
import os
import sys
from typing import Any, Dict


def _eqn_flops(eqn) -> float:
    name = eqn.primitive.name
    if name == "dot_general":
        lhs = eqn.invars[0].aval.shape
        k = math.prod(lhs[d] for d in eqn.params["dimension_numbers"][0][0])
        return 2.0 * math.prod(eqn.outvars[0].aval.shape) * k
    if name == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval.shape
        spatial = math.prod(rhs[d] for d in dn.rhs_spec[2:])
        in_per_group = rhs[dn.rhs_spec[1]]
        return 2.0 * math.prod(eqn.outvars[0].aval.shape) * spatial * in_per_group
    return 0.0


def jaxpr_flops(jaxpr) -> float:
    """Matrix and convolution FLOPs of a jaxpr, sub-jaxprs included (a scan's
    body times its length)."""
    total = 0.0
    for eqn in jaxpr.eqns:
        total += _eqn_flops(eqn)
        times = float(eqn.params.get("length", 1)) if eqn.primitive.name == "scan" else 1.0
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    total += times * jaxpr_flops(inner)
    return total


def yardstick(config_file: Dict[str, Any]) -> Dict[str, float]:
    import jax
    import jax.numpy as jnp

    from rt1_tpu.trainer.train import _loss_fn

    from benchmarks import program

    config = program.program_config(config_file)
    _, model, init_fn, loss_fn, tx = program.build_model(
        config, devices=jax.devices()[:1]
    )
    shapes = program.abstract_state(config, model, init_fn, tx)
    batch = program.batch_spec(config)
    if loss_fn is None:
        loss_fn = lambda p, bs, b, r, train: _loss_fn(model, p, bs, b, r, train)  # noqa: E731

    def forward(params, batch_stats, batch, rng):
        return loss_fn(params, batch_stats, batch, rng, train=True)[0]

    jaxpr = jax.make_jaxpr(forward)(
        shapes.params, shapes.batch_stats, batch, jax.ShapeDtypeStruct((2,), jnp.uint32)
    )
    forward_flops = jaxpr_flops(jaxpr.jaxpr)
    nbytes = lambda tree: sum(  # noqa: E731
        math.prod(a.shape) * a.dtype.itemsize for a in jax.tree.leaves(tree)
    )
    params_b, opt_b, batch_b = nbytes(shapes.params), nbytes(shapes.opt_state), nbytes(batch)
    b = int(config.per_host_batch_size)
    return {
        "forward_flops_per_sample": forward_flops / b,
        "flops_per_sample": 3.0 * forward_flops / b,
        # parameters and optimizer state read and written, the batch read once
        "min_bytes_per_step": float(2 * params_b + 2 * opt_b + batch_b),
        "param_bytes": float(params_b),
        "batch_bytes": float(batch_b),
    }


def main() -> int:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "configs", sys.argv[1] + ".json")) as f:
        print(json.dumps(yardstick(json.load(f)), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

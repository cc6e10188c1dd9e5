"""Times the candidate kernels of the lfm2_moe family's step on the chip, at
the benchmark cell's shapes: causal attention forward + backward (the
library's Pallas flash kernel at several block sizes against the blockwise
lax form) and the grouped expert product forward + backward
(``lax.ragged_dot`` against megablox ``gmm`` at several tilings) with an
eighth of the row buffer in groups.  A tool for PERF.md section 6; no
benchmark metric reads it.

    python scripts/lm_kernel_probe.py [--rows 65536 --held_rows 8192]
"""

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax import lax


def timed(fn, *args, repeats=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(repeats):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / repeats * 1e3


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=65536)
    ap.add_argument("--held_rows", type=int, default=8192)
    ap.add_argument("--seq", type=int, default=8192)
    args = ap.parse_args()

    sys.path.insert(0, ".")
    from rt1_tpu.models.lm import layers

    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    key = jax.random.PRNGKey(0)
    b, s, kvh, g, d = 2, args.seq, 8, 4, 64
    q = jax.random.normal(key, (b, s, kvh, g, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, kvh, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, kvh, d), jnp.bfloat16)

    def attn(impl, block):
        def loss(q, k, v):
            if impl == "flash":
                out = layers.flash_attention(q, k, v, d ** -0.5, block)
            else:
                out = layers.blockwise_attention(q, k, v, d ** -0.5, block)
            return jnp.sum(out.astype(jnp.float32))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

    for impl, block in (("flash", 512), ("flash", 1024), ("flash", 256), ("flash", 2048),
                        ("blockwise", 512), ("blockwise", 1024)):
        try:
            ms = timed(attn(impl, block), q, k, v)
            print(json.dumps({"attention": impl, "block": block, "fwd_bwd_ms": ms}), flush=True)
        except Exception as exc:  # noqa: BLE001 - a probe reports and goes on
            print(json.dumps({"attention": impl, "block": block, "error": repr(exc)[:300]}),
                  flush=True)

    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    held, dm, f = 8, 2048, 1536
    rows = jax.random.normal(key, (args.rows, dm), jnp.bfloat16)
    w13 = jax.random.normal(key, (held, dm, 2 * f), jnp.bfloat16) * 0.02
    w2 = jax.random.normal(key, (held, f, dm), jnp.bfloat16) * 0.02
    for held_rows in (args.held_rows, args.rows):
        sizes = jnp.full((held,), held_rows // held, jnp.int32)

        def experts(product):
            def loss(rows, w13, w2):
                gate, up = jnp.split(product(rows, w13, sizes), 2, axis=-1)
                out = product(jax.nn.silu(gate) * up, w2, sizes)
                valid = (jnp.arange(args.rows) < held_rows)[:, None]
                return jnp.sum(jnp.where(valid, out, 0).astype(jnp.float32))
            return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

        candidates = [("ragged_dot", None)] + [("megablox", t) for t in (
            (512, 1024, 1024), (512, 512, 512), (256, 1024, 1024), (1024, 1024, 1024),
            (512, 2048, 1536), (128, 128, 128))]
        for impl, tiling in candidates:
            if impl == "ragged_dot":
                product = lambda a, w, gs: lax.ragged_dot(  # noqa: E731
                    a, w, gs, preferred_element_type=a.dtype)
            else:
                product = lambda a, w, gs, t=tiling: megablox.gmm(  # noqa: E731
                    a, w, gs, a.dtype, t)
            try:
                ms = timed(experts(product), rows, w13, w2)
                print(json.dumps({"experts": impl, "tiling": tiling, "rows_in_groups": held_rows,
                                  "buffer_rows": args.rows, "fwd_bwd_ms": ms}), flush=True)
            except Exception as exc:  # noqa: BLE001
                print(json.dumps({"experts": impl, "tiling": tiling, "rows_in_groups": held_rows,
                                  "error": repr(exc)[:300]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

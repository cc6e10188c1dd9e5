"""Times the candidate kernels of a decoder LM's step on the chip, at a
benchmark cell's shapes (the defaults are ``lfm2-24b-a2b.train-tokens-8k``'s;
``--seq 16384 --batch 1 --heads 32 --kv-heads 4 --head-dim 128 --window 1024
--hidden 2304 --expert-width 896 --experts-held 16 --top-k 8`` are
``mellum2-12b-a2.5b.train-tokens-16k``'s): causal attention forward + backward (the
library's splash kernel over block sizes, fused and unfused backward and k's
layout, against the older flash kernel with its KV heads repeated and the
blockwise lax form) and the grouped expert product forward + backward
(``lax.ragged_dot`` against megablox ``gmm`` at several tilings) with an
eighth of the row buffer in groups; and the routed layer's ways between
tokens and expert rows (``--only routed``): the slot gather against a
scatter-add of the rows (expert order, and token order with the sorted hint)
at the rule's row buffer and at all the slots, the index each needs (the sort
of all slots against a token-major compaction that sorts the buffer's keys
only), the combine's way back gathering float32 rows (jax's own transpose)
against bfloat16 rows widened after (``moe.token_sums``), and what was tried
for the row path's moves and not taken (PERF.md section 6, PR 32; what a
Pallas row mover has to beat): each move as a loop over the tiles that hold
rows (tiles of 512, 1,024 and 2,048; the rows the routing holds, and a full
buffer), a tile's scatter-add with its rows sorted inside the trip, and a
whole buffer's scatter-add with the compiler's sort done by hand (one sort,
bfloat16 rows permuted); then the whole layer both ways at both capacities
and with each of those in the program's place (``--only layer``: those rows
alone, 5-6 min a shape: seven programs compile).  A tool for PERF.md section
6; no benchmark metric reads it.

With ``--window`` the attention rows are a sliding layer's (``LocalMask``,
the query's own key and the ``window - 1`` before it): blocks of 256, 512 and
1,024, fused and unfused backward, and the blockwise lax form.

    python scripts/lm_kernel_probe.py [--rows 65536 --held_rows 8192] [--only routed]

Three parts run on request only: ``--only head`` (the output head and the
loss both ways at the three token cells' shapes: the loop of blocks under
``jax.checkpoint`` that PR 36 had against the gradient made in the forward
loop, with a float32 and a bfloat16 accumulator, at every ``--block``),
``--only latent`` (a latent-attention layer's kernel: keys of 192 as they are
and zero-padded to 256, values of 128, fused and unfused backward, blocks;
``--batch 1 --seq 8192 --heads 16``) and ``--only streams`` (one
hyper-connection sublayer both ways, the streams held in bfloat16 and in
float32; ``--batch 1 --seq 8192 --hidden 3584 --streams 4``).
"""

import argparse
import functools
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
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=64)
    ap.add_argument("--window", type=int, default=None,
                    help="keys a query sees, its own among them (default: causal)")
    ap.add_argument("--hidden", type=int, default=2048)
    ap.add_argument("--expert-width", type=int, default=1536)
    ap.add_argument("--experts-held", type=int, default=8)
    ap.add_argument("--top-k", type=int, default=4)
    ap.add_argument("--only", choices=("attention", "experts", "routed", "layer", "latent",
                                       "streams", "head"), default=None)
    ap.add_argument("--block", type=int, nargs="+", default=[1024, 2048, 4096],
                    help="--only head: the loss's tokens a block, a set of rows each")
    ap.add_argument("--streams", type=int, default=4, help="residual streams a token")
    ap.add_argument("--tile", type=int, nargs="+", default=[128],
                    help="--only streams: the kernels' tokens a tile, a set of rows each")
    args = ap.parse_args()

    sys.path.insert(0, ".")
    print(json.dumps({"device": jax.devices()[0].device_kind}), flush=True)
    if args.only in ("latent", "streams", "head"):      # a part's own rows, on request
        {"latent": latent, "streams": streams, "head": head}[args.only](args)
        return 0
    for part in ("attention", "experts", "routed"):
        if args.only in (None, part) or (part, args.only) == ("routed", "layer"):
            {"attention": attention, "experts": experts, "routed": routed}[part](args)
    return 0


def latent(args) -> None:
    """A latent-attention layer's kernel both ways (``--only latent --batch 1
    --seq 8192 --heads 16``): keys of 192 (128 + the 64-wide rotary part) as
    they are and zero-padded to 256 (two whole lane tiles), values of 128; fused
    and unfused backward; blocks.  Layout changes and the scale are in the time,
    as in the program."""
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    from rt1_tpu.models.lm import layers

    b, s, h, qk, dv = args.batch, args.seq, args.heads, 192, 128
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, h, 1, qk), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, qk), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, dv), jnp.bfloat16)
    scale = qk ** -0.5 * 2.0047

    def splash(blocks, fused, pad):
        sizes = sk.BlockSizes(
            block_q=blocks[0], block_kv=blocks[1], block_kv_compute=blocks[2],
            block_q_dkv=blocks[0], block_kv_dkv=blocks[1], block_kv_dkv_compute=blocks[1],
            block_q_dq=None if fused else blocks[0], block_kv_dq=None if fused else blocks[1],
            use_fused_bwd_kernel=fused)

        def fn(q, k, v):
            kernel = sk.make_splash_mha(
                sm.MultiHeadMask([sm.CausalMask((s, s))] * h), block_sizes=sizes,
                head_shards=1, q_seq_shards=1)
            qh = (q.astype(jnp.float32) * scale).astype(q.dtype).reshape(b, s, h, qk)
            if pad:
                widen = ((0, 0), (0, 0), (0, 0), (0, pad - qk))
                qh, k = jnp.pad(qh, widen), jnp.pad(k, widen)
            out = jax.vmap(kernel)(*(x.transpose(0, 2, 1, 3) for x in (qh, k, v)))
            return out.transpose(0, 2, 1, 3).reshape(b, s, h, 1, dv)
        return fn

    rows = [({"latent": "program"}, lambda q, k, v: layers.splash_attention(q, k, v, scale))]
    for pad in (0, 256):
        for fused in (True, False):
            for blocks in ((1024, 1024, 512), (1024, 1024, 1024), (512, 512, 512),
                           (1024, 2048, 512), (2048, 1024, 512)):
                rows.append(({"latent": "splash", "keys": pad or qk, "values": dv, "fused": fused,
                              "blocks": blocks}, splash(blocks, fused, pad)))
    rows.append(({"latent": "program", "again": "the chip's clock at the end of the sweep"},
                 rows[0][1]))
    reference = None
    for row, fn in rows:
        try:
            step = both_ways(fn)
            grads = step(q, k, v)
            reference = grads if reference is None else reference
            row["dq_gap"] = float(jnp.max(jnp.abs(
                grads[0].astype(jnp.float32) - reference[0].astype(jnp.float32))))
            row["fwd_bwd_ms"] = timed(step, q, k, v, repeats=20)
            row["device_ms"] = device_ms_by_op(step, q, k, v)
        except Exception as exc:  # noqa: BLE001 - a probe reports and goes on
            row["error"] = repr(exc)[:300]
        print(json.dumps(row), flush=True)


STREAM_STAGES = ("xla", "fused_mix_out", "fused_all", "fused_all_sinkhorn")


def streams(args) -> None:
    """One hyper-connection sublayer both ways around a sublayer that costs
    nothing (``--only streams --batch 1 --seq 8192 --hidden 3584 --streams 4``):
    the maps (norm over the n d-wide streams, the phi product, 20 Sinkhorn
    rounds) and both mixes, a row a path and stage: ``xla`` the plain functions
    (with the streams held in bfloat16 and in float32), ``fused_mix_out`` pass
    B alone by its kernels, ``fused_all`` both passes with the rounds left to
    XLA, ``fused_all_sinkhorn`` the rounds in their kernel too (what a TPU
    runs: models/lm/streams.py), each at every ``--tile``.  Least bytes: the
    streams read once and written once each way.  Design bytes: what the
    stage's kernels move by their own count (pass A reads X and writes one
    stream's width; pass B reads X and F and writes X; back, B reads g, X, F
    and writes dX, dF; A reads X, dX, d_mixed and writes dX)."""
    from rt1_tpu.models.lm import model as lm_model
    from rt1_tpu.models.lm import streams as kernels
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import xing4_0

    lm = xing4_0.get_config().model.lm
    lm.hidden_size, lm.hc_mult = args.hidden, args.streams

    def sublayer(maps, stage):
        def fn(params, x):
            if stage in ("xla", "fused_mix_out"):
                h_pre, h_post, h_res, _ = maps.apply(params, x)
                inside = lm_model.mix_in(x, h_pre)
            else:
                inside, x, h_post, h_res, _ = maps.apply(params, x, method="enter")
            leave = lm_model.mix_out if stage == "xla" else kernels.mix_out
            return leave(x, h_res, h_post, inside)
        return fn

    for held, stage, tile in ([(jnp.bfloat16, "xla", None), (jnp.float32, "xla", None)]
                              + [(jnp.bfloat16, stage, tile) for tile in args.tile
                                 for stage in STREAM_STAGES[1:]]
                              + [(jnp.float32, STREAM_STAGES[-1], args.tile[0])]):
        if tile is not None:
            kernels.TILE_TOKENS = tile
        kernels.SINKHORN_IN_KERNEL = stage == "fused_all_sinkhorn"
        spec = LMSpec.from_config(lm, held)
        x = jax.random.normal(
            jax.random.PRNGKey(0), (args.streams, args.batch, args.seq, args.hidden), held)
        maps = lm_model.HyperConnection(spec)
        params = jax.jit(maps.init)(jax.random.PRNGKey(1), x)
        params = jax.tree.map(       # phi as a seed draws it: logits of N(0, 0.25)
            lambda a: a if a.ndim < 2 else 0.5 * jax.random.normal(
                jax.random.PRNGKey(2), a.shape) / a.shape[0] ** 0.5, params)
        fn = sublayer(maps, stage)
        step = jax.jit(jax.grad(
            lambda p, x: jnp.sum(fn(p, x).astype(jnp.float32) ** 2), argnums=(0, 1)))
        row = {"path": stage, "tile_tokens": tile, "streams": args.streams,
               "held_in": jnp.dtype(held).name, "tokens": args.batch * args.seq,
               "hidden": args.hidden}
        try:
            row["fwd_bwd_ms"] = timed(step, params, x, repeats=20)
            whole, one = x.size * x.dtype.itemsize, x.size * x.dtype.itemsize // args.streams
            row["least_bytes_ms"] = round(2 * 2 * whole / 819e9 * 1e3, 3)
            design = {"xla": None, "fused_mix_out": 5 * whole + 3 * one}.get(
                stage, 9 * whole + 5 * one)
            row["design_bytes_ms"] = design and round(design / 819e9 * 1e3, 3)
            row["device_ms"] = device_ms_by_op(step, params, x, kernels=("streams_",))
        except Exception as exc:  # noqa: BLE001 - a probe reports and goes on
            row["error"] = repr(exc)[:300]
        print(json.dumps(row), flush=True)


def attention_candidates(d: int = 64, window=None):
    """``(row, fn)`` pairs: each ``fn(q, k, v)`` takes the program's layout,
    q ``(b, s, kv heads, group, d)``, k and v ``(b, s, kv heads, d)``, so the
    transposes (and the old kernel's repeats) a step pays are in its time.  A
    splash row gives each kernel's blocks as (block_q, block_kv,
    block_kv_compute); ``dq`` null is the fused backward."""
    from jax.experimental.pallas.ops.tpu import flash_attention as fa
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_kernel as sk
    from jax.experimental.pallas.ops.tpu.splash_attention import splash_attention_mask as sm

    from rt1_tpu.models.lm import layers

    scale = d ** -0.5

    def flash_old(q, k, v, blk=1024):
        """What ``layers.py`` ran through PR 29: the library's older kernel,
        one head count, every KV head repeated for its group."""
        b, s, kvh, g, _ = q.shape
        qh = q.reshape(b, s, kvh * g, d).transpose(0, 2, 1, 3)
        kh = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3)
        vh = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)
        sizes = fa.BlockSizes(
            block_q=blk, block_k_major=blk, block_k=blk, block_b=1,
            block_q_major_dkv=blk, block_k_major_dkv=blk, block_k_dkv=blk, block_q_dkv=blk,
            block_k_major_dq=blk, block_k_dq=blk, block_q_dq=blk)
        out = fa.flash_attention(qh, kh, vh, causal=True, sm_scale=scale, block_sizes=sizes)
        return out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, d)

    def splash(fwd, dkv, dq, k_layout):
        sizes = sk.BlockSizes(
            block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2],
            block_q_dkv=dkv[0], block_kv_dkv=dkv[1], block_kv_dkv_compute=dkv[2],
            block_q_dq=dq and dq[0], block_kv_dq=dq and dq[1],
            use_fused_bwd_kernel=dq is None, k_layout=sk.QKVLayout[k_layout])

        def fn(q, k, v):
            b, s, kvh, g, _ = q.shape
            one = (sm.CausalMask((s, s)) if window is None
                   else sm.LocalMask((s, s), window_size=(window - 1, 0), offset=0))
            kernel = sk.make_splash_mha(
                sm.MultiHeadMask([one] * (kvh * g)), block_sizes=sizes,
                head_shards=1, q_seq_shards=1)
            qh = (q * scale).reshape(b, s, kvh * g, d).transpose(0, 2, 1, 3)
            out = jax.vmap(kernel)(qh, k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))
            return out.transpose(0, 2, 1, 3).reshape(b, s, kvh, g, d)
        return fn

    head, seq = "HEAD_DIM_MINOR", "SEQ_MINOR"
    program = ({"attention": "program", "window": window},
               lambda q, k, v: layers.splash_attention(q, k, v, scale, window))
    if window is not None:
        # a sliding layer: a query block visits ceil(window / block) + 1 key blocks
        # (2 at 1,024, 3 at 512, 5 at 256 for a window of 1,024), so smaller
        # blocks visit fewer keys and pay more grid steps; the old kernel has no window
        out = [program]
        for fused in (True, False):
            for blocks in ((1024, 1024, 512), (1024, 1024, 1024), (512, 512, 512),
                           (256, 256, 256), (1024, 512, 512), (512, 1024, 512),
                           (2048, 1024, 512)):
                out.append(({"attention": "splash", "window": window, "block_q": blocks[0],
                             "block_kv": blocks[1], "fwd": blocks, "dkv": blocks,
                             "dq": None if fused else blocks[:2], "fused": fused,
                             "k_layout": head},
                            splash(blocks, blocks, None if fused else blocks[:2], head)))
        for fwd, dkv in (((1024, 1024, 512), (512, 512, 512)), ((512, 512, 512), (1024, 1024, 1024)),
                         ((256, 256, 256), (512, 512, 512))):
            out.append(({"attention": "splash", "window": window, "block_q": fwd[0],
                         "block_kv": fwd[1], "fwd": fwd, "dkv": dkv, "dq": None, "fused": True,
                         "k_layout": head}, splash(fwd, dkv, None, head)))
        out.append(({"attention": "blockwise", "window": window, "block_q": 512, "block_kv": 512},
                    lambda q, k, v: layers.blockwise_attention(q, k, v, scale, 512, window)))
        out.append((dict(program[0], again="the chip's clock at the end of the sweep"), program[1]))
        return out
    out = [({"attention": "flash_old", "block_q": 1024, "block_kv": 1024}, flash_old), program]
    # one block pair for all three kernels, unfused and fused; then k's layout,
    # the inner compute block, and a query block of 2,048 (the scratch allows
    # it only with a compute block of 512)
    same = [((bq, bkv, min(bkv, 1024)), fused, head)
            for fused in (False, True) for bq in (512, 1024) for bkv in (512, 1024, 2048)]
    same += [((1024, 1024, 1024), False, seq), ((1024, 1024, 1024), True, seq),
             ((1024, 2048, 1024), True, seq),
             ((1024, 1024, 512), False, head), ((1024, 2048, 512), False, head),
             ((1024, 1024, 512), True, head), ((1024, 2048, 512), True, head),
             ((512, 512, 256), False, head),
             ((2048, 1024, 512), False, head), ((2048, 1024, 512), True, head),
             ((2048, 512, 512), False, head)]
    settings = [(blocks, blocks, None if fused else blocks[:2], layout)
                for blocks, fused, layout in same]
    # each kernel with the blocks it read fastest with in the rows above
    settings += [((1024, 1024, 512), (1024, 1024, 1024), None, head),
                 ((1024, 1024, 512), (1024, 2048, 1024), None, head),
                 ((1024, 1024, 512), (2048, 1024, 512), None, head),
                 ((1024, 1024, 256), (1024, 1024, 1024), None, head),
                 ((1024, 1024, 512), (1024, 1024, 1024), (1024, 1024), head)]
    for fwd, dkv, dq, k_layout in settings:
        out.append(({"attention": "splash", "block_q": fwd[0], "block_kv": fwd[1],
                     "fwd": fwd, "dkv": dkv, "dq": dq, "fused": dq is None,
                     "k_layout": k_layout}, splash(fwd, dkv, dq, k_layout)))
    out.append(({"attention": "blockwise", "block_q": 512, "block_kv": 512},
                lambda q, k, v: layers.blockwise_attention(q, k, v, scale, 512)))
    out.append(({"attention": "flash_old", "block_q": 1024, "block_kv": 1024,
                 "again": "the chip's clock at the end of the sweep"}, flash_old))
    return out


def attention_inputs(seq: int, d: int = 64, b: int = 2, kvh: int = 8, g: int = 4):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, seq, kvh, g, d), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, seq, kvh, d), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, seq, kvh, d), jnp.bfloat16)
    return q, k, v


# tokens, hidden, vocabulary rows held: lfm2's, mellum's and xing's cells
HEAD_SHAPES = ((16384, 2048, 8192), (16384, 2304, 24576), (8192, 3584, 16384))


def checkpointed_loss(x, head, targets, block):
    """``next_token_loss`` as it was through PR 36 (the probe's baseline): each
    block under ``jax.checkpoint``, so its logits and log-sum-exp are made in the
    forward loop and again in the transposed one."""
    from rt1_tpu.models.lm.spec import IGNORE

    @jax.checkpoint
    def one(args):
        xb, tb = args
        logits = jnp.einsum("td,vd->tv", xb, head, preferred_element_type=jnp.float32)
        picked = jnp.take_along_axis(logits, jnp.maximum(tb, 0)[:, None], axis=-1)[:, 0]
        ce = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(jnp.where(tb != IGNORE, ce, 0.0))

    total = jnp.sum(lax.map(one, (x.reshape(-1, block, x.shape[-1]),
                                  targets.reshape(-1, block))))
    return total / jnp.maximum(jnp.sum(targets != IGNORE), 1)


def head(args) -> None:
    """The output head and the loss both ways (``--only head``), a row a shape,
    path and block: ``checkpointed`` (four products a block in two loops) and
    ``grad_in_forward`` (``model.next_token_loss``: three products in one loop)
    with ``d_head`` summed over the blocks in float32 (what ships) and in
    bfloat16; the loss's cotangent 1 (the trunk's pass: the scaling folds away)
    and, for what ships, 0.3 (a prediction module's: a pass over ``d_x`` and
    ``d_head``).  ``roof_ms``: three products at the MXU's peak.  ``temp_bytes``:
    the compiled program's temporaries."""
    from rt1_tpu.models.lm import model as lm_model
    from rt1_tpu.models.lm.spec import IGNORE

    def with_accumulator(accumulator):
        fn = jax.custom_vjp(lm_model.next_token_loss_plain)
        fn.defvjp(functools.partial(lm_model._loss_and_gradients, accumulator=accumulator),
                  lm_model._scaled_gradients)
        return fn

    for tokens, d, rows in HEAD_SHAPES:
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (1, tokens, d), jnp.bfloat16)
        table = (0.02 * jax.random.normal(jax.random.fold_in(key, 1), (rows, d))
                 ).astype(jnp.bfloat16)
        targets = jax.random.randint(jax.random.fold_in(key, 2), (1, tokens), 0, rows)
        targets = jnp.where(jnp.arange(tokens)[None] < 0.85 * tokens, targets, IGNORE)
        for block in args.block:
            lm_model.LOSS_BLOCK = block
            for path, accumulator, weight, loss in (
                    ("checkpointed", None, 1.0,
                     functools.partial(checkpointed_loss, block=block)),
                    ("grad_in_forward", "float32", 1.0, lm_model.next_token_loss),
                    ("grad_in_forward", "float32", 0.3, lm_model.next_token_loss),
                    ("grad_in_forward", "bfloat16", 1.0, with_accumulator(jnp.bfloat16))):
                row = {"path": path, "accumulator": accumulator, "cotangent": weight,
                       "tokens": tokens, "hidden": d, "rows": rows, "block": block,
                       "roof_ms": round(3 * 2 * tokens * d * rows / 197e12 * 1e3, 3)}
                try:
                    step = jax.jit(jax.value_and_grad(
                        lambda x, table, loss=loss, weight=weight:
                        weight * loss(x, table, targets), argnums=(0, 1)))
                    row["temp_bytes"] = step.lower(
                        x, table).compile().memory_analysis().temp_size_in_bytes
                    row["fwd_bwd_ms"] = round(timed(step, x, table, repeats=20), 3)
                except Exception as exc:  # noqa: BLE001 - a probe reports and goes on
                    row["error"] = repr(exc)[:300]
                print(json.dumps(row), flush=True)


def both_ways(fn):
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)))


def device_ms_by_op(fn, *args, runs=3, kernels=("splash", "flash")):
    """Device time a run of each op of ``fn``'s program, from a profile of
    ``runs`` runs: the Pallas kernels under their own names, all else summed
    as ``around`` (transposes, repeats, the backward's row sums)."""
    import collections
    import shutil
    import tempfile

    from benchmarks.trace import xplane

    logdir = tempfile.mkdtemp(prefix="lm_kernel_probe_")
    try:
        jax.profiler.start_trace(logdir)
        for _ in range(runs):
            out = fn(*args)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        total = collections.Counter()
        for _, line, name, _, duration_ns in xplane.rows_from_xplane(xplane.find_xplane(logdir)):
            if line == "XLA Ops":
                name = name.split(".")[0]
                kernel = any(word in name for word in kernels)
                total[name if kernel else "around"] += duration_ns
        return {name: round(ns / runs / 1e6, 3) for name, ns in sorted(total.items())}
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def attention(args) -> None:
    """One attention layer both ways, ``--batch`` x ``--seq`` positions,
    ``--heads`` over ``--kv-heads`` KV heads of ``--head-dim`` (the defaults: 2 x
    8,192, 32 over 8 of 64): the library's splash kernel over its block sizes,
    fused and unfused backward and k's layout, the older flash kernel the
    program ran through PR 29, and what ``layers.splash_attention`` picks;
    under ``--window`` a sliding layer's rows."""
    q, k, v = attention_inputs(args.seq, args.head_dim, args.batch, args.kv_heads,
                               args.heads // args.kv_heads)
    reference = None
    for row, fn in attention_candidates(args.head_dim, args.window):
        try:
            step = both_ways(fn)
            grads = step(q, k, v)
            if reference is None:
                reference = grads
            # the candidates are one function: the largest gap of dq to the first row's
            row["dq_gap"] = float(jnp.max(jnp.abs(
                grads[0].astype(jnp.float32) - reference[0].astype(jnp.float32))))
            row["fwd_bwd_ms"] = timed(step, q, k, v, repeats=20)
            row["device_ms"] = device_ms_by_op(step, q, k, v)
        except Exception as exc:  # noqa: BLE001 - a probe reports and goes on
            row["error"] = repr(exc)[:300]
        print(json.dumps(row), flush=True)


def experts(args) -> None:
    from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

    from rt1_tpu.models.lm import moe

    key = jax.random.PRNGKey(0)
    held, dm, f = args.experts_held, args.hidden, args.expert_width
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

        # the program's rule (a tiling a product), then one tiling for both
        # products: whole and half sides, sides of 1,024 with a masked remainder,
        # lane multiples that divide both widths where there are any
        both = sorted({t for t in range(128, 1025, 128) if dm % t == 0 and (2 * f) % t == 0
                       and f % t == 0})
        candidates = [("ragged_dot", None), ("megablox", "rule")] + [("megablox", t) for t in [
            (512, 1024, 1024), (512, 512, 512), (256, 1024, 1024), (1024, 1024, 1024),
            (512, dm, f), (128, 128, 128)] + [(512, t, t) for t in both]]
        for impl, tiling in candidates:
            if impl == "ragged_dot":
                product = lambda a, w, gs: lax.ragged_dot(  # noqa: E731
                    a, w, gs, preferred_element_type=a.dtype)
            elif tiling == "rule":
                product = lambda a, w, gs: megablox.gmm(  # noqa: E731
                    a, w, gs, a.dtype, moe.megablox_tiling(*w.shape[1:]))
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


def routed(args) -> None:
    """``--batch`` x ``--seq`` tokens, top-``--top-k`` of 64 experts,
    ``--experts-held`` held, 82 % of the tokens live, widths ``--hidden`` and
    ``--expert-width`` (the defaults: 2 x 8,192, top-4, 8 held, 2048 and 1536:
    the lfm2 token cell's routed layer)."""
    import functools

    import numpy as np

    from rt1_tpu.models.lm import moe
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import lfm2_moe

    lm = lfm2_moe.get_config().model.lm
    lm.hidden_size, lm.moe_intermediate_size = args.hidden, args.expert_width
    lm.num_experts_per_tok, lm.experts_held = args.top_k, (0, args.experts_held)
    spec = LMSpec.from_config(lm, jnp.bfloat16)
    tokens, k = args.batch * args.seq, spec.experts_per_tok
    d, f = spec.hidden_size, spec.moe_intermediate_size
    held, num_experts = spec.experts_held[1], spec.num_experts
    n = tokens * k
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (tokens, d), jnp.bfloat16)
    scores = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, num_experts))
    weights, idx = lax.top_k(scores, k)
    live = jnp.arange(tokens) % args.seq < int(0.82 * args.seq)
    is_held = (idx < held) & live[:, None]        # experts_held starts at 0 in the config
    sort_key = jnp.where(is_held, idx, held).reshape(n)
    order = jnp.argsort(sort_key, stable=True).astype(jnp.int32)
    total = int(jnp.sum(is_held))

    def report(name, rows, fn, *operands, **fields):
        row = dict({"routed": name, "rows": rows, "held_rows": total}, **fields)
        try:
            print(json.dumps(dict(row, ms=timed(jax.jit(fn), *operands))), flush=True)
        except Exception as exc:  # noqa: BLE001
            print(json.dumps(dict(row, error=repr(exc)[:300])), flush=True)

    capacity = moe.row_capacity(n, held, num_experts)
    d_out = jax.random.normal(jax.random.fold_in(key, 4), (tokens, d), jnp.bfloat16)

    if args.only != "layer":
        # the index: what each way needs before a row moves
        report("argsort_keys", n, lambda a: jnp.argsort(a, stable=True), sort_key)
        report("bincount", n, lambda a: jnp.bincount(a, length=held + 1), sort_key)
        report("count_by_compare", n,
               lambda a: jnp.sum(a[:, None] == jnp.arange(held + 1)[None, :], axis=0), sort_key)
        report("inverse_permutation", n, lambda o: jnp.zeros((n,), jnp.int32).at[o].set(
            jnp.arange(n, dtype=jnp.int32), unique_indices=True), order)

        for rows in (capacity, n):
            def compaction(is_held, idx, rows=rows):
                flat = is_held.reshape(n)
                slot = jnp.nonzero(flat, size=rows, fill_value=n - 1)[0].astype(jnp.int32)
                keys = jnp.where(jnp.arange(rows) < jnp.sum(flat), idx.reshape(n)[slot], held)
                by_expert = jnp.argsort(keys, stable=True).astype(jnp.int32)
                back = jnp.zeros((rows,), jnp.int32).at[by_expert].set(
                    jnp.arange(rows, dtype=jnp.int32), unique_indices=True)
                return slot[by_expert], back

            report("token_major_compaction_and_sort", rows, compaction, is_held, idx)

            valid = jnp.arange(rows) < total
            by_expert = order[:rows] // k                       # tokens in expert order
            by_token = jnp.sort(jnp.where(valid, by_expert, tokens - 1))     # and in token order
            position = jnp.zeros((n,), jnp.int32).at[order].set(
                jnp.arange(n, dtype=jnp.int32), unique_indices=True)
            position = jnp.where(is_held, jnp.minimum(position, rows - 1).reshape(tokens, k), 0)
            out_rows = jax.random.normal(jax.random.fold_in(key, 2), (rows, d), jnp.bfloat16)
            row_weights = jax.random.uniform(jax.random.fold_in(key, 3), (rows,))

            report("gather_rows_of_tokens", rows,
                   lambda x, t, v: jnp.where(v[:, None], x[t], 0), x, by_expert, valid)

            def slot_gather_combine(out_rows, weights, position, is_held):
                picked = jnp.where(is_held[..., None], out_rows[position], 0)
                return jnp.sum(picked.astype(jnp.float32) * weights[..., None], axis=1).astype(
                    out_rows.dtype)

            def scatter_combine(out_rows, row_weights, token, valid, sorted_=False, drop=False):
                weighed = jnp.where(valid[:, None], out_rows, 0).astype(jnp.float32) \
                    * row_weights[:, None]
                if drop:        # the rows after the last group go nowhere
                    token = jnp.where(valid, token, tokens)
                return jnp.zeros((tokens, d), jnp.float32).at[token].add(
                    weighed, indices_are_sorted=sorted_, mode="drop").astype(out_rows.dtype)

            report("combine_slot_gather", rows, slot_gather_combine,
                   out_rows, weights, position, is_held)
            report("combine_scatter_add_expert_order", rows, scatter_combine,
                   out_rows, row_weights, by_expert, valid)
            report("combine_scatter_add_expert_order_invalid_dropped", rows,
                   functools.partial(scatter_combine, drop=True),
                   out_rows, row_weights, by_expert, valid)
            report("combine_scatter_add_bfloat16_rows", rows,
                   lambda r, t: jnp.zeros((tokens, d), r.dtype).at[t].add(r), out_rows, by_expert)
            report("combine_scatter_add_token_order_sorted", rows,
                   functools.partial(scatter_combine, sorted_=True),
                   out_rows, row_weights, by_token, valid)
            # dispatch's way back: a token's rows added up in float32
            report("dispatch_back_slot_gather", rows, lambda r, p, h: jnp.sum(
                jnp.where(h[..., None], r[p], 0).astype(jnp.float32), axis=1).astype(r.dtype),
                out_rows, position, is_held)
            report("dispatch_back_scatter_add", rows, lambda r, t, v: jnp.zeros(
                (tokens, d), jnp.float32).at[t].add(
                    jnp.where(v[:, None], r, 0).astype(jnp.float32)).astype(r.dtype),
                out_rows, by_expert, valid)
            report("permute_rows", rows, lambda r, p: r[p], out_rows,
                   jax.random.permutation(key, rows))

            def combine_back(d_out, out_rows, row_weights, token, valid, gathered=jnp.float32):
                d_weighed = d_out.astype(gathered)[token].astype(jnp.float32)
                kept = jnp.where(valid[:, None], out_rows, 0).astype(jnp.float32)
                return (jnp.where(valid[:, None], d_weighed * row_weights[:, None], 0).astype(
                    out_rows.dtype), jnp.sum(d_weighed * kept, axis=1))

            report("combine_back_gather", rows, combine_back,
                   d_out, out_rows, row_weights, by_expert, valid)
            report("combine_back_gather_bfloat16_rows", rows,
                   functools.partial(combine_back, gathered=jnp.bfloat16),
                   d_out, out_rows, row_weights, by_expert, valid)
            if rows != capacity:
                continue
            report("scatter_add_sorted_by_hand", rows,
                   lambda r, t, v: sum_sorted_by_hand(r, None, t, v, tokens).astype(r.dtype),
                   out_rows, by_expert, valid)
            # each move as a loop over the tiles that hold rows, the trip count an
            # argument as in a step: the rows the routing holds, and a full buffer
            for tile in (512, 1024, 2048):
                for moved in (total, rows):
                    loop = {"tile": tile, "rows_moved": moved}
                    moved = jnp.int32(moved)
                    report("gather_rows_of_tokens_loop", rows,
                           functools.partial(gather_loop, tile), x, by_expert, moved, **loop)
                    report("dispatch_back_scatter_add_loop", rows,
                           lambda r, t, m, tile=tile: sum_loop(
                               tile, r, None, t, m, tokens).astype(r.dtype),
                           out_rows, by_expert, moved, **loop)
                    report("combine_scatter_add_loop", rows,
                           lambda r, w, t, m, tile=tile: sum_loop(
                               tile, r, w, t, m, tokens).astype(r.dtype),
                           out_rows, row_weights, by_expert, moved, **loop)
                    if tile == 1024:
                        report("dispatch_back_scatter_add_loop_sorted_in_the_trip", rows,
                               lambda r, t, m, tile=tile: sum_loop(
                                   tile, r, None, t, m, tokens, sort=True).astype(r.dtype),
                               out_rows, by_expert, moved, **loop)

    # the layer forward and backward: as the program runs it at both capacities,
    # then with each alternative in the place of the row path's moves
    w1 = jax.random.normal(jax.random.fold_in(key, 5), (held, d, f)) * 0.02
    w3 = jax.random.normal(jax.random.fold_in(key, 6), (held, d, f)) * 0.02
    w2 = jax.random.normal(jax.random.fold_in(key, 7), (held, f, d)) * 0.02
    program = moe._row_dispatch, moe._row_combine
    for rows, moves, (dispatch, combine) in [
            (capacity, "program", program), (n, "program", program),
            (capacity, "combine_back_by_jax", (moe._row_dispatch, combine_transposed_by_jax)),
            (capacity, "sums_sorted_by_hand", sorted_by_hand_moves()),
    ] + [(capacity, f"loops_of_{tile}", loop_moves(tile)) for tile in (512, 1024, 2048)]:
        moe._row_dispatch, moe._row_combine = dispatch, combine    # read when a path is traced
        moe._forward.clear_cache()          # and a path is traced once a capacity
        moe._backward.clear_cache()

        def layer(x, weights, w1, w3, w2, rows=rows):
            out, _, fell_back = moe.held_experts_ffn(
                x, idx, weights, live, w1, w3, w2, spec, rows)
            return jnp.sum((out * d_out).astype(jnp.float32)), fell_back

        def forward(*a):
            return layer(*a)[0]

        report("layer_forward", rows, forward, x, weights, w1, w3, w2, moves=moves)
        report("layer_both_ways", rows,
               jax.grad(layer, argnums=(0, 1, 2, 3, 4), has_aux=True), x, weights, w1, w3, w2,
               moves=moves)
    moe._row_dispatch, moe._row_combine = program
    moe._forward.clear_cache()
    moe._backward.clear_cache()
    print(json.dumps({"routed": "fallback_at_capacity", "value": np.asarray(
        moe.held_experts_ffn(x, idx, weights, live, w1, w3, w2, spec, capacity)[2]).item()}),
        flush=True)


# What PR 32 tried for the row path's moves and did not take.  ``index`` is
# ``moe._row_index``'s: (token, slot, valid) of each row of the buffer.

def combine_transposed_by_jax(out_rows, weights, index):
    """``moe.token_sums`` with the way back jax derives: float32 rows gathered."""
    from rt1_tpu.models.lm import moe

    return moe.token_sums.__wrapped__(out_rows, weights, *index)


def sum_sorted_by_hand(rows, scale, token, valid, tokens):
    """``out[t]`` = the float32 sum of ``rows[r] * scale[r]`` over the valid
    rows of token t, the compiler's rewrite of a scatter-add done by hand: one
    sort of the rows by token, the rows permuted as they are (bfloat16), and a
    scatter-add told that its indices are sorted."""
    at, by_token = lax.sort(
        (jnp.where(valid, token, tokens), jnp.arange(token.shape[0], dtype=jnp.int32)),
        num_keys=1)
    kept = jnp.where((at < tokens)[:, None], rows[by_token], 0).astype(jnp.float32)
    if scale is not None:
        kept = kept * scale[by_token][:, None]
    return jnp.zeros((tokens, rows.shape[1]), jnp.float32).at[at].add(
        kept, indices_are_sorted=True, mode="drop")


def sum_loop(tile, rows, scale, token, moved, tokens, sort=False):
    """The same sum over the first ``moved`` rows, ``tile`` rows a trip of a
    loop whose carry is the (tokens, d) accumulator; ``sort``: a tile's rows
    in the order of their tokens."""
    def trip(i, out):
        at = lax.dynamic_slice_in_dim(token, i * tile, tile)
        row = i * tile + jnp.arange(tile)
        kept = lax.dynamic_slice_in_dim(rows, i * tile, tile)
        weight = None if scale is None else lax.dynamic_slice_in_dim(scale, i * tile, tile)
        if sort:
            at, by_token = lax.sort((at, jnp.arange(tile)), num_keys=1)
            row, kept = row[by_token], kept[by_token]
            weight = None if weight is None else weight[by_token]
        kept = jnp.where((row < moved)[:, None], kept, 0).astype(jnp.float32)
        if weight is not None:
            kept = kept * weight[:, None]
        return out.at[at].add(kept, indices_are_sorted=sort)

    return lax.fori_loop(0, (moved + tile - 1) // tile, trip,
                         jnp.zeros((tokens, rows.shape[1]), jnp.float32))


def gather_loop(tile, x, token, moved):
    """``rows[r] = x[token[r]]`` for the first ``moved`` rows, zero after
    them, ``tile`` rows a trip into a buffer that starts as zeros."""
    def trip(i, rows):
        valid = i * tile + jnp.arange(tile) < moved
        picked = jnp.where(valid[:, None], x[lax.dynamic_slice_in_dim(token, i * tile, tile)], 0)
        return lax.dynamic_update_slice_in_dim(rows, picked, i * tile, 0)

    return lax.fori_loop(0, (moved + tile - 1) // tile, trip,
                         jnp.zeros((token.shape[0], x.shape[1]), x.dtype))


def _moves(sum_per_token, gather):
    """(dispatch, combine) for ``moe._path``'s row path from a sum per token
    ``(rows, scale, token, valid, tokens)`` and a gather ``(x, token, valid)``:
    both ways of both written out, the combine's way back the program's."""
    @jax.custom_vjp
    def dispatch(x, index):
        return gather(x, index[0], index[2])

    def dispatch_back(res, d_rows):
        (token, _, valid), tokens = res
        return sum_per_token(d_rows, None, token, valid, tokens).astype(d_rows.dtype), None

    dispatch.defvjp(lambda x, index: (dispatch(x, index), (index, x.shape[0])), dispatch_back)

    @jax.custom_vjp
    def combine(out_rows, weights, index):
        token, slot, valid = index
        weight = weights.reshape(-1).at[slot].get(unique_indices=True)
        return sum_per_token(out_rows, weight, token, valid, weights.shape[0]).astype(
            out_rows.dtype)

    def combine_back(res, d_out):
        from rt1_tpu.models.lm import moe

        out_rows, weights, index = res
        return moe._token_sums_bwd((out_rows, weights, index), d_out)[:2] + (None,)

    combine.defvjp(lambda r, w, index: (combine(r, w, index), (r, w, index)), combine_back)
    return dispatch, combine


def sorted_by_hand_moves():
    """Both scatter-adds sorted by hand, the gather the program's."""
    return _moves(sum_sorted_by_hand,
                  lambda x, token, valid: jnp.where(valid[:, None], x[token], 0))


def loop_moves(tile):
    """ISSUE 32's design: the gather and both scatter-adds ``tile`` rows a
    trip, the trips the tiles that hold rows."""
    def held(valid):
        return jnp.sum(valid, dtype=jnp.int32)

    return _moves(
        lambda rows, scale, token, valid, tokens: sum_loop(
            tile, rows, scale, token, held(valid), tokens),
        lambda x, token, valid: gather_loop(tile, x, token, held(valid)))


if __name__ == "__main__":
    sys.exit(main())

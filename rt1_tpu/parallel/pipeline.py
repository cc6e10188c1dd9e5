"""Pipeline parallelism: GPipe-style microbatch rotation over a ``stage`` axis.

Beyond reference parity (SURVEY.md §2.6: "Pipeline parallelism: No") — the
reference never shards layers. Here the decoder's layer stack can be
partitioned over the mesh's ``stage`` axis, with microbatches flowing
stage-to-stage over ICI via `jax.lax.ppermute` inside a `shard_map`:

  tick t:  stage 0 ingests microbatch t;  stage s computes the microbatch it
           received from stage s-1 last tick;  after M + S - 1 ticks every
           microbatch has crossed all S stages.

This is the collective-pipelining recipe (one `lax.scan` over ticks, a rotate
per tick) rather than a hand-scheduled 1F1B: autodiff through the scan +
ppermute gives the backward pipeline for free, and XLA overlaps the
(tiny, point-to-point) rotate with each stage's compute. Bubble fraction is
the GPipe (S-1)/(M+S-1); pick ``num_microbatches`` ≥ 4·S to amortize.

The unit here is a *stage function* ``stage_fn(stage_params, x) -> y`` with
``y.shape == x.shape`` (true for transformer blocks: (b, s, d_model) in/out).
``stacked_params`` holds every stage's parameters stacked on a leading axis
of size S·(layers-per-stage); `shard_map` splits that axis across stages, and
each stage folds its own chunk with an inner `lax.scan` (layers are
sequential within a stage).

`pp_causal_transformer_apply` applies a full `CausalTransformer`
(models/transformer.py) this way from its standard Flax params — embedding
and head are computed replicated (they are <2% of FLOPs); only the layer
stack is pipelined. Exactness vs the sequential module is pinned by
tests/test_pipeline.py.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_layer_params(params: Any, num_layers: int, prefix: str = "layer_") -> Any:
    """Stack `CausalTransformer` per-layer param subtrees on a leading axis.

    Takes the module's standard params dict ({'layer_0': {...}, ...}) and
    returns a single pytree whose leaves have a leading ``num_layers`` axis —
    the layout `pipeline_apply` shards over ``stage``.
    """
    layers = [params[f"{prefix}{i}"] for i in range(num_layers)]
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layer_params(stacked: Any, prefix: str = "layer_") -> dict:
    """Inverse of `stack_layer_params` (for porting params back)."""
    num_layers = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    return {
        f"{prefix}{i}": jax.tree.map(lambda x, i=i: x[i], stacked)
        for i in range(num_layers)
    }


def pipeline_apply(
    stage_fn: Callable[..., jnp.ndarray],
    stacked_params: Any,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    num_microbatches: int,
    stage_axis: str = "stage",
    data_axis: Optional[str] = "data",
    pass_context: bool = False,
) -> jnp.ndarray:
    """Run ``x`` through S pipelined stages; returns the final activations.

    * ``stacked_params`` leaves: (L, ...) with L divisible by S; stage s owns
      the [s·L/S, (s+1)·L/S) slice and scans `stage_fn` over it.
    * ``x``: (b, ...) activations. With a >1 ``data`` axis the batch dim is
      sharded over it (each data row runs an independent pipeline down its
      own stage column). The per-shard batch must divide `num_microbatches`.
    * Output == sequentially applying all L layers (exact; no renorm).
    * ``pass_context``: call ``stage_fn(p, x, layer_idx, microbatch_idx)``
      instead of ``stage_fn(p, x)`` — the hook that lets training fold a
      dropout rng per (layer, microbatch). Both indices are traced int32
      scalars (global layer index; microbatch index clamped to [0, M) on
      bubble ticks, whose outputs are discarded).

    Differentiable: the backward pass pipelines in reverse through the same
    scan/ppermute structure via autodiff.
    """
    M = num_microbatches
    S = mesh.shape[stage_axis]
    if S == 1:  # degenerate: plain scan over the stack, no collectives
        L1 = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]

        def fold(x, p_i):
            p, i = p_i
            y = stage_fn(p, x, i, jnp.zeros((), jnp.int32)) if pass_context \
                else stage_fn(p, x)
            return y, None

        out, _ = jax.lax.scan(
            fold, x, (stacked_params, jnp.arange(L1, dtype=jnp.int32))
        )
        return out

    L = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
    if L % S != 0:
        raise ValueError(f"{L} stacked layers not divisible by {S} stages")
    batch_spec = (
        P(data_axis)
        if data_axis and mesh.shape.get(data_axis, 1) > 1
        else P()
    )

    def local(params_chunk, x_local):
        # params_chunk leaves: (L/S, ...) — this stage's layers.
        b_local = x_local.shape[0]
        if b_local % M != 0:
            raise ValueError(
                f"per-shard batch {b_local} not divisible by "
                f"num_microbatches={M}"
            )
        mb = b_local // M
        s_idx = jax.lax.axis_index(stage_axis)
        feed = x_local.reshape((M, mb) + x_local.shape[1:])
        # Ticks M..M+S-2 feed no new microbatch; zeros keep shapes static.
        pad = jnp.zeros((S - 1,) + feed.shape[1:], feed.dtype)
        feed = jnp.concatenate([feed, pad], axis=0)  # (T, mb, ...)

        layers_per_stage = L // S

        def run_stage(x_in, m_idx):
            def fold(x, p_l):
                p, l = p_l
                if pass_context:
                    y = stage_fn(p, x, s_idx * layers_per_stage + l, m_idx)
                else:
                    y = stage_fn(p, x)
                return y, None

            out, _ = jax.lax.scan(
                fold, x_in,
                (params_chunk, jnp.arange(layers_per_stage, dtype=jnp.int32)),
            )
            return out

        rotate = [(i, (i + 1) % S) for i in range(S)]

        def tick(prev_y, x_t_and_t):
            x_t, t = x_t_and_t
            incoming = jax.lax.ppermute(prev_y, stage_axis, rotate)
            x_in = jnp.where(s_idx == 0, x_t, incoming)
            # Stage s processes microbatch t - s at tick t (clamped on the
            # warm-up/drain bubbles, whose outputs never leave the mask).
            m_idx = jnp.clip(t - s_idx, 0, M - 1).astype(jnp.int32)
            y = run_stage(x_in, m_idx)
            return y, y

        y0 = jnp.zeros(feed.shape[1:], feed.dtype)
        ticks = jnp.arange(feed.shape[0], dtype=jnp.int32)
        _, ys = jax.lax.scan(tick, y0, (feed, ticks))  # (T, mb, ...)
        # Microbatch m exits the last stage at tick S-1+m. Replicate the
        # last stage's results to every stage with a masked psum so the
        # caller sees identical activations on all shards.
        out = ys[S - 1:]                      # (M, mb, ...)
        out = out * (s_idx == S - 1).astype(out.dtype)
        out = jax.lax.psum(out, stage_axis)
        return out.reshape((b_local,) + x_local.shape[1:])

    # Pre-reshard placement comes from the plan (plan.PIPELINE_STACK_RULES),
    # not inline special-casing: the stacked tree is pinned replicated before
    # the P(stage) reshard — the XLA:CPU miscompile guard documented there,
    # pinned by tests/test_pipeline.py::test_pp_train_step_equals_dense.
    from rt1_tpu.parallel import plan as planlib

    stacked_params = planlib.pipeline_stack_placement(stacked_params, mesh)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(P(stage_axis), batch_spec),
        out_specs=batch_spec,
        check_vma=False,
    )(stacked_params, x)


def pp_causal_transformer_apply(
    transformer: Any,
    params: Any,
    inputs: jnp.ndarray,
    *,
    mesh: Mesh,
    num_microbatches: int,
    attention_mask: Optional[jnp.ndarray] = None,
    stage_axis: str = "stage",
    train: bool = False,
    dropout_rng: Optional[jax.Array] = None,
) -> jnp.ndarray:
    """`CausalTransformer.__call__` with the layer stack pipelined.

    ``transformer`` is the `CausalTransformer` module instance (for its
    hyperparameters), ``params`` its standard Flax params. Embedding, the
    positional table, and the vocab head run replicated; the N pre-norm
    blocks run under `pipeline_apply` (the sequential module has no dropout
    outside the blocks, so this split is train-exact too).

    Training: pass ``train=True`` and a ``dropout_rng``; each (layer,
    microbatch) folds its indices into the rng, so masks are independent
    across layers and microbatches. This matches the sequential module's
    dropout *distribution* (every activation element keeps an independent
    Bernoulli mask) but not its bitstream — with `dropout_rate > 0` the
    pipelined and sequential losses are equal in expectation, not bitwise;
    exactness tests must set `dropout_rate = 0`.
    """
    from rt1_tpu.models.transformer import TransformerLayer

    b, s, _ = inputs.shape
    p = params["params"] if "params" in params else params
    x = inputs @ p["token_emb"]["kernel"] + p["token_emb"]["bias"]
    x = x + p["position_emb"]["embedding"][None, :s, :]

    if transformer.attention_impl != "dense":
        # The pallas kernel inside a pipelined stage would nest under this
        # shard_map; unsupported.
        raise ValueError(
            "pipeline parallelism supports attention_impl='dense' only, "
            f"got {transformer.attention_impl!r}"
        )
    use_dropout = train and transformer.dropout_rate > 0
    if use_dropout and dropout_rng is None:
        raise ValueError(
            "train=True with dropout_rate > 0 requires dropout_rng"
        )
    from flax import linen as _nn

    # Honor the module's remat flag on the pipelined path too (otherwise
    # remat=True + stage>1 would silently skip decoder rematerialization).
    # static_argnums counts self as 0: (self, x, mask, train) → train=3.
    layer_cls = (
        _nn.remat(TransformerLayer, static_argnums=(3,))
        if getattr(transformer, "remat", False)
        else TransformerLayer
    )
    layer = layer_cls(
        key_dim=transformer.key_dim,
        num_heads=transformer.num_heads,
        d_model=transformer.d_model,
        dropout_rate=transformer.dropout_rate,
        dtype=transformer.dtype,
        # Detach from any enclosing module context: this is a stateless
        # stage template applied with explicit params, not a submodule
        # (RT1Policy calls this helper from inside its own apply).
        parent=None,
    )

    # Inside the shard_map each data row is a different slice of the batch,
    # so the mask must differ per data shard too (folding only layer/micro
    # would reuse one mask across all data rows, shrinking effective dropout
    # noise as DP grows). axis_index is only bindable under the shard_map,
    # i.e. on the S > 1 path; the degenerate S == 1 path runs unsharded.
    fold_data = (
        mesh.shape[stage_axis] > 1 and mesh.shape.get("data", 1) > 1
    )

    def stage_fn(layer_params, h, layer_idx, mb_idx):
        rngs = None
        if use_dropout:
            r = jax.random.fold_in(dropout_rng, layer_idx)
            r = jax.random.fold_in(r, mb_idx)
            if fold_data:
                r = jax.random.fold_in(r, jax.lax.axis_index("data"))
            rngs = {"dropout": r}
        # Positional (x, mask, train): static_argnums on the remat wrap
        # refers to positional indices.
        out, _ = layer.apply(
            {"params": layer_params}, h, attention_mask, train, rngs=rngs
        )
        return out

    stacked = stack_layer_params(p, transformer.num_layers)
    x = pipeline_apply(
        stage_fn,
        stacked,
        x,
        mesh=mesh,
        num_microbatches=num_microbatches,
        stage_axis=stage_axis,
        pass_context=True,
    )
    return x @ p["output_tokens"]["kernel"] + p["output_tokens"]["bias"]

"""One declarative sharding plan: name-pattern → PartitionSpec for every
RT-1 parameter group, resolved once and consumed identically by train
(`trainer/train.py`), eval restore (`eval/restore.py`), and serve
(`serve/engine.py`).

Before this module, parallelism was piecemeal: two hand-written rule lists in
`parallel/sharding.py` consumed only by the trainer, an inline XLA:CPU
replication workaround in `parallel/pipeline.py`, and ad-hoc `device_put`s on
the eval/serve path. Here the whole layout is ONE ordered list of
``(path-regex, PartitionSpec)`` rules in the GSPMD annotation-driven style
(Xu et al., 2021): annotate where each weight lives, let the partitioner
propagate everything else. The axes the specs name are the
``('data', 'stage', 'fsdp', 'model')`` mesh of `parallel/mesh.py`:

* ``fsdp`` — ZeRO-3 weight sharding. The batch is sharded over it together
  with ``data``; weight matrices shard one dimension over it, so GSPMD emits
  per-layer all-gathers at use sites and reduce-scatters for gradients.
* ``model`` — tensor parallelism (attention heads / FFN columns; the
  `models/lm` family's experts).

Every spec is written against all axes; size-1 axes are free, so the same plan
degenerates to pure DP on a `dp=N` mesh at zero cost. Kernel layouts are Flax
Dense ``(in, out)``, which mirrors SNIPPETS.md [3]'s torch ``(out, in)``
``('tp','fsdp')`` map transposed: column-parallel kernels are
``P('fsdp', 'model')``, row-parallel are ``P('model', 'fsdp')``.

Coverage is checked, not assumed: `sharding_for_path`'s silent replicate-on-
no-match stays as the *mechanism*, but the plan refuses to let a weight matrix
fall through silently — `ShardingPlan.coverage` lists every rank≥2 leaf no
rule matched, `tree_shardings(check=True)` warns loudly (or raises in strict
mode) so a renamed module can't quietly replicate a gigabyte of experts.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, List, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from rt1_tpu.parallel.mesh import MeshConfig, make_mesh

Rule = Tuple[str, P]

# Mesh-shape selection by device count when `config.parallel.auto` is set:
# n_devices -> (dp, fsdp, tp). The table follows SNIPPETS.md [1]'s shape
# ladder (small slices mix dp×fsdp, 8 adds tp, 16 goes fsdp×tp-heavy); the
# fallback for unlisted counts is pure fsdp — the memory-optimal default for
# a model that fits compute-bound on every chip.
#
# Keys are GLOBAL device counts (`jax.devices()`, host-major on multi-host
# slices — never `jax.local_devices()`): the 32/64 rows are pod-slice
# topologies where `dp` is the axis that crosses hosts. Because the mesh
# reshape is host-major with `dp` outermost (mesh.py), keeping fsdp×tp at or
# below the per-host device count keeps the bandwidth-hungry weight
# all-gathers on intra-host ICI while the (once-per-step, overlappable)
# gradient psum takes the DCN hops — `auto_mesh_shape` rebalances fsdp→dp
# when a row's model axes would spill across hosts.
AUTO_MESH_SHAPES = {
    1: (1, 1, 1),
    2: (2, 1, 1),
    4: (2, 2, 1),
    8: (2, 2, 2),
    16: (1, 4, 4),
    32: (4, 4, 2),
    64: (8, 4, 2),
}


def auto_mesh_shape(
    n_devices: int, local_device_count: Optional[int] = None
) -> Tuple[int, int, int]:
    """(dp, fsdp, tp) for `n_devices` GLOBAL devices, per AUTO_MESH_SHAPES.

    ``local_device_count`` (multi-host runs: `jax.local_device_count()`)
    keeps the table's rows host-contiguous: when a row's fsdp×tp product
    exceeds one host's devices, factors of 2 move from ``fsdp`` to ``dp``
    until the model axes fit inside a host — fsdp all-gathers stay on
    intra-host ICI and only the data-parallel gradient reduction crosses
    DCN. A single-host call (``local_device_count`` None or >= n_devices)
    returns the table row unchanged.
    """
    dp, fsdp, tp = AUTO_MESH_SHAPES.get(n_devices, (1, n_devices, 1))
    if local_device_count is not None and 0 < local_device_count < n_devices:
        while fsdp > 1 and fsdp % 2 == 0 and fsdp * tp > local_device_count:
            fsdp //= 2
            dp *= 2
    return dp, fsdp, tp


def rt1_sharding_plan() -> List[Rule]:
    """THE plan: ordered (path-regex, PartitionSpec) over every RT-1 param
    group. First match wins; paths are '/'-joined flax param paths.

    Covers the decoder, the FiLM-EfficientNet tokenizer, TokenLearner,
    embeddings, and the action head, so the coverage check can demand an
    explicit decision for every weight matrix.
    Norms/biases/BN stats are explicitly replicated — listed, not fallen
    through, so `coverage` distinguishes "decided small" from "forgotten".
    """
    return [
        # --- transformer decoder: attention ---------------------------------
        # qkv: (d_model, heads*key_dim) — columns over tp, rows over fsdp.
        (r"transformer/layer_\d+/attn/(query|key|value)/kernel$",
         P("fsdp", "model")),
        (r"transformer/layer_\d+/attn/(query|key|value)/bias$", P("model")),
        # out: (heads*key_dim, d_model) — row-parallel; GSPMD emits the psum
        # from the contraction.
        (r"transformer/layer_\d+/attn/out/kernel$", P("model", "fsdp")),
        (r"transformer/layer_\d+/attn/out/bias$", P()),
        # --- transformer decoder: FFN (single square Dense, transformer.py) -
        (r"transformer/layer_\d+/ff/kernel$", P("fsdp", "model")),
        (r"transformer/layer_\d+/ff/bias$", P("model")),
        (r"transformer/layer_\d+/norm_\d+/(scale|bias)$", P()),
        # --- embeddings + action head (the vocab head IS the action head:
        # action tokens decode from its logits) ------------------------------
        (r"transformer/token_emb/kernel$", P("fsdp", "model")),
        (r"transformer/token_emb/bias$", P("model")),
        (r"transformer/position_emb/embedding$", P(None, "fsdp")),
        (r"transformer/output_tokens/kernel$", P("fsdp", "model")),
        (r"transformer/output_tokens/bias$", P("model")),
        # --- FiLM-EfficientNet tokenizer ------------------------------------
        # FiLM projections: (512, channels) — shard the (large, always
        # divisible) embedding dim over fsdp; channels can be as small as 8.
        (r"projection_(add|mult)/kernel$", P("fsdp", None)),
        (r"projection_(add|mult)/bias$", P()),
        # Conv kernels, (kh, kw, cin, cout): output channels over fsdp.
        # Matches the EfficientNet stem/top/expand/project/depthwise convs,
        # the SE fc1/fc2 1x1 convs, the encoder conv1x1, the TokenLearner
        # conv1/conv2, and the tiny tokenizer's stem conv.
        (r"(conv|conv1|conv2|conv1x1|fc1|fc2)/kernel$",
         P(None, None, None, "fsdp")),
        (r"(conv|conv1|conv2|conv1x1|fc1|fc2)/bias$", P()),
        (r"bn/(scale|bias|mean|var)$", P()),
        (r"token_learner/norm/(scale|bias)$", P()),
        # --- tiny tokenizer (configs/tiny.py) -------------------------------
        (r"image_tokenizer_def/ctx_proj/kernel$", P("fsdp", None)),
        (r"image_tokenizer_def/ctx_proj/bias$", P()),
        (r"image_tokenizer_def/tok/kernel$", P(None, "fsdp")),
        (r"image_tokenizer_def/tok/bias$", P()),
        # --- block-spec decoder LM (models/lm) ------------------------------
        # Expert stacks (held, in, out): experts over `model`. On one chip
        # (every axis 1) the layer is told its share instead
        # (`model.lm.experts_held`) and runs without the exchange.
        (r"ffn/experts/w[123]/kernel$", P("model", None, None)),
        # fp32 router and its selection bias: every shard routes alike.
        (r"ffn/router/kernel$", P()),
        (r"ffn/expert_bias/kernel$", P()),
        # The (sliced) embedding, and the output head where it is a leaf of
        # its own (untied): rows over `model`.
        (r"(embed|lm_head)/embedding$", P("model", None)),
        # Mixers, the dense FFN and the norms: replicated.
        (r"mixer/(in_proj|out_proj|[qkvo]_proj)/kernel$", P()),
        (r"mixer/kernel$", P()),
        (r"ffn/w[123]/kernel$", P()),
        (r"(mixer_norm|ffn_norm|final_norm|q_norm|k_norm)/scale$", P()),
        # Latent attention: on one chip the layer is told its heads
        # (`model.lm.heads_held`); the latents' projections and norms are
        # computed by every shard alike.
        (r"mixer/(q_a_proj|q_b_proj|kv_a_proj|kv_b_proj)/kernel$", P()),
        (r"mixer/(q_a_layernorm|kv_a_layernorm)/scale$", P()),
        # The shared expert, a sublayer's stream maps, the prediction module's
        # merge and norms: replicated.
        (r"ffn/shared_expert/w[123]/kernel$", P()),
        (r"(mixer_hc|ffn_hc)/(norm/scale|alpha/scale|phi/kernel|maps_bias/bias)$", P()),
        (r"mtp/eh_proj/kernel$", P()),
        (r"mtp/(hnorm|enorm)/scale$", P()),
    ]


# --------------------------------------------------------------- quant plan
#
# Quantization groups for the low-precision serving engine
# (rt1_tpu/models/quant.py): the SAME path-regex machinery as the sharding
# rules above, so "what gets int8" is declared next to "how it shards"
# (SNIPPETS.md [3]'s sharding map carries torch.int8 dtypes per entry for
# exactly this reason). First match wins; an unmatched path serves at the
# master dtype. Groups:
QUANT_INT8 = "int8"   # per-output-channel int8 weights + f32 scale sidecar
QUANT_F32 = "f32"     # never quantized (master/compute dtype)


def rt1_quant_rules() -> List[Tuple[str, str]]:
    """THE quant plan: ordered (path-regex, group) over every RT-1 param
    group. int8 covers the matmul/conv weights whose bytes dominate the
    serving tree — transformer qkv/out/FFN, FiLM projections,
    every EfficientNet/SE/TokenLearner/encoder conv, and the tiny
    tokenizer's projections. Embeddings, the action head (`output_tokens`
    IS the action decode), norms, biases and BN statistics
    are listed f32 EXPLICITLY — `quant_coverage` distinguishes
    "decided full-precision" from "forgotten", same philosophy as the
    sharding plan's coverage check.
    """
    return [
        # --- explicit full-precision: embeddings + the action head -------
        (r"transformer/(token_emb|position_emb|output_tokens)/", QUANT_F32),
        # Norm/BN leaves are rank<2 (never quantizable) — listed anyway so
        # the decision is readable here, not implied by rank.
        (r"(norm_\d+|norm|bn)/(scale|bias|mean|var)$", QUANT_F32),
        # --- int8: transformer decoder matmuls ---------------------------
        (r"transformer/layer_\d+/attn/(query|key|value|out)/kernel$",
         QUANT_INT8),
        (r"transformer/layer_\d+/ff/kernel$", QUANT_INT8),
        # --- int8: FiLM-EfficientNet tokenizer ---------------------------
        (r"projection_(add|mult)/kernel$", QUANT_INT8),
        # Conv kernels (stem/top/expand/project/depthwise, SE fc1/fc2,
        # encoder conv1x1, TokenLearner conv1/conv2, tiny stem conv).
        (r"(conv|conv1|conv2|conv1x1|fc1|fc2)/kernel$", QUANT_INT8),
        # --- int8: tiny tokenizer projections ----------------------------
        (r"image_tokenizer_def/(ctx_proj|tok)/kernel$", QUANT_INT8),
    ]


def quant_group_for_path(
    path_str: str, rules: Optional[Sequence[Tuple[str, str]]] = None
) -> str:
    """First matching quant rule's group; unmatched paths serve at the
    master dtype (QUANT_F32)."""
    if rules is None:
        rules = rt1_quant_rules()
    for pattern, group in rules:
        if re.search(pattern, path_str):
            return group
    return QUANT_F32


def quant_coverage(
    tree: Any, rules: Optional[Sequence[Tuple[str, str]]] = None
) -> List[str]:
    """Paths of rank>=2 leaves no quant rule decided (fell through to the
    master-dtype default). Mirrors `ShardingPlan.coverage`: a weight
    matrix nobody DECIDED about is how a renamed module quietly loses its
    3x memory win — tier-1 pins this empty for the shipped configs."""
    from rt1_tpu.parallel import sharding as shardlib

    if rules is None:
        rules = rt1_quant_rules()
    undecided = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        if getattr(leaf, "ndim", 0) < 2:
            continue
        s = shardlib._path_str(path)
        if not any(re.search(pattern, s) for pattern, _ in rules):
            undecided.append(s)
    return undecided


# Plan-level placement for the stacked per-layer tree pipeline_apply shards
# over `stage`. The explicit replicated pin is load-bearing on XLA:CPU
# (jax 0.4.x): a stack/concatenate of per-layer params resharded straight
# into P(stage) on a mesh with another >1 axis SUMS the other axis' replicas
# into each stage shard. Pinning the stacked tree to a replicated layout
# first forces the partitioner to materialize the value before the stage
# reshard, which compiles correctly (the failure it masks is pinned in
# tests/test_pipeline.py::test_pp_train_step_equals_dense). Expressed as a
# rule list so the workaround lives in the plan, not inline in pipeline.py.
PIPELINE_STACK_RULES: List[Rule] = [
    (r".*", P()),
]


def pipeline_stack_placement(stacked_params: Any, mesh: Mesh) -> Any:
    """Apply the plan's pre-reshard placement to a stacked layer tree."""
    from rt1_tpu.parallel import sharding as shardlib

    return jax.tree_util.tree_map_with_path(
        lambda path, x: jax.lax.with_sharding_constraint(
            x, shardlib.sharding_for_path(path, mesh, PIPELINE_STACK_RULES)
        ),
        stacked_params,
    )


class PlanCoverageError(ValueError):
    """Strict mode: a weight matrix matched no plan rule."""


def strip_fsdp_axis(spec: P) -> P:
    """`spec` with the ``fsdp`` axis removed from every dim (the in-step
    gathered layout: tp sharding kept, weight shards whole again).

    The train step applies this as a `with_sharding_constraint` on the
    params at step entry: weights are STORED fsdp-sharded between steps
    (masters + optimizer moments — the ZeRO memory win) and gathered ONCE
    per step for fwd/bwd, with the gradient/update resharded back by the
    state's out_shardings (a reduce-scatter at the step boundary). One
    clean all-gather per step instead of per-use resharding also sidesteps
    the jax 0.4.x XLA:CPU partitioner's "involuntary full
    rematerialization" paths, which miscompute on dp>1 × fsdp>1 meshes
    when weights stay sharded through the fwd/bwd (pinned by
    tests/test_plan.py::test_dense_fsdp_tp_pp_equivalence_on_4_devices —
    the same bug family as PIPELINE_STACK_RULES' pin).
    """
    dims = []
    for d in spec:
        if d == "fsdp":
            dims.append(None)
        elif isinstance(d, (tuple, list)):
            kept = tuple(a for a in d if a != "fsdp")
            dims.append(kept if kept else None)
        else:
            dims.append(d)
    return P(*dims)


@dataclasses.dataclass
class ShardingPlan:
    """A resolved plan: mesh + rules + the batch layout, with coverage
    checking. Built once (`from_config`) and handed to every consumer.
    """

    mesh: Mesh
    rules: Sequence[Rule] = dataclasses.field(
        default_factory=rt1_sharding_plan
    )
    strict: bool = False

    # ------------------------------------------------------------ specs
    @property
    def batch_axes(self) -> Tuple[str, ...]:
        """Mesh axes the leading batch dim shards over. FSDP is data
        parallelism for activations, so the batch covers both axes."""
        return ("data", "fsdp")

    @property
    def data_parallel_size(self) -> int:
        """Total batch-sharding ways (per_host_batch_size must divide it)."""
        size = 1
        for a in self.batch_axes:
            size *= self.mesh.shape.get(a, 1)
        return size

    def batch_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(self.batch_axes))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    # ------------------------------------------------------------ matching
    def quant_group(self, path_str: str) -> str:
        """The quantization group for a param path (module-level quant
        rules; on the plan so layout consumers read shard + quant
        decisions from one object)."""
        return quant_group_for_path(path_str)

    def spec_for(self, path_str: str) -> Optional[P]:
        """First matching rule's spec, or None (≠ P()!) when unmatched."""
        for pattern, spec in self.rules:
            if re.search(pattern, path_str):
                return spec
        return None

    def coverage(self, tree: Any) -> List[str]:
        """Paths of rank≥2 leaves (weight matrices) no rule matched.

        Rank<2 leaves (biases, norms, BN stats, scalars) may fall through
        to replication freely — they are too small to matter; a silently
        replicated *matrix* is the bug this check exists for.
        """
        from rt1_tpu.parallel import sharding as shardlib

        unmatched = []
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if getattr(leaf, "ndim", 0) < 2:
                continue
            s = shardlib._path_str(path)
            if self.spec_for(s) is None:
                unmatched.append(s)
        return unmatched

    def check_coverage(self, tree: Any, what: str = "params") -> List[str]:
        """Loud-warn (or strict-raise) on unmatched weight matrices."""
        unmatched = self.coverage(tree)
        if unmatched:
            msg = (
                f"sharding plan: {len(unmatched)} {what} weight matrices "
                f"matched NO rule and would silently replicate: "
                f"{unmatched[:8]}{'...' if len(unmatched) > 8 else ''} — "
                f"add rules to rt1_tpu/parallel/plan.py"
            )
            if self.strict:
                raise PlanCoverageError(msg)
            import logging

            logging.getLogger("rt1_tpu.parallel.plan").warning(msg)
        return unmatched

    # ------------------------------------------------------------ placement
    def tree_shardings(self, tree: Any, check: bool = False) -> Any:
        """Pytree of NamedShardings matching `tree` per the rules; unmatched
        leaves replicate (after `check_coverage` when `check`)."""
        from rt1_tpu.parallel import sharding as shardlib

        if check:
            self.check_coverage(tree)
        return shardlib.shard_pytree(tree, self.mesh, self.rules)

    def place_variables(self, variables: Any, check: bool = True) -> Any:
        """device_put a restored `{'params': ..., 'batch_stats': ...}` tree
        through the plan — the eval/serve placement path."""
        return jax.device_put(
            variables, self.tree_shardings(variables, check=check)
        )

    def gather_shardings(self, tree: Any) -> Any:
        """Per-leaf NamedShardings for the IN-STEP layout: plan specs with
        the fsdp axis stripped (see `strip_fsdp_axis`). Applied by the
        train step as a with_sharding_constraint at step entry."""
        from rt1_tpu.parallel import sharding as shardlib

        def one(path, leaf):
            spec = self.spec_for(shardlib._path_str(path))
            spec = strip_fsdp_axis(spec if spec is not None else P())
            shape = getattr(leaf, "shape", None)
            if shape is not None:
                spec = shardlib.spec_for_shape(spec, shape, self.mesh)
            return NamedSharding(self.mesh, spec)

        return jax.tree_util.tree_map_with_path(one, tree)

    # ------------------------------------------------------------ factory
    @classmethod
    def from_config(
        cls,
        config: Any = None,
        devices: Optional[Sequence[jax.Device]] = None,
        n_devices: Optional[int] = None,
        collapse_data: bool = False,
    ) -> "ShardingPlan":
        """Resolve the plan ONCE from `config.parallel` (dp/fsdp/tp/pp
        sizes, `auto` mesh-shape selection by device count, `strict`
        coverage), falling back to the legacy `config.mesh` block
        (data/model/stage) for configs that predate `config.parallel`,
        and to pure DP when neither block exists (pinned proof configs).

        ``collapse_data=True`` is the serving resolution (eval/restore.py
        `serving_plan`): there is no batch axis to shard (sessions are
        slots, not data shards), so `dp` collapses to 1 and the mesh covers
        exactly the fsdp × tp × pp devices model parallelism needs —
        raising when the host has fewer. One resolver for train AND serve,
        so the ladder/axes can never drift between them.
        """
        dp, fsdp, tp, pp = -1, 1, 1, 1
        strict = False
        par = _get(config, "parallel")
        for block, key in (("parallel", "sp"), ("mesh", "seq")):
            if int(_get(_get(config, block), key, 1)) > 1:
                raise ValueError(
                    f"config.{block}.{key} > 1 asks for sequence parallelism "
                    "(ring attention), which was removed and has no "
                    "replacement: the mesh is ('data', 'stage', 'fsdp', "
                    "'model')"
                )
        if par is not None:
            if _get(par, "auto", False):
                # Resolution is against the GLOBAL device set (`jax.
                # devices()`, host-major on a multi-process slice) — the
                # mesh spans every process's devices; `jax.local_devices()`
                # would build N disjoint single-host meshes instead of one
                # slice-wide program.
                local = None
                if n_devices is None and devices is None:
                    pool = jax.devices()
                    n = len(pool)
                    if jax.process_count() > 1:
                        local = jax.local_device_count()
                else:
                    n = n_devices if n_devices is not None else len(devices)
                pp = int(_get(par, "pp", 1))
                # pp is honored as configured: the auto table splits only
                # the devices left after the stage axis takes its own, so
                # auto composes with pp>1 instead of over-subscribing the
                # mesh.
                dp, fsdp, tp = auto_mesh_shape(max(n // max(pp, 1), 1), local)
            else:
                dp = int(_get(par, "dp", -1))
                fsdp = int(_get(par, "fsdp", 1))
                tp = int(_get(par, "tp", 1))
                pp = int(_get(par, "pp", 1))
            strict = bool(_get(par, "strict", False))
        else:
            legacy = _get(config, "mesh")
            if legacy is not None:
                dp = int(_get(legacy, "data", -1))
                tp = int(_get(legacy, "model", 1))
                pp = int(_get(legacy, "stage", 1))
        if collapse_data:
            dp = 1
            n = fsdp * tp * pp
            pool = list(devices) if devices is not None else jax.devices()
            if len(pool) < n:
                raise ValueError(
                    f"config.parallel asks for fsdp*tp*pp={n} devices "
                    f"but this serving host has {len(pool)}"
                )
            devices = pool[:n]
        mesh = make_mesh(
            MeshConfig(data=dp, fsdp=fsdp, model=tp, stage=pp),
            devices=devices,
        )
        return cls(mesh=mesh, strict=strict)


def _get(obj: Any, key: str, default: Any = None) -> Any:
    """config attribute/key lookup tolerating ml_collections, dicts, None."""
    if obj is None:
        return default
    if hasattr(obj, "get"):
        try:
            v = obj.get(key, default)
            return default if v is None else v
        except TypeError:
            pass
    v = getattr(obj, key, default)
    return default if v is None else v


def mixed_precision_from_config(config: Any) -> bool:
    """The `config.parallel.mixed_precision` switch (False when absent)."""
    return bool(_get(_get(config, "parallel"), "mixed_precision", False))

"""SPMD parallelism: device meshes, sharding rules, and distributed helpers.

This package is the TPU-native replacement for the reference's two comm backends
(SURVEY.md §2.6): PyTorch-Lightning `DDPStrategy` over NCCL
(`distribute_train.py:235`) and `jax.pmap`/`lax.pmean` with axis name "batch"
(`language_table/train/train.py:143-151`). Instead of explicit allreduce calls,
we lay out a single `jax.sharding.Mesh` over the slice and let GSPMD insert XLA
collectives (psum / all-gather / reduce-scatter) over ICI.

Layout policy lives in `plan.py`: one declarative (name-pattern →
PartitionSpec) plan over the ``('data', 'stage', 'fsdp', 'model')``
mesh, resolved once from `config.parallel` and consumed identically by train,
eval, and serve — dense/fsdp/tp/pp are config switches, not code paths.
"""

from rt1_tpu.parallel.distributed import (
    DistributedOptions,
    describe_devices,
    initialize_from_config,
    is_primary,
)
from rt1_tpu.parallel.mesh import MeshConfig, make_mesh
from rt1_tpu.parallel.pipeline import (
    pipeline_apply,
    pp_causal_transformer_apply,
    stack_layer_params,
    unstack_layer_params,
)
from rt1_tpu.parallel.plan import (
    AUTO_MESH_SHAPES,
    PlanCoverageError,
    ShardingPlan,
    auto_mesh_shape,
    mixed_precision_from_config,
    rt1_sharding_plan,
)
from rt1_tpu.parallel.sharding import (
    batch_sharding,
    replicated,
    rt1_parameter_rules,
    shard_pytree,
    sharding_for_path,
)

__all__ = [
    "AUTO_MESH_SHAPES",
    "DistributedOptions",
    "MeshConfig",
    "PlanCoverageError",
    "ShardingPlan",
    "auto_mesh_shape",
    "describe_devices",
    "initialize_from_config",
    "is_primary",
    "make_mesh",
    "batch_sharding",
    "mixed_precision_from_config",
    "pipeline_apply",
    "pp_causal_transformer_apply",
    "replicated",
    "rt1_parameter_rules",
    "rt1_sharding_plan",
    "shard_pytree",
    "sharding_for_path",
    "stack_layer_params",
    "unstack_layer_params",
]

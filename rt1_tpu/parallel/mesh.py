"""Device-mesh construction.

The reference's device topology is implicit (one process per GPU, NCCL ring under
Lightning DDP, `distribute_train.py:194,235`). On TPU the topology is explicit: a
`jax.sharding.Mesh` over the slice, with named axes that sharding specs refer to.

Axis conventions used throughout rt1_tpu:

* ``data``  — data parallelism (batch axis). Gradient reduction becomes an XLA
  psum over ICI, replacing DDP's NCCL bucket allreduce.
* ``fsdp``  — fully-sharded data parallelism (ZeRO-3): the batch is sharded over
  it like ``data``, but parameters/optimizer state are *also* sharded over it
  (per the plan in rt1_tpu/parallel/plan.py), so GSPMD emits all-gathers for
  weights at use sites and reduce-scatters for gradients.
* ``model`` — tensor parallelism (attention heads / FFN columns).
* ``stage`` — pipeline parallelism (GPipe-style microbatch rotation over layer
  stages, rt1_tpu/parallel/pipeline.py). Beyond reference parity.

All axes are optional; size-1 axes are free (no collectives are emitted for them).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh


@dataclasses.dataclass(frozen=True)
class MeshConfig:
    """Logical mesh shape. -1 for `data` means "all remaining devices"."""

    data: int = -1
    fsdp: int = 1
    model: int = 1
    stage: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.fsdp * self.model * self.stage
        if n_devices % fixed != 0:
            raise ValueError(
                f"{n_devices} devices not divisible by "
                f"fsdp*model*stage={fixed}"
            )
        data = self.data if self.data != -1 else n_devices // fixed
        if data * fixed != n_devices:
            raise ValueError(
                f"mesh {data}x{self.stage}x{self.fsdp}x{self.model} "
                f"!= {n_devices} devices"
            )
        return MeshConfig(
            data=data, fsdp=self.fsdp, model=self.model, stage=self.stage
        )


def make_mesh(
    config: MeshConfig = MeshConfig(),
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a ('data', 'stage', 'fsdp', 'model') mesh over `devices`
    (default: all).

    Axis order puts ``model`` innermost so tensor-parallel collectives ride the
    fastest ICI links (nearest-neighbor on a TPU slice), ``data`` outermost so DP
    psum tolerates the slower hops (and DCN across hosts on multi-host slices,
    where `jax.devices()` is already ordered host-major). ``fsdp`` sits between:
    its per-layer weight all-gathers are bandwidth-hungry like TP but overlap
    with compute, so it takes the middle hops. ``stage`` sits next to ``data``:
    pipeline ppermutes are point-to-point once per microbatch tick — far less
    bandwidth-hungry than TP collectives — so they get the longer hops.
    """
    devices = list(devices if devices is not None else jax.devices())
    cfg = config.resolve(len(devices))
    arr = np.asarray(devices).reshape(cfg.data, cfg.stage, cfg.fsdp, cfg.model)
    return Mesh(arr, axis_names=("data", "stage", "fsdp", "model"))

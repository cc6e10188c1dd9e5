"""Multi-process (multi-host) runtime initialization.

Single-host rt1_tpu needs none of this: `jax.devices()` is the local chip
set and every collective stays on ICI. A pod slice is N cooperating
processes (one per host), and before any of them touches a device the JAX
runtime must rendezvous — `jax.distributed.initialize` with a coordinator
address plus this process's id — so `jax.devices()` becomes the host-major
GLOBAL device list the sharding plan resolves against
(`ShardingPlan.from_config`), cross-host collectives lower to DCN, and
Orbax checkpointing coordinates its per-host shard writes.

Config surface (`config.parallel.distributed`, docs/parallelism.md
"Multi-host"):

* ``enabled``             — off (default) keeps the exact single-process path.
* ``coordinator_address`` — "host:port" of process 0.
* ``process_id`` / ``num_processes`` — this process's rank and the world
  size; ``-1`` defers to environment fallbacks.

Environment fallbacks (checked in order) let one config file serve every
host of a slice — the per-host identity rides the launcher's environment:

* ``RT1_COORDINATOR`` / ``RT1_PROCESS_ID`` / ``RT1_NUM_PROCESSES`` — ours.
* ``JAX_COORDINATOR_ADDRESS`` / ``JAX_PROCESS_ID`` / ``JAX_NUM_PROCESSES``
  — the names `jax.distributed` itself honors.
* On TPU pods all three may be absent: `jax.distributed.initialize()` with
  no arguments reads the TPU metadata server (the "enabled with nothing
  else set" path).

`initialize_from_config` is idempotent (a second call is a no-op, loudly)
and must run before the first device access — the train entry calls it
ahead of plan resolution (`train/train.py train_and_evaluate`).
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

#: Module-level latch: `jax.distributed.initialize` may run once per
#: process; a second train_and_evaluate in the same process (tests, sweeps)
#: must not crash on re-init.
_INITIALIZED = False


@dataclasses.dataclass(frozen=True)
class DistributedOptions:
    """Resolved `config.parallel.distributed` block (env fallbacks applied)."""

    enabled: bool = False
    coordinator_address: Optional[str] = None
    process_id: Optional[int] = None
    num_processes: Optional[int] = None

    @classmethod
    def from_config(cls, config: Any) -> "DistributedOptions":
        from rt1_tpu.parallel.plan import _get

        block = _get(_get(config, "parallel"), "distributed")
        if block is None:
            return cls()
        enabled = bool(_get(block, "enabled", False))
        addr = _get(block, "coordinator_address") or _env_str(
            "RT1_COORDINATOR", "JAX_COORDINATOR_ADDRESS"
        )
        pid = _int_or_none(_get(block, "process_id", -1))
        if pid is None:
            pid = _env_int("RT1_PROCESS_ID", "JAX_PROCESS_ID")
        count = _int_or_none(_get(block, "num_processes", -1))
        if count is None:
            count = _env_int("RT1_NUM_PROCESSES", "JAX_NUM_PROCESSES")
        return cls(
            enabled=enabled,
            coordinator_address=addr,
            process_id=pid,
            num_processes=count,
        )

    def validate(self) -> None:
        """Fail at the config seam: a half-specified rendezvous hangs in
        the coordinator handshake instead of erroring, so partial explicit
        settings are rejected here with the missing field named."""
        if not self.enabled:
            return
        explicit = [
            self.coordinator_address is not None,
            self.process_id is not None,
            self.num_processes is not None,
        ]
        if any(explicit) and not all(explicit):
            missing = [
                name
                for name, have in zip(
                    ("coordinator_address", "process_id", "num_processes"),
                    explicit,
                )
                if not have
            ]
            raise ValueError(
                f"parallel.distributed: {', '.join(missing)} unset while "
                f"other rendezvous fields are explicit — set them in the "
                f"config block or via RT1_COORDINATOR / RT1_PROCESS_ID / "
                f"RT1_NUM_PROCESSES (all three, or none for TPU-metadata "
                f"auto-discovery)"
            )
        if self.num_processes is not None and self.num_processes < 1:
            raise ValueError(
                f"parallel.distributed.num_processes={self.num_processes} "
                f"must be >= 1"
            )
        if (
            self.process_id is not None
            and self.num_processes is not None
            and not 0 <= self.process_id < self.num_processes
        ):
            raise ValueError(
                f"parallel.distributed.process_id={self.process_id} out of "
                f"range [0, {self.num_processes})"
            )


def _env_str(*names: str) -> Optional[str]:
    for n in names:
        v = os.environ.get(n)
        if v:
            return v
    return None


def _env_int(*names: str) -> Optional[int]:
    v = _env_str(*names)
    return int(v) if v is not None else None


def _int_or_none(v: Any) -> Optional[int]:
    """Config ints where -1/None mean "defer to the environment"."""
    if v is None:
        return None
    v = int(v)
    return None if v < 0 else v


def initialize_from_config(config: Any) -> bool:
    """`jax.distributed.initialize` per `config.parallel.distributed`.

    Returns True when this call performed the initialization, False when
    the block is absent/disabled or the process was already initialized
    (idempotent — a second train run in one process logs and moves on).
    Must run before the first device access; the train entry calls it
    before resolving the sharding plan.
    """
    global _INITIALIZED

    opts = DistributedOptions.from_config(config)
    if not opts.enabled:
        return False
    opts.validate()
    from absl import logging

    if _INITIALIZED:
        logging.warning(
            "parallel.distributed: already initialized in this process — "
            "skipping re-initialization"
        )
        return False
    import jax

    kwargs = {}
    if opts.coordinator_address is not None:
        kwargs = dict(
            coordinator_address=opts.coordinator_address,
            process_id=opts.process_id,
            num_processes=opts.num_processes,
        )
    jax.distributed.initialize(**kwargs)
    _INITIALIZED = True
    logging.info(
        "parallel.distributed: process %d/%d up (%d local / %d global "
        "devices, coordinator %s)",
        jax.process_index(),
        jax.process_count(),
        jax.local_device_count(),
        jax.device_count(),
        opts.coordinator_address or "<tpu metadata>",
    )
    return True


def force_cpu_multiprocess_runtime(
    devices_per_process: int, gloo: bool = True
) -> None:
    """Pin THIS process to a forced-CPU multi-device platform with a real
    cross-process collectives backend — the bootstrap every CPU-mesh
    scale-out rehearsal needs (tests/multiprocess_worker.py,
    tests/distributed_worker.py, scripts/bench_multihost.py), kept in ONE
    place so a collectives tweak cannot drift between suites.

    Gloo matters: XLA:CPU's default collectives ("none") cannot dispatch
    a computation spanning processes ("Multiprocess computations aren't
    implemented on the CPU backend"). Both the env var AND the live
    config are set so the call works whether or not the caller has
    already imported jax. Must run before the first device access;
    never call it in a process that should keep its own backend (a parent
    test session importing a worker module, e.g.).
    """
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={devices_per_process}"
    ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")
    if gloo:
        os.environ["JAX_CPU_COLLECTIVES_IMPLEMENTATION"] = "gloo"
        jax.config.update("jax_cpu_collectives_implementation", "gloo")


def describe_devices() -> Dict[str, Any]:
    """`platform`, `device_kind` and `device_count` of this process's
    backend — what every entry point logs once at start.

    This is also where a process first touches the accelerator, so a
    backend that cannot be initialized is named here instead of surfacing
    from some first array op: the usual cause on a TPU host is that
    another process holds the chip (libtpu then reports an "internal
    error" about its lockfile).
    """
    import jax

    from rt1_tpu.obs import startup

    # The start-up log's listeners go in before anything is traced, on the
    # trainer's path and the benchmark's alike (obs/startup.py).
    startup.install()
    try:
        with startup.phase("backend_init"):
            devices = jax.devices()
    except RuntimeError as exc:
        raise RuntimeError(
            f"cannot initialize the accelerator backend: {exc}\n"
            "A chip belongs to one process at a time. If another process on "
            "this host already holds it — a trainer, another serve replica "
            "(a fleet's replicas all inherit the same devices), a fleet "
            "supervisor running its eval gate — this one cannot start: run "
            "one JAX process per chip."
        ) from exc
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def free_local_port() -> int:
    """An OS-assigned free loopback port — coordinator-address plumbing
    for the multi-process rehearsals (tests/bench spawn groups that need
    a rendezvous port before any process exists). One copy here so a
    port-allocation fix (e.g. reuse-race mitigation) lands everywhere."""
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def is_primary() -> bool:
    """True on the process that owns single-writer side effects (manifests,
    markers, reports) — process 0, or any process of a single-process run."""
    import jax

    return jax.process_index() == 0

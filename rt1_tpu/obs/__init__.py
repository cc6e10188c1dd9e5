"""rt1_tpu.obs — unified observability across train, data, and serve.

One subsystem, ten pieces, all optional and all cheap when off:

* :mod:`rt1_tpu.obs.trace`      — host-side Chrome-trace span recorder
  (Perfetto-loadable); train loop, feeder workers, and serve batcher emit
  into one timeline.
* :mod:`rt1_tpu.obs.steps`      — `StepTimeline`: per-step wall-time
  attribution (wait_data / h2d / device_step / host) + the rolling
  `stall_pct` gauge.
* :mod:`rt1_tpu.obs.prometheus` — exposition text format + the opt-in
  scrape listener (`MetricsServer`).
* :mod:`rt1_tpu.obs.recorder`   — `FlightRecorder`: ring buffer of recent
  step records, dumped to JSONL on crash/SIGTERM.
* :mod:`rt1_tpu.obs.health`     — on-device model-health pack (per-layer
  gradient/update norms, logit entropy, token accuracy) computed inside
  the jitted step, fetched only at log steps.
* :mod:`rt1_tpu.obs.goodput`    — `GoodputLedger`: run-level wall-time
  partition (init/compile/step/stall/ckpt/rollback/preempt) + live MFU.
* :mod:`rt1_tpu.obs.startup`    — the start-up log: set-up phases
  (`rt1/setup/*`) and every trace, lowering and compile by function name
  from `jax.monitoring`; feeds the ledger's `compile` bucket, always on.
* :mod:`rt1_tpu.obs.flops`      — XLA cost-analysis FLOPs + MFU math,
  shared by `bench.py --mode mfu` and the goodput ledger.
* :mod:`rt1_tpu.obs.slo`        — serving SLO ledger: request outcome
  buckets, availability, error-budget burn, `slo_summary.json`.
* :mod:`rt1_tpu.obs.quantiles`  — the one percentile implementation
  (exact-from-samples + histogram upper bound) every reporter shares.

Import hygiene is part of the contract: this package (and everything it
imports at module scope) must not require clu, tensorboard, or tensorflow
— headless serve deployments scrape `/metrics` without dragging in the
training stack. `tests/test_obs_imports.py` pins this.

See `docs/observability.md` for the operator guide.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional

from rt1_tpu.obs import (
    flops,
    goodput,
    health,
    prometheus,
    quantiles,
    recorder,
    slo,
    startup,
    steps,
    trace,
)
from rt1_tpu.obs.goodput import GoodputLedger
from rt1_tpu.obs.prometheus import MetricsServer
from rt1_tpu.obs.recorder import ExemplarRing, FlightRecorder
from rt1_tpu.obs.slo import SLOLedger, SLOObjectives
from rt1_tpu.obs.steps import StepTimeline
from rt1_tpu.obs.trace import TraceRecorder

__all__ = [
    "ExemplarRing",
    "FlightRecorder",
    "GoodputLedger",
    "MetricsServer",
    "ObsOptions",
    "SLOLedger",
    "SLOObjectives",
    "StepTimeline",
    "TraceRecorder",
    "flops",
    "goodput",
    "health",
    "prometheus",
    "quantiles",
    "recorder",
    "slo",
    "startup",
    "steps",
    "trace",
]


@dataclasses.dataclass
class ObsOptions:
    """Resolved `config.obs` with defaults for configs that predate it.

    The train loop consumes this instead of poking `config.obs.*` directly
    so pre-obs configs (proof configs, pinned sweep artifacts) keep running
    unmodified, and so defaults live in exactly one place.
    """

    trace: bool = False
    trace_path: Optional[str] = None  # None -> <workdir>/trace.json
    trace_max_events: int = 200_000
    stall_window: int = 50
    sync_timing: bool = False
    prometheus_port: int = -1  # < 0: no train-side listener; 0: ephemeral
    prometheus_host: str = "127.0.0.1"
    flight_recorder: bool = True
    flight_recorder_size: int = 256
    flight_recorder_path: Optional[str] = None  # None -> <workdir>/...jsonl
    # Model-health pack (obs/health.py): computed inside the jitted step,
    # fetched at log steps. Off by default so configs predating it keep a
    # bit-identical step program.
    model_health: bool = False
    health_group_depth: int = 2
    # Goodput ledger (obs/goodput.py): host-side run wall-time partition +
    # final JSON summary. Pure host arithmetic — safe to default on.
    goodput: bool = True
    goodput_summary_path: Optional[str] = None  # None -> <workdir>/goodput...
    # Live MFU gauge: estimate step FLOPs via XLA cost analysis of the
    # *lowered* step (no extra compile). Off by default: lowering costs a
    # second trace of the step at startup.
    goodput_mfu: bool = False

    @classmethod
    def from_config(cls, config, workdir: Optional[str] = None) -> "ObsOptions":
        """Read `config.obs` if present (ml_collections or plain mapping);
        absent keys fall back to the dataclass defaults."""
        node = None
        if config is not None:
            get = getattr(config, "get", None)
            node = get("obs") if callable(get) else getattr(config, "obs", None)
        kwargs = {}
        if node is not None:
            for field in dataclasses.fields(cls):
                getter = getattr(node, "get", None)
                value = (
                    getter(field.name)
                    if callable(getter)
                    else getattr(node, field.name, None)
                )
                if value is not None:
                    kwargs[field.name] = value
        opts = cls(**kwargs)
        if workdir:
            if opts.trace_path is None:
                opts.trace_path = os.path.join(workdir, "trace.json")
            if opts.flight_recorder_path is None:
                opts.flight_recorder_path = os.path.join(
                    workdir, "flight_record.jsonl"
                )
            if opts.goodput_summary_path is None:
                opts.goodput_summary_path = os.path.join(
                    workdir, goodput.SUMMARY_BASENAME
                )
        return opts

"""XLA cost-analysis FLOPs + MFU arithmetic, shared by bench and the ledger.

Extracted from ``bench.py --mode mfu`` (which remains the lab A/B
entrypoint) so the *same* estimator can feed the run-level goodput ledger
(:mod:`rt1_tpu.obs.goodput`) as a live ``goodput/mfu_pct`` gauge: FLOPs per
train step come from XLA's own cost analysis of the step program — the
whole fwd+bwd+update graph, not a hand-derived 6·N·D guess — and MFU is
``measured FLOP/s / peak FLOP/s``.

Two analysis paths, deliberately distinct:

* :func:`train_step_flops` with ``compile=False`` (default) analyzes the
  *lowered* (pre-compile) program. No executable is built, so the train
  loop can estimate FLOPs from ``ShapeDtypeStruct`` avals without paying a
  second multi-minute compile or touching device memory.
* ``compile=True`` analyzes the *compiled* executable — post-fusion, the
  numbers ``bench.py --mode mfu`` has always published. Bench keeps this
  path so its baselines stay comparable.

Peak FLOP/s comes from :data:`PEAK_FLOPS_BY_DEVICE_KIND`, keyed by jax's
``device_kind``. A device that is not in the table has no peak and so no
MFU: :func:`peak_flops` warns, naming the device, and returns None.

Import-light by contract: stdlib at module scope, jax only inside the
functions that analyze a program (pinned by tests/test_obs_imports.py).
"""

from __future__ import annotations

import logging
from typing import Any, Dict, Optional

#: Peak dense bf16 FLOP/s of ONE chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16 per
#: chip); jax reports that chip as "TPU v5 lite".
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v5 lite": 197e12,
}


def peak_flops(device_kind: str) -> Optional[float]:
    """Peak FLOP/s of one `device_kind` chip; None (and a warning) if unknown."""
    peak = PEAK_FLOPS_BY_DEVICE_KIND.get(device_kind)
    if peak is None:
        logging.warning(
            "no peak FLOP/s known for device_kind %r: MFU is not reported "
            "(add it to rt1_tpu.obs.flops.PEAK_FLOPS_BY_DEVICE_KIND with "
            "its source)",
            device_kind,
        )
    return peak


def cost_analysis_flops(cost: Optional[Dict[str, float]]) -> float:
    """The 'flops' entry of a jax ``cost_analysis()`` result.

    jax 0.9 returns one dict, or None where the backend has no analysis
    (it catches the backend's NotImplementedError itself).
    """
    return float(cost.get("flops", 0.0)) if cost else 0.0


def train_step_flops(
    jitted_fn: Any, *args: Any, compile: bool = False
) -> Optional[float]:
    """FLOPs of one call of `jitted_fn(*args)` per XLA cost analysis.

    `args` may be concrete arrays or ``jax.ShapeDtypeStruct`` avals (the
    train loop passes avals so no device transfer happens). Returns None
    when the analysis reports no FLOPs — callers treat that as "no MFU
    gauge", never as a real measurement. Lowering and compile errors
    propagate: the same program is about to run, so they are real.
    """
    lowered = jitted_fn.lower(*args)
    target = lowered.compile() if compile else lowered
    flops = cost_analysis_flops(target.cost_analysis())
    return flops if flops > 0 else None


def mfu_pct(
    flops_per_step: float,
    sec_per_step: float,
    n_chips: int,
    peak_flops: float,
) -> float:
    """Model-FLOPs-utilization in percent: achieved / peak FLOP/s."""
    if sec_per_step <= 0 or flops_per_step <= 0:
        return 0.0
    n = max(int(n_chips), 1)
    return flops_per_step / sec_per_step / (float(peak_flops) * n) * 100.0


def mfu_detail(
    flops_per_step: float,
    sec_per_step: float,
    n_chips: int,
    peak_flops: float,
) -> Dict[str, float]:
    """The stderr detail dict bench prints next to the metric."""
    return {
        "flops_per_step": float(flops_per_step),
        "sec_per_step": round(float(sec_per_step), 6),
        "peak_flops": float(peak_flops),
        "mfu_pct": round(
            mfu_pct(flops_per_step, sec_per_step, n_chips, peak_flops), 3
        ),
    }

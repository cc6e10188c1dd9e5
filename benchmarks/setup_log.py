"""What the program's own start-up log says of set-up (rt1_tpu/obs/startup.py):
the phases it opened and every trace, lowering and compile by function name.

``reading`` carries nothing of set-up, so the ``setup_*`` readers take the
process's own snapshot: they run in the run's process, after the driver
returns.  A traced run's set-up is the untraced run's (the profiler starts
inside the window).  A test hands a snapshot in under ``reading["startup"]``.
A program from before the log has no such module: every reader then returns
None and the line leaves the metric out.
"""

from typing import Any, Dict, Optional

BUILD_PHASES = ("build_model", "make_optimizer", "init_state", "make_step_fns",
                "shard_state", "open_feed", "first_batch")


def snapshot(reading: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The snapshot handed in, else the process's own, taken once a run and
    kept in ``reading`` so that the seven readers read the same one."""
    if "startup" not in reading:
        try:
            from rt1_tpu.obs import startup
        except ImportError:
            return None
        reading["startup"] = startup.snapshot()
    return reading["startup"]


def phase_seconds(reading: Dict[str, Any], names, key: str = "seconds") -> Optional[float]:
    """Sum of ``key`` over the named phases; None where none of them was opened."""
    phases = (snapshot(reading) or {}).get("phase_s") or {}
    held = [phases[n][key] for n in names if n in phases]
    return float(sum(held)) if held else None


def step(reading: Dict[str, Any], key: str):
    """``key`` of the role ``train_step``: summed over the step's functions."""
    role = ((snapshot(reading) or {}).get("roles") or {}).get("train_step")
    return None if role is None else role.get(key)

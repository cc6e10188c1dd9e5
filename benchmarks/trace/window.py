"""Tracing a short steady slice of a window with the JAX profiler.

The driver calls ``tick`` before each dispatch.  The first part of the
window runs untraced and gives the host-clock readings (the interval between
completions, the feeder's wait, H2D); then a short slice is traced and gives
the device's.  The profiler stalls the device every few steps while it runs
(PERF.md section 5) and stopping it blocks for some seconds a traced step,
which is why the slice is short, no wall-clock number is read from it, and no
end-to-end number from a traced run.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Any, Callable, Dict, Optional

START_AFTER_SHARE = 0.4   # of the window, run untraced first
MIN_STEPS = 8            # traced steps; the window's end closes a slice early
MIN_SECONDS = 1.0


class Tracer:
    def __init__(self, trace_dir: str, log: Callable[[str], None], seconds: float):
        self.start_after_s = START_AFTER_SHARE * seconds
        self.dir = trace_dir
        self.log = log
        self.state = "waiting"
        self._t_start = 0.0
        self._mark: Dict[str, float] = {}
        self._first: Optional[Dict[str, float]] = None
        self.untraced: Optional[Dict[str, float]] = None
        self.slice: Optional[Dict[str, float]] = None

    def _snapshot(self, completions, host, h2d_s: float) -> Dict[str, float]:
        return {"t": time.perf_counter(), "steps": len(completions),
                "done": completions[-1] if completions else 0.0,
                "wait_s": host.wait_s, "calls": host.calls, "h2d_s": h2d_s}

    def tick(self, elapsed_s: float, completions, host, h2d_s: float) -> None:
        """``completions``: the host's stamps of the steps completed so far."""
        import jax

        if self._first is None:
            if not completions:
                return
            self._first = self._snapshot(completions, host, h2d_s)
        steps = len(completions)
        if (self.state == "waiting" and elapsed_s >= self.start_after_s
                and steps > self._first["steps"]):
            self.untraced = self._between(self._first, self._snapshot(completions, host, h2d_s))
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            # no python-frame tracing: it slows the host several-fold and is
            # read by nothing; TraceAnnotations are host TraceMe events
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.dir, profiler_options=options)
            self.state = "tracing"
            self._mark = self._snapshot(completions, host, h2d_s)
        elif self.state == "tracing":
            now = self._snapshot(completions, host, h2d_s)
            if (now["steps"] - self._mark["steps"] >= MIN_STEPS
                    and now["t"] - self._mark["t"] >= MIN_SECONDS):
                self._stop(now)

    def finish(self, completions, host, h2d_s: float) -> None:
        if self.state == "tracing":
            self._stop(self._snapshot(completions, host, h2d_s))

    def _stop(self, now: Dict[str, float]) -> None:
        import jax

        jax.profiler.stop_trace()
        self.state = "done"
        took = time.perf_counter() - now["t"]
        self.slice = self._between(self._mark, now)
        self.log(f"untraced part: {self.untraced['steps']} intervals between completions in "
                 f"{self.untraced['seconds']:.3f}s; traced slice: {self.slice['steps']} "
                 f"steps in {now['t'] - self._mark['t']:.3f}s (stop_trace took {took:.1f}s)")

    @staticmethod
    def _between(a: Dict[str, float], b: Dict[str, float]) -> Dict[str, float]:
        """``seconds`` runs from a's last completion to b's: whole intervals."""
        return {"steps": b["steps"] - a["steps"], "seconds": b["done"] - a["done"],
                "feeder_wait_s": b["wait_s"] - a["wait_s"],
                "feeder_calls": b["calls"] - a["calls"], "h2d_s": b["h2d_s"] - a["h2d_s"]}

    def summary(self) -> Dict[str, Any]:
        from benchmarks.trace import reduce, xplane

        if self.slice is None:
            raise RuntimeError("the window ended before the traced slice began: "
                               "--seconds is too short to trace")
        rows = xplane.rows_from_xplane(xplane.find_xplane(self.dir))
        out = reduce.reduce_rows(rows)
        out["slice"] = self.slice
        out["untraced"] = self.untraced
        shutil.rmtree(self.dir, ignore_errors=True)
        return out

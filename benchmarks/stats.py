"""Arithmetic of the end-to-end metrics, apart from any device so that it can
be tested on hand-made stamps."""

from __future__ import annotations

from typing import Dict, Sequence

import numpy as np


def train_window_metrics(first_dispatch_s: float, completions_s: Sequence[float],
                         batch: int) -> Dict[str, float]:
    """Rate over all the work and all the time of the window, and the tail
    of every interval between consecutive step completions.

    ``completions_s[i]`` is the host time at which step i's loss was ready.
    """
    done = np.asarray(completions_s, np.float64)
    if done.size < 2:
        raise ValueError("a window needs two completed steps or more")
    span = float(done[-1] - first_dispatch_s)
    intervals_ms = np.diff(done) * 1e3
    return {
        "train_samples_per_s": float(done.size * batch / span),
        "train_step_ms_p95": float(np.percentile(intervals_ms, 95)),
        "train_step_ms_p50": float(np.percentile(intervals_ms, 50)),
        "train_step_ms_max": float(intervals_ms.max()),
        "steps": int(done.size),
        "intervals": int(intervals_ms.size),
        "span_s": span,
    }

"""Rate and tail arithmetic on hand-made completion stamps."""

import numpy as np
import pytest

from benchmarks import stats


def _stamps(n, step_s, stall_at=None, stall_s=0.0):
    t, out = 100.0, []
    for i in range(n):
        t += step_s + (stall_s if i == stall_at else 0.0)
        out.append(t)
    return 100.0, out


def test_steady_window():
    first, done = _stamps(200, 0.020)
    m = stats.train_window_metrics(first, done, batch=16)
    assert m["train_samples_per_s"] == pytest.approx(16 / 0.020, rel=1e-9)
    assert m["train_step_ms_p95"] == pytest.approx(20.0, rel=1e-6)
    assert m["steps"] == 200 and m["intervals"] == 199


def test_one_long_stall_lowers_the_rate_and_not_the_p95():
    first, done = _stamps(200, 0.020, stall_at=100, stall_s=1.0)
    m = stats.train_window_metrics(first, done, batch=16)
    # all the work over all the time: the second of stall is in the divisor
    assert m["train_samples_per_s"] == pytest.approx(200 * 16 / (200 * 0.020 + 1.0), rel=1e-9)
    assert m["train_step_ms_p95"] == pytest.approx(20.0, rel=1e-6)   # 1 of 199 is no tail
    assert m["train_step_ms_max"] == pytest.approx(1020.0, rel=1e-6)


def test_the_tail_is_of_all_intervals_not_of_chunk_means():
    rng = np.random.default_rng(0)
    first, done = 0.0, list(np.cumsum(np.where(rng.random(400) < 0.1, 0.060, 0.020)))
    m = stats.train_window_metrics(first, done, batch=1)
    assert m["train_step_ms_p95"] == pytest.approx(60.0, rel=1e-6)   # a tenth stall
    chunk_means = np.diff(done).reshape(-1, 21).mean(axis=1) * 1e3
    assert np.median(chunk_means) < 30.0


def test_too_few_steps():
    with pytest.raises(ValueError):
        stats.train_window_metrics(0.0, [1.0], batch=1)

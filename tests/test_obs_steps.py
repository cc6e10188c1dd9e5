"""obs/steps.py: stall attribution arithmetic on synthetic (fake-clock)
timings + the train-loop integration (trace spans, stall_pct scalar)."""

import json
import os

import pytest

from rt1_tpu.obs import steps as steps_mod
from rt1_tpu.obs import trace
from rt1_tpu.obs.steps import StepTimeline


class FakeClock:
    """Deterministic stand-in for the `time` module inside obs.steps."""

    def __init__(self):
        self.t = 100.0

    def advance(self, seconds):
        self.t += seconds

    def perf_counter(self):
        return self.t


@pytest.fixture
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(steps_mod, "time", c)
    return c


@pytest.fixture(autouse=True)
def _no_global_tracer():
    trace._tracer = None
    yield
    trace._tracer = None


def _fed(clock, dt, items=100):
    """Iterator whose every pull costs `dt` fake seconds."""

    def gen():
        for i in range(items):
            clock.advance(dt)
            yield i

    return gen()


def test_bucket_attribution_and_stall(clock):
    tl = StepTimeline(window=10)
    host_iter = tl.timed(_fed(clock, 0.030))

    tl.start_step(0)
    with tl.phase("h2d", exclusive_of="wait_data"):
        next(host_iter)          # 30 ms -> wait_data, not h2d
        clock.advance(0.010)     # 10 ms -> h2d proper
    with tl.phase("device_step"):
        clock.advance(0.050)     # 50 ms
    clock.advance(0.010)         # 10 ms untracked -> host residual
    rec = tl.end_step()

    assert rec["step"] == 0
    assert rec["wait_data_ms"] == pytest.approx(30.0)
    assert rec["h2d_ms"] == pytest.approx(10.0)
    assert rec["device_step_ms"] == pytest.approx(50.0)
    assert rec["host_ms"] == pytest.approx(10.0)
    assert rec["total_ms"] == pytest.approx(100.0)
    assert rec["stall_pct"] == pytest.approx(40.0)  # (30 + 10) / 100


def test_rolling_window_and_scalars(clock):
    tl = StepTimeline(window=2)
    for step, (wait, dev) in enumerate([(0.08, 0.02), (0.01, 0.09), (0.03, 0.07)]):
        tl.start_step(step)
        tl._add("wait_data", wait)
        with tl.phase("device_step"):
            clock.advance(dev)
        clock.advance(wait)  # wall time must cover the injected wait
        tl.end_step()
    # Window of 2: steps 1 and 2 -> stall = (10 + 30) / 200.
    assert tl.stall_pct == pytest.approx(20.0)
    scalars = tl.scalars()
    assert scalars["stall_pct"] == pytest.approx(20.0)
    assert scalars["timing/wait_data_ms"] == pytest.approx(20.0)
    assert scalars["timing/device_step_ms"] == pytest.approx(80.0)
    assert scalars["timing/total_ms"] == pytest.approx(100.0)
    assert tl.last()["step"] == 2


def test_orphan_time_folds_into_next_step(clock):
    """Bucket time accrued while no step is open (prefetch warm-up pulls,
    out-of-step phases) folds into the next started step, not /dev/null."""
    tl = StepTimeline(window=4)
    host_iter = tl.timed(_fed(clock, 0.020))
    next(host_iter)  # warm-up pull, no open step
    with tl.phase("host"):  # out-of-step phase
        clock.advance(0.005)
    tl.start_step(3)
    clock.advance(0.001)
    rec = tl.end_step()
    assert rec["wait_data_ms"] == pytest.approx(20.0)
    assert rec["host_ms"] == pytest.approx(5.0)


def test_sync_mode_charges_block_to_device_step(clock, monkeypatch):
    tl = StepTimeline(window=4, sync=True)

    class FakeJax:
        @staticmethod
        def block_until_ready(x):
            clock.advance(0.040)

    import sys

    monkeypatch.setitem(sys.modules, "jax", FakeJax)
    tl.start_step(0)
    with tl.phase("device_step"):
        clock.advance(0.010)  # dispatch
    rec = tl.end_step(sync_on=object())
    assert rec["device_step_ms"] == pytest.approx(50.0)


def test_end_step_without_start_raises():
    tl = StepTimeline()
    with pytest.raises(RuntimeError):
        tl.end_step()
    with pytest.raises(ValueError):
        StepTimeline(window=0)


def test_train_loop_emits_trace_and_stall_scalars(tmp_path):
    """Integration: tiny synthetic train run with config.obs.trace=True
    writes a loadable Chrome trace with step spans and keeps the
    flight recorder armed without dumping (clean exit)."""
    from rt1_tpu.train.configs import tiny
    from rt1_tpu.train.train import train_and_evaluate

    config = tiny.get_config()
    config.data.height, config.data.width = 32, 56
    config.num_steps = 3
    config.checkpoint_every_steps = 10
    config.obs.trace = True
    config.obs.stall_window = 2
    workdir = str(tmp_path / "run")
    train_and_evaluate(config, workdir)

    trace_path = os.path.join(workdir, "trace.json")
    with open(trace_path) as f:
        doc = json.load(f)
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    names = {e["name"] for e in spans}
    assert "step" in names
    # dispatch, not device_step: without obs.sync_timing the phase is the
    # host's dispatch of the step and is named for what it is
    assert {"step/h2d", "step/dispatch"} <= names and "step/device_step" not in names
    step_spans = [e for e in spans if e["name"] == "step"]
    assert {e["args"]["step"] for e in step_spans} == {0, 1, 2}
    # Clean exit: no flight-recorder dump.
    assert not os.path.exists(os.path.join(workdir, "flight_record.jsonl"))
    # The global tracer was uninstalled for the next run in this process.
    assert not trace.enabled()

"""obs/goodput.py: run-level wall-time partition under a fake clock.

The contract the tests pin: every second of wall time lands in exactly one
bucket, the fractions sum to exactly 1.0 no matter what sequence of
phases/steps/IO/rollbacks/preemptions occurred, checkpoint I/O inside an
open phase is carved out (not double-counted), what the start-up log measured
as tracing and compiling is `compile` and is carved out of the phase or step
it fell into, and replayed steps are badput — plus the MFU gauge arithmetic
and the summary JSON round-trip.
"""

import json

import pytest

from rt1_tpu.obs.goodput import BUCKETS, GoodputLedger, read_summary


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def advance(self, seconds):
        self.t += seconds

    def __call__(self):
        return self.t


class FakeCompiled:
    """The start-up log's cumulative compile seconds, by hand."""

    def __init__(self):
        self.seconds = 0.0

    def __call__(self):
        return self.seconds


def _step_record(total_ms, wait_ms=0.0, h2d_ms=0.0, compile_ms=0.0):
    return {
        "total_ms": total_ms,
        "wait_data_ms": wait_ms,
        "h2d_ms": h2d_ms,
        "compile_ms": compile_ms,
    }


@pytest.fixture
def clock():
    return FakeClock()


def test_full_run_partition_sums_to_exactly_one(clock):
    compiled = FakeCompiled()
    led = GoodputLedger(clock=clock, compile_seconds=compiled)

    with led.phase("init"):
        clock.advance(10.0)
        led.note_io("ckpt_restore", 4.0)  # restore during init: carved out
    # The first step traces, lowers and compiles for all of its 30 s.
    clock.advance(30.0)
    compiled.seconds += 30.0
    led.note_step(_step_record(30_000.0, compile_ms=30_000.0))
    # Three productive steps, 20% input-stalled each.
    for _ in range(3):
        clock.advance(1.0)
        led.note_step(_step_record(1000.0, wait_ms=150.0, h2d_ms=50.0))
    # A checkpoint save between steps.
    led.note_io("ckpt_save", 2.0)
    clock.advance(2.0)
    # Rollback: two steps replayed wholesale.
    led.mark_rollback()
    for _ in range(2):
        clock.advance(1.0)
        led.note_step(_step_record(1000.0, wait_ms=500.0), replay=True)
    # Preemption drain with a force-save inside (also carved out).
    led.mark_preempted()
    with led.phase("preempt_drain"):
        clock.advance(3.0)
        led.note_io("ckpt_save", 1.0)

    s = led.summary()
    b = s["buckets_s"]
    assert b["init"] == pytest.approx(6.0)  # 10 - 4 stolen by the restore
    assert b["ckpt_restore"] == pytest.approx(4.0)
    assert b["compile"] == pytest.approx(30.0)
    assert b["step"] == pytest.approx(3 * 0.8)
    assert b["data_stall"] == pytest.approx(3 * 0.2)
    assert b["ckpt_save"] == pytest.approx(3.0)  # between-steps + in-drain
    assert b["rollback_replay"] == pytest.approx(2.0)  # stall incl.
    assert b["preempt_drain"] == pytest.approx(2.0)  # 3 - 1 stolen
    # Wall = 48s advanced; attributed = 50 (the note_io 2s save overlapped
    # the between-steps 2s advance only partially in this synthetic
    # schedule) -> denominator max() keeps fractions exact.
    assert sum(s["fractions"].values()) == pytest.approx(1.0, abs=1e-12)
    assert set(s["buckets_s"]) == set(BUCKETS)
    assert s["steps_productive"] == 4  # the first step is one, at 0 s of its own
    assert s["steps_replayed"] == 2
    assert s["rollbacks"] == 1
    assert s["preempted"] is True
    assert s["goodput_pct"] == pytest.approx(
        s["fractions"]["step"] * 100.0
    )
    assert s["badput_pct"] == pytest.approx(100.0 - s["goodput_pct"])


def test_unattributed_absorbs_uninstrumented_time(clock):
    led = GoodputLedger(clock=clock)
    clock.advance(5.0)
    led.note_step(_step_record(1000.0))
    clock.advance(7.0)  # nobody claims this
    s = led.summary()
    assert s["buckets_s"]["unattributed"] == pytest.approx(11.0)
    assert s["wall_s"] == pytest.approx(12.0)
    assert sum(s["fractions"].values()) == pytest.approx(1.0, abs=1e-12)


def test_stall_clamped_to_step_total(clock):
    led = GoodputLedger(clock=clock)
    # Degenerate record (clock jitter): stall claims more than the total.
    led.note_step(_step_record(100.0, wait_ms=80.0, h2d_ms=40.0))
    b = led.summary()["buckets_s"]
    assert b["data_stall"] == pytest.approx(0.1)
    assert b["step"] == pytest.approx(0.0)


def test_open_phase_visible_in_live_snapshot_and_scalars(clock):
    led = GoodputLedger(clock=clock)
    led.open_phase("init")
    clock.advance(4.0)
    # A scrape mid-phase sees the partial accrual (and doesn't close it).
    assert led.summary()["buckets_s"]["init"] == pytest.approx(4.0)
    scalars = led.scalars()
    assert scalars["goodput/init_s"] == pytest.approx(4.0)
    assert scalars["goodput/init_pct"] == pytest.approx(100.0)
    clock.advance(1.0)
    led.close_phase()
    assert led.summary()["buckets_s"]["init"] == pytest.approx(5.0)


def test_phase_misuse_raises(clock):
    led = GoodputLedger(clock=clock)
    with pytest.raises(ValueError):
        led.open_phase("not_a_bucket")
    with pytest.raises(RuntimeError):
        led.close_phase()
    led.open_phase("init")
    with pytest.raises(RuntimeError):
        led.open_phase("compile")


def test_unknown_io_kind_folds_into_ckpt_save(clock):
    led = GoodputLedger(clock=clock)
    led.note_io("mystery", 2.0)
    assert led.summary()["buckets_s"]["ckpt_save"] == pytest.approx(2.0)


def test_mfu_gauge_arithmetic(clock):
    led = GoodputLedger(clock=clock)
    assert led.mfu_pct() is None  # disarmed
    led.set_flops_per_step(1e12, peak_flops=200e12, n_chips=2)
    assert led.mfu_pct() is None  # no productive steps yet
    for _ in range(4):
        clock.advance(0.1)
        led.note_step(_step_record(100.0, wait_ms=50.0))
    # 4 steps x 0.05s productive each ->
    # 1e12 / 0.05 / (200e12 * 2) * 100 = 5.0%.
    assert led.mfu_pct() == pytest.approx(5.0)
    s = led.summary()
    assert s["mfu_pct"] == pytest.approx(5.0)
    assert led.scalars()["goodput/mfu_pct"] == pytest.approx(5.0)
    led.set_flops_per_step(None)
    assert led.mfu_pct() is None  # disarm again


def test_unknown_device_kind_has_no_peak_and_no_mfu(clock, caplog):
    """An unknown device gets no MFU gauge and a warning naming it — never
    a default peak (obs/flops.py); the one known chip has its table row."""
    from rt1_tpu.obs import flops

    assert flops.peak_flops("TPU v5 lite") == 197e12
    with caplog.at_level("WARNING"):
        assert flops.peak_flops("Imaginary TPU v99") is None
    assert "Imaginary TPU v99" in caplog.text
    assert not hasattr(flops, "DEFAULT_PEAK_FLOPS")

    led = GoodputLedger(clock=clock)
    led.set_flops_per_step(
        1e12, peak_flops=flops.peak_flops("Imaginary TPU v99"), n_chips=1
    )
    clock.advance(0.1)
    led.note_step(_step_record(100.0))
    assert led.mfu_pct() is None
    assert "mfu_pct" not in led.summary()
    assert "goodput/mfu_pct" not in led.scalars()


def test_summary_json_roundtrip(tmp_path, clock):
    led = GoodputLedger(clock=clock)
    with led.phase("init"):
        clock.advance(1.0)
    led.note_step(_step_record(500.0))
    path = str(tmp_path / "sub" / "goodput_summary.json")
    assert led.write_summary(path) == path
    loaded = read_summary(path)
    assert loaded == json.loads(json.dumps(led.summary()))
    assert sum(loaded["fractions"].values()) == pytest.approx(1.0)


def test_scalars_render_as_rt1_train_goodput_gauges(clock):
    """The end-to-end naming contract: ledger scalars through the train
    listener's renderer come out as rt1_train_goodput_* gauges."""
    from rt1_tpu.obs.prometheus import render_scalar_gauges

    led = GoodputLedger(clock=clock)
    led.note_step(_step_record(1000.0))
    text = render_scalar_gauges(led.scalars())
    assert "# TYPE rt1_train_goodput_compile_s gauge" in text
    assert "rt1_train_goodput_goodput_pct" in text
    assert "rt1_train_goodput_badput_pct" in text


def test_compile_inside_a_phase_and_between_steps(clock):
    """What was traced inside `init` leaves `init`; what was compiled
    between steps (an eval step's first call) comes out of `unattributed`."""
    compiled = FakeCompiled()
    compiled.seconds = 5.0      # the process compiled before this ledger began
    led = GoodputLedger(clock=clock, compile_seconds=compiled)
    led.open_phase("init")
    clock.advance(8.0)
    compiled.seconds += 3.0
    assert led.summary()["buckets_s"]["init"] == pytest.approx(5.0)  # live
    led.close_phase()
    clock.advance(1.0)
    led.note_step(_step_record(1000.0))
    clock.advance(2.0)
    compiled.seconds += 2.0     # between steps
    b = led.summary()["buckets_s"]
    assert b["init"] == pytest.approx(5.0)
    assert b["compile"] == pytest.approx(5.0)
    assert b["step"] == pytest.approx(1.0)
    assert b["unattributed"] == pytest.approx(0.0)


def test_write_summary_carries_extra_keys(tmp_path, clock):
    led = GoodputLedger(clock=clock, compile_seconds=FakeCompiled())
    path = led.write_summary(str(tmp_path / "g.json"), startup={"totals": {"traces": 3}})
    loaded = read_summary(path)
    assert loaded["startup"] == {"totals": {"traces": 3}}
    assert set(loaded["buckets_s"]) == set(BUCKETS)

"""The trace reduction, second half on hand-made rows with known answers,
first half on a fixture recorded on the chip (three steps of rt1-b3-lt at
batch 16 out of a traced slice, device and annotation rows only, with one of
the profiler's stalls between the first step and the second), and the refusal to invent device numbers from a trace
that has no device plane."""

import os

import pytest

from bench_testlib import DATA, pretend_chip, run_cell, temp_checkout
from benchmarks.trace import reduce, xplane

DEV, HOST = "/device:TPU:0", "/host:CPU"
US = 1000


def _rows():
    ops, mods, notes = reduce.OPS_LINE, reduce.MODULES_LINE, "python"
    return [
        # window: 0 .. 100 us by the annotations
        (HOST, notes, "bench/h2d", 0, 10 * US),
        (HOST, notes, "bench/dispatch", 10 * US, 5 * US),
        (HOST, notes, "bench/sync", 15 * US, 45 * US),
        (HOST, notes, "bench/h2d", 60 * US, 30 * US),
        (HOST, notes, "bench/dispatch", 90 * US, 10 * US),
        # two runs of the step program, 30 us each, and a small other program
        (DEV, mods, "jit_train_step(1)", 20 * US, 30 * US),
        (DEV, mods, "jit_train_step(1)", 60 * US, 30 * US),
        (DEV, mods, "jit_fold_in(2)", 52 * US, 2 * US),
        # ops: busy 20-50 (two overlapping), 52-54, 60-90
        (DEV, ops, "fusion.1", 20 * US, 20 * US),
        (DEV, ops, "convolution.7", 35 * US, 15 * US),
        (DEV, ops, "fusion.9", 52 * US, 2 * US),
        (DEV, ops, "fusion.1", 60 * US, 30 * US),
        # outside the window: clipped away
        (DEV, ops, "fusion.1", 150 * US, 30 * US),
    ]


def test_reduce_hand_made_rows():
    s = reduce.reduce_rows(_rows())
    assert s["window_s"] == pytest.approx(100e-6)
    assert s["busy_s"] == pytest.approx(62e-6)          # 30 + 2 + 30, overlap once
    assert s["idle_share"] == pytest.approx(0.38)
    assert s["programs"]["jit_train_step(1)"] == {"count": 2, "seconds": pytest.approx(60e-6)}
    name, program = reduce.step_program(s)
    assert name == "jit_train_step(1)" and program["count"] == 2
    assert s["device_ops"][0] == ["fusion.1", pytest.approx(50e-6)]
    assert s["device_ops"][1] == ["convolution.7", pytest.approx(15e-6)]
    # gaps: 0-20 (h2d 10, dispatch 5, sync 5 -> h2d), 50-52 and 54-60 (sync), 90-100 (dispatch)
    gaps = dict(s["idle_gaps"])
    assert gaps["bench/h2d"] == pytest.approx(20e-6)
    assert gaps["bench/sync"] == pytest.approx(8e-6)
    assert gaps["bench/dispatch"] == pytest.approx(10e-6)
    assert s["longest_gap_s"] == pytest.approx(20e-6)
    # 20-50 and 60-90 are inside the step program and 52-54 inside the other:
    # all busy, so no program waited on itself
    assert s["in_program_idle_s"] == pytest.approx(0.0)
    assert s["queued_stalls"]["count"] == 0 and s["slice_s"] == s["window_s"]


def _rows_with_a_gap(under):
    """Two runs of a 30 us step, 200 us apart, the host under ``under``."""
    ops, mods, notes = reduce.OPS_LINE, reduce.MODULES_LINE, "python"
    return [
        (HOST, notes, "bench/dispatch", 0, 10 * US),
        (HOST, notes, under, 10 * US, 228 * US),
        (HOST, notes, "bench/dispatch", 238 * US, 2 * US),
        (HOST, notes, "bench/sync", 240 * US, 30 * US),
        (DEV, mods, "jit_train_step(1)", 10 * US, 30 * US),
        (DEV, mods, "jit_train_step(1)", 240 * US, 30 * US),
        (DEV, ops, "fusion.1", 10 * US, 30 * US),
        (DEV, ops, "fusion.1", 240 * US, 30 * US),
    ]


def test_a_stall_with_a_step_queued_is_taken_out_and_a_host_stall_is_not():
    """Longer than a step and under bench/sync: the host was waiting for the
    device, so the loop did not cause it.  Under the feeder it is the loop's."""
    s = reduce.reduce_rows(_rows_with_a_gap("bench/sync"))
    assert s["queued_stalls"] == {"count": 1, "seconds": pytest.approx(200e-6),
                                  "longest_s": pytest.approx(200e-6)}
    assert s["slice_s"] == pytest.approx(270e-6) and s["window_s"] == pytest.approx(70e-6)
    assert s["busy_s"] == pytest.approx(60e-6)
    assert [reduce.REMOVED, pytest.approx(200e-6)] in s["idle_gaps"]
    assert s["longest_gap_s"] == pytest.approx(10e-6)
    host = reduce.reduce_rows(_rows_with_a_gap("bench/next_batch"))
    assert host["queued_stalls"]["count"] == 0
    assert host["window_s"] == pytest.approx(270e-6)
    assert dict(host["idle_gaps"])["bench/next_batch"] == pytest.approx(200e-6)


def test_device_idle_is_the_step_program_against_the_untraced_interval():
    from benchmarks import run
    from bench_testlib import REPO

    s = reduce.reduce_rows(_rows_with_a_gap("bench/sync"))
    s["untraced"] = {"steps": 10, "seconds": 10 * 40e-6, "feeder_wait_s": 0.0,
                     "feeder_calls": 10, "h2d_s": 0.0}
    lines = []
    value = run.metric_reader(REPO, "device_idle_pct.train").read({"trace": s, "log": lines.append})
    assert value == pytest.approx(25.0)          # 30 us of program in every 40 us
    assert "1 stall(s)" in lines[0]


def test_union_and_no_device_plane():
    assert reduce.union([(5, 7), (0, 3), (2, 4), (7, 9)]) == [(0, 4), (5, 9)]
    host_only = [r for r in _rows() if r[0] == HOST]
    with pytest.raises(reduce.NoDevicePlane):
        reduce.reduce_rows(host_only)
    with pytest.raises(ValueError):
        reduce.reduce_rows([r for r in _rows() if r[0] == DEV])


FIXTURE = os.path.join(DATA, "rows_rt1_tpu_v5e.json.gz")


def test_rows_recorded_on_the_chip():
    rows = xplane.load_rows(FIXTURE)
    assert os.path.getsize(FIXTURE) < 1 << 20
    assert {r[1] for r in rows if xplane.is_device_plane(r[0])} >= {
        reduce.OPS_LINE, reduce.MODULES_LINE}
    s = reduce.reduce_rows(rows)
    name, program = reduce.step_program(s)
    assert "train_step" in name and program["count"] >= 1
    assert 0.0 < s["busy_s"] <= s["window_s"]
    # the 1.62 s in which the second step sat queued are out of the window
    assert s["queued_stalls"]["count"] == 1
    assert s["queued_stalls"]["seconds"] == pytest.approx(1.6197, abs=1e-3)
    assert s["idle_share"] < 0.01 and s["slice_s"] - s["window_s"] > 1.6
    assert s["device_ops"] and s["device_ops"][0][1] > 0
    assert all(k.startswith("bench/") or k == reduce.REMOVED for k, _ in s["idle_gaps"])


def test_a_trace_without_a_device_plane_gives_no_numbers(tmp_path, monkeypatch):
    """The CPU's trace has host planes only: the traced run must fail."""
    pretend_chip(monkeypatch)
    # long enough for the slice to begin on a loaded test machine (a step a second)
    rc, line, text = run_cell(temp_checkout(tmp_path), "small.pool", seconds=8, trace=1)
    assert rc != 0 and line is None and text == ""

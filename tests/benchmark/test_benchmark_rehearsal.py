"""The whole command on the CPU at rehearsal sizes (tests/benchmark/data),
through both traffic mixes and both families: the last line has the
contract's keys, nothing is measured without a chip, and nothing is printed where there is no chip."""

import pytest

from bench_testlib import pretend_chip, run_cell, temp_checkout

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_no_accelerator_no_result(tmp_path):
    rc, line, text = run_cell(temp_checkout(tmp_path), "small.pool")
    assert rc != 0 and line is None and text == ""


@pytest.mark.parametrize("cell", ["small.packed", "small.pool"])
def test_rehearsal_prints_the_contracts_line(tmp_path, monkeypatch, cell):
    pretend_chip(monkeypatch)
    rc, line, _ = run_cell(temp_checkout(tmp_path), cell)
    assert rc == 0
    assert KEYS <= set(line) and list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s", "train_step_ms_p95"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    for number in line["compared"].values():
        assert number["value"] <= number["limit"]

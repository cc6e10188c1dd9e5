"""BENCHMARK.json against the contract's rules that a test can hold, and the
harness's promise that a cell is data only."""

import json
import os
import re
import shutil

import pytest

from bench_testlib import REPO, manifest, temp_checkout

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
M = manifest()
METRICS = M["end_to_end"] + M["per_layer"]


def _names():
    out = [c["name"] for c in M["configs"]] + [m["name"] for m in METRICS]
    for w in M["workloads"]:
        out += [w["name"], w["config"], w["traffic"]]
    return sorted(set(out))


@pytest.mark.parametrize("name", _names())
def test_name_is_legal(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if metric in M["end_to_end"]:
        assert 0 < metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
        assert set(metric) <= allowed | {"bound"}
    else:
        assert set(metric) <= allowed | {"layer", "moves"}
        reported = {m["name"]: m for m in M["end_to_end"]}
        assert metric["moves"] in reported
        cells = metric.get("workloads", [w["name"] for w in M["workloads"]])
        moved = reported[metric["moves"]]
        for cell in cells:      # each of its cells reports the metric it moves
            assert cell in moved.get("workloads", [w["name"] for w in M["workloads"]])
        if "roofline" in metric["name"] or "mfu" in re.split(r"[_.]", metric["name"]):
            assert metric["unit"] == "%"


def test_manifest_shape():
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in M["end_to_end"])
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in M["workloads"]}
    assert used == {c["name"] for c in M["configs"]}
    assert sum(w["chips"] == 4 for w in M["workloads"]) <= max(1, len(M["workloads"]) // 4)
    for text in [w["why"] for w in M["workloads"]] + [c["why"] for c in M["configs"]]:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_resolves_to_files(cell):
    from benchmarks import run

    config = os.path.join(REPO, run.config_path(M, cell["config"]))
    with open(config) as f:
        cf = json.load(f)
    assert cf["flops_per_sample"] > 0 and cf["min_bytes_per_step"] > 0
    from benchmarks import check

    assert cf["limits"] and set(cf["limits"]) <= set(check.NUMBERS)
    assert os.path.exists(os.path.join(REPO, "benchmarks", "references", cf["reference"] + ".py"))
    with open(os.path.join(REPO, "benchmarks", "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    assert os.path.exists(os.path.join(REPO, "benchmarks", "drivers", mix["kind"] + ".py"))
    per_layer = run.cell_metrics(M, "per_layer", cell["name"])
    assert per_layer and len(run.cell_metrics(M, "end_to_end", cell["name"])) >= 2
    for metric in per_layer:
        assert callable(run.metric_reader(REPO, metric["name"]).read)


@pytest.mark.parametrize("config,mix", [("rt1-b3-lt", "train-pool")])
def test_a_new_cell_is_one_workloads_entry(tmp_path, config, mix):
    """Nothing but the entry is added: the configuration, the mix, the driver
    and every metric without a ``workloads`` key are found by name."""
    from benchmarks import program, run, traffic

    name = f"{config}.{mix}"
    if any(w["name"] == name for w in M["workloads"]):
        pytest.skip("the cell is in the manifest already")
    entry = {"name": name, "config": config, "traffic": mix, "chips": 1, "why": "added by a test"}
    root = temp_checkout(tmp_path, extra_workloads=[entry])
    m = run.load_manifest(root)
    cell = run.find_cell(m, name)
    cf = program.load_config_file(os.path.join(root, run.config_path(m, cell["config"])))
    assert program.program_config(cf).per_host_batch_size == cf["overrides"]["per_host_batch_size"]
    assert traffic.load_traffic_file(
        os.path.join(root, "benchmarks", "traffic", mix + ".json"))["kind"] == "train"
    assert {x["name"] for x in run.cell_metrics(m, "end_to_end", name)} >= {
        "setup_s", "train_samples_per_s"}
    assert len(run.cell_metrics(m, "per_layer", name)) == len(
        [x for x in m["per_layer"] if "workloads" not in x])


def test_a_new_configuration_lands_as_additions(tmp_path):
    """A configuration (a renamed copy of a file that is there), its cell and a
    per-layer entry of its own, appended: nothing that was there is edited, the
    cell resolves, it reports every entry without a list and its own, and every
    other cell reports what it reported."""
    from benchmarks import program, run

    config, mix = "added-config", "train-tokens-8k"
    cell, metric = f"{config}.{mix}", "added_ms.train"
    root = temp_checkout(tmp_path)
    configs = os.path.join(root, "benchmarks", "configs")
    shutil.copy(os.path.join(configs, "lfm2-24b-a2b.json"), os.path.join(configs, config + ".json"))
    metrics = os.path.join(root, "benchmarks", "metrics")
    shutil.copy(os.path.join(metrics, "moe_ms.train.py"), os.path.join(metrics, metric + ".py"))
    path = os.path.join(root, "BENCHMARK.json")
    before, m = run.load_manifest(root), run.load_manifest(root)
    m["configs"].append({"name": config, "source": "added by a test", "reduced": [],
                         "file": f"benchmarks/configs/{config}.json", "why": "added by a test"})
    m["workloads"].append({"name": cell, "config": config, "traffic": mix, "chips": 1,
                           "why": "added by a test"})
    m["per_layer"].append({"name": metric, "unit": "ms", "better": "lower",
                           "source": "device_trace", "layer": "Model step",
                           "moves": "train_samples_per_s", "workloads": [cell]})
    with open(path, "w") as f:
        json.dump(m, f)

    m = run.load_manifest(root)
    entry = run.find_cell(m, cell)
    cf = program.load_config_file(os.path.join(root, run.config_path(m, entry["config"])))
    assert program.program_config(cf).model.family == "lfm2_moe"
    assert os.path.exists(os.path.join(root, "benchmarks", "traffic", mix + ".json"))
    unlisted = {x["name"] for x in m["per_layer"] if "workloads" not in x}
    assert {"train_mfu_pct", "setup_build_s", "setup_cache_misses"} <= unlisted
    mine = run.cell_metrics(m, "per_layer", cell)
    assert {x["name"] for x in mine} == unlisted | {metric}
    for x in mine:
        assert callable(run.metric_reader(root, x["name"]).read)
    assert {x["name"] for x in run.cell_metrics(m, "end_to_end", cell)} >= {
        "setup_s", "train_samples_per_s"}
    for other in before["workloads"]:
        for group in ("per_layer", "end_to_end"):
            assert run.cell_metrics(m, group, other["name"]) == run.cell_metrics(
                before, group, other["name"]), other["name"]


def test_peaks_know_the_chip_and_nothing_else():
    from benchmarks import devices

    peak = devices.peaks(REPO, "TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        devices.peaks(REPO, "TPU v9 imaginary")
    with pytest.raises(KeyError):
        devices.peaks(REPO, "_source")

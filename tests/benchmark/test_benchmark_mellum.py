"""``mellum2-12b-a2.5b`` in the harness, on the CPU at a rehearsal size
(data/mellum-small-test.json, data/train-tokens-test.json): the configuration
and its mix load, the window loop prints the contract's line, ``correct``
passes for the sound program and fails for the int8 control, for the two
controls that take one of the configuration's mechanisms away (the window, the
full layers' YaRN) and for planted faults; the yardstick against the frozen
counts and ISSUE 31's arithmetic; the three readers on a hand-made reading: a
number where the program has the scope, ``None`` where it has not (as the
parent commit has not)."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from bench_testlib import (REPO, add_rehearsal_cell, manifest, pretend_chip, run_cell,
                           temp_checkout)
from benchmarks import flops_lm_mixed, program, run, traffic

CONFIG = "mellum2-12b-a2.5b"
CELL = CONFIG + ".train-tokens-16k"
NEW_READERS = ("attention_ms.train", "attention_masked_roofline", "lm_loss_ms.train")


def mellum_checkout(tmp_path):
    """bench_testlib's temporary checkout with the mellum rehearsal cell."""
    return add_rehearsal_cell(temp_checkout(tmp_path), "small.mellum", "mellum-small-test")


def config_file():
    return program.load_config_file(os.path.join(REPO, "benchmarks", "configs", CONFIG + ".json"))


def test_the_configuration_and_its_mix_load():
    m = manifest()
    cell = run.find_cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-tokens-16k", 1)
    assert run.config_path(m, CONFIG) == "benchmarks/configs/" + CONFIG + ".json"
    cf = config_file()
    config = program.program_config(cf)
    lm = config.model.lm
    assert config.model.family == "mellum" and config.per_host_batch_size == 1
    # every published width as published; the cut is depth, experts held, vocabulary held
    assert (lm.hidden_size, lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim,
            lm.moe_intermediate_size, lm.num_experts, lm.num_experts_per_tok,
            lm.sliding_window) == (2304, 32, 4, 128, 896, 64, 8, 1024)
    assert tuple(lm.layer_types) == ("sliding_attention",) * 3 + ("full_attention",)
    assert tuple(lm.experts_held) == (0, 16) and lm.vocab_held == 24576
    assert lm.rope_parameters.full_attention.to_dict() == {
        k: v for k, v in cf["rope_parameters"]["full_attention"].items()}
    assert lm.rope_parameters.sliding_attention.rope_type == "default"
    for key in ("published", "deployment", "reduced_how", "assumed", "limits", "limits_from"):
        assert cf[key], key
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cf["reduced_how"])
    mix = traffic.load_traffic_file(
        os.path.join(REPO, "benchmarks", "traffic", "train-tokens-16k.json"))
    assert (mix["kind"], mix["seq_len"], mix["corpus"]["doc_len_median"]) == (
        "train_tokens", 16384, 2048)
    assert lm.seq_len == mix["seq_len"]


def test_the_frozen_counts_are_the_yardsticks():
    cf = config_file()
    y = flops_lm_mixed.yardstick(cf)
    assert cf["flops_per_sample"] == pytest.approx(y["flops_per_sample"], rel=1e-9)
    assert cf["min_bytes_per_step"] == pytest.approx(y["min_bytes_per_step"], rel=1e-9)
    lm = flops_lm_mixed.lm_sizes(cf["overrides"])
    # ISSUE 31's arithmetic, Tentpole section 2
    parts = flops_lm_mixed.parameters(lm)
    assert parts["attention_mixer"] == 21233664 + 256
    assert parts["routed_ffn"] == 99090432 + 147456
    assert parts["layer"] == 120476416 and parts["embedding_and_head"] == 113246208
    assert y["parameters"] == 595154176
    forward = flops_lm_mixed.forward_flops_per_token(lm, 16384)
    assert forward["attention_projections"] == 42467328
    assert forward["attention_scores_full"] == 134217728
    assert forward["attention_scores_sliding"] == 16253440      # 992.03 keys a query
    assert forward["experts"] == 24772608 and forward["head"] == 113246208
    assert y["flops_per_sample"] == pytest.approx(27.84e12, rel=1e-3)
    # the kernels' count follows the masks: a full layer is 8.26 sliding ones
    flops, nbytes = flops_lm_mixed.attention_cost(1, 16384, lm)
    pairs = 16384 * 16384 / 2 + 3 * (16384 * 1024 - 1024 * 1023 / 2)
    assert flops == pytest.approx(3 * 2 * 2 * 32 * 128 * pairs)
    assert flops == pytest.approx(8.99e12, rel=2e-3)
    assert nbytes == 4 * 2 * 16384 * 128 * (2 * 32 + 2 * 4) * 2
    assert flops_lm_mixed.pairs_kept("sliding_attention", 512, 1024) == 512 * 513 / 2
    with pytest.raises(ValueError, match="no attention mask"):
        flops_lm_mixed.pairs_kept("conv", 512, 1024)


def _reading(program_summary, counters):
    lines = []
    return {"trace": {"program": program_summary, "counters": counters},
            "config_file": config_file(), "batch": 1, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "log": lines.append}


def test_every_new_reader_is_in_the_manifest_for_the_cell_alone():
    by_name = {m["name"]: m for m in manifest()["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["source"] == "device_trace"
    # of the five that list the lfm2 token cell, the routed layers' three list
    # this cell too since PR 33 (the same routed layer, PERF.md section 3)
    for name in ("moe_ms.train", "moe_experts_roofline", "moe_assignments_held_share.train"):
        assert by_name[name]["workloads"] == ["lfm2-24b-a2b.train-tokens-8k", CELL]
    for name in ("mixer_ms.train", "attention_roofline"):
        assert by_name[name]["workloads"] == ["lfm2-24b-a2b.train-tokens-8k"]


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_reads_a_number_or_nothing(name):
    read = run.metric_reader(REPO, name).read
    scope_s = {"attention": 0.030, "attention_kernel": 0.090, "lm_loss": 0.040}
    counters = {"routed_layers": 4, "attention_layers": 1, "seq_len": 16384}
    value = read(_reading({"scope_s": scope_s}, counters))
    assert value is not None and value > 0
    if name == "attention_ms.train":
        assert value == pytest.approx(120.0)
    if name == "lm_loss_ms.train":
        assert value == pytest.approx(40.0)
    if name == "attention_masked_roofline":     # 8.99 TFLOP at 197e12 = 45.6 ms of 90
        assert value == pytest.approx(50.7, abs=0.2)
    # a program without the scopes (the parent), a driver without the reduction
    assert read(_reading({"scope_s": {"optimizer": 0.007}}, counters)) is None
    assert read(_reading(None, {})) is None


def test_rehearsal_prints_the_contracts_line(tmp_path, monkeypatch):
    pretend_chip(monkeypatch)
    rc, line, _ = run_cell(mellum_checkout(tmp_path), "small.mellum")
    assert rc == 0
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s", "train_step_ms_p95"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert list(line)[-1] == "compared" and all(
        n["value"] <= n["limit"] for n in line["compared"].values())


def _readings(root, *options):
    from benchmarks import readings_controls

    out = io.StringIO()
    with redirect_stdout(out):
        rc = readings_controls.main(["small.mellum", "3000000019", *options], root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_controls_are_not_correct_and_the_program_is(tmp_path, monkeypatch):
    """Three Adam steps through make_train_step_fns against check.follow; the
    reference one precision down, with the window taken away, and with the
    full layers' YaRN replaced by the default rotary, each in the program's
    place, fails ``check.judge``."""
    pretend_chip(monkeypatch)
    out = _readings(mellum_checkout(tmp_path), "--control", "int8", "--control", "no_window",
                    "--control", "default_rotary", "--assignments")
    assert out["program"]["correct"] is True, out["program"]
    assert out["skips"] == 0
    assert set(out["controls"]) == {"int8", "no_window", "default_rotary"}
    for name, control in out["controls"].items():
        assert control["correct"] is False and control["over"], name
    assert all(c["attention/window_layers"] == 3.0 and c["moe/fallback_layers"] == 0.0
               for c in out["counters"])
    # float32 on both sides: the same experts for every token
    differing = out["assignments_differing"]
    assert 0 < differing["of"] <= 8 * 64 * 4 and sum(differing["by_routed_layer"]) == 0


@pytest.mark.parametrize("fault", ["half_targets", "one_leaf"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    pretend_chip(monkeypatch)
    out = _readings(mellum_checkout(tmp_path), "--fault", fault)
    assert out["program"]["correct"] is False and out["program"]["over"]
    if fault == "one_leaf":     # no median, no percentile sees one leaf of 51
        assert out["program"]["over"] == ["change_3_worst_ratio"]


def test_a_control_the_configuration_does_not_name_is_refused(tmp_path, monkeypatch):
    pretend_chip(monkeypatch)
    from benchmarks import readings_controls

    with pytest.raises(SystemExit, match="no control 'fp8'"):
        readings_controls.main(["small.mellum", "1", "--control", "fp8"],
                               root=mellum_checkout(tmp_path))

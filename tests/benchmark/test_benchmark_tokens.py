"""``kind: train_tokens`` on the CPU at a rehearsal size
(data/lfm2-small-test.json, data/train-tokens-test.json): the window loop and
its result line, ``correct`` passing for the sound program and failing for the
control and for planted faults, the yardstick against the configuration's
frozen counts, the scope rules, and every reader of the cell on a hand-made
reading: a number where the program has the scope or the counter, ``None``
where it has not (as the parent commit has not)."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from bench_testlib import (REPO, add_rehearsal_cell, manifest, pretend_chip, run_cell,
                           temp_checkout)
from benchmarks import flops_lm, run
from benchmarks.trace import program as trace_program

CELL = "lfm2-24b-a2b.train-tokens-8k"
MELLUM_CELL = "mellum2-12b-a2.5b.train-tokens-16k"
NEW_READERS = ("moe_ms.train", "mixer_ms.train", "moe_experts_roofline", "attention_roofline",
               "moe_assignments_held_share.train")


def tokens_checkout(tmp_path):
    """bench_testlib's temporary checkout with the token rehearsal cell."""
    return add_rehearsal_cell(temp_checkout(tmp_path), "small.tokens", "lfm2-small-test")


def config_file():
    with open(os.path.join(REPO, "benchmarks", "configs", "lfm2-24b-a2b.json")) as f:
        return json.load(f)


def test_rehearsal_prints_the_contracts_line(tmp_path, monkeypatch):
    pretend_chip(monkeypatch)
    rc, line, _ = run_cell(tokens_checkout(tmp_path), "small.tokens")
    assert rc == 0
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert list(line)[-1] == "compared"
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s", "train_step_ms_p95"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert line["compared"] and all(
        n["value"] <= n["limit"] for n in line["compared"].values())


def _readings(root, *options):
    from benchmarks import readings_tokens

    out = io.StringIO()
    with redirect_stdout(out):
        rc = readings_tokens.main(["small.tokens", "3000000019", *options], root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_control_is_not_correct_and_the_program_is(tmp_path, monkeypatch):
    """Three Adam steps through make_train_step_fns against check.follow; the
    reference one precision down, in the program's place, fails."""
    pretend_chip(monkeypatch)
    out = _readings(tokens_checkout(tmp_path), "--control", "--assignments")
    assert out["program"]["correct"] is True, out["program"]
    assert out["skips"] == 0
    assert out["control"]["correct"] is False and out["control"]["over"]
    # float32 on both sides: the same experts for every token
    differing = out["assignments_differing"]
    assert 0 < differing["of"] <= 8 * 64 * 4 and sum(differing["by_routed_layer"]) == 0


@pytest.mark.parametrize("fault", ["half_batch", "one_leaf"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    pretend_chip(monkeypatch)
    out = _readings(tokens_checkout(tmp_path), "--fault", fault)
    assert out["program"]["correct"] is False and out["program"]["over"]
    if fault == "one_leaf":     # no median, no percentile sees one leaf of 53
        assert out["program"]["over"] == ["change_3_worst_ratio"]


def test_the_frozen_counts_are_the_yardsticks():
    cf = config_file()
    y = flops_lm.yardstick(cf)
    assert cf["flops_per_sample"] == pytest.approx(y["flops_per_sample"], rel=1e-9)
    assert cf["min_bytes_per_step"] == pytest.approx(y["min_bytes_per_step"], rel=1e-9)
    # ISSUE 27's arithmetic: 469.3 M parameters, ~406 MFLOP forward a token
    assert y["parameters"] == pytest.approx(469.3e6, rel=2e-3)
    assert y["forward_flops_per_token"] == pytest.approx(406e6, rel=0.02)
    lm = flops_lm.lm_sizes(cf["overrides"])
    one, _ = flops_lm.experts_cost(1024.0, 4, lm)
    two, _ = flops_lm.experts_cost(2048.0, 4, lm)
    assert two == pytest.approx(2 * one)        # the work follows the rows computed
    flops, _ = flops_lm.attention_cost(2, 8192, 1, lm)
    assert flops == pytest.approx(3 * 2 * 2 * 2 * 32 * 8192 * 8192 * 64 / 2)


@pytest.mark.parametrize("scope,group", [
    ("jit(train_step)/jvp(DecoderLM)/layer_2/ffn/moe/experts/gmm", "moe_experts"),
    ("jit(train_step)/transpose(jvp(DecoderLM))/layer_2/ffn/moe/dispatch/gather", "moe_dispatch"),
    ("jit(train_step)/jvp(DecoderLM)/layer_1/mixer/attention/kernel/flash", "attention_kernel"),
    ("jit(train_step)/jvp(DecoderLM)/layer_1/mixer/attention/q_proj/dot_general", "attention"),
    ("jit(train_step)/jvp(DecoderLM)/layer_0/mixer/shortconv/in_proj/dot_general", "shortconv"),
    ("jit(train_step)/jvp(DecoderLM)/layer_0/ffn/dense_ffn/w1/dot_general", "dense_ffn"),
    ("jit(train_step)/jvp(DecoderLM)/lm_loss/reduce_max", "lm_loss"),
    ("jit(train_step)/optimizer/add", "optimizer"),
    ("jit(train_step)/health/reduce_sum", "health"),
    ("jit(train_step)/jvp(DecoderLM)/layer_3/ffn_norm/mul", "norms"),
])
def test_scope_rules_of_the_token_step(scope, group):
    from benchmarks.drivers import train_tokens

    rules = trace_program.load_rules(train_tokens.SCOPE_RULES)
    assert trace_program.group_of(scope, rules) == group


def _reading(program, counters):
    lines = []
    return {"trace": {"program": program, "counters": counters},
            "config_file": config_file(), "batch": 2, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "log": lines.append}


def test_every_new_reader_is_in_the_manifest_for_the_cell_alone():
    by_name = {m["name"]: m for m in manifest()["per_layer"]}
    # since PR 33 the routed layers' three also list mellum's cell, whose
    # routed layers are the same code; the mixers' two stay this cell's alone
    for name in NEW_READERS:
        shared = name.startswith("moe_")
        assert by_name[name]["workloads"] == [CELL] + [MELLUM_CELL] * shared
        assert by_name[name]["moves"] == "train_samples_per_s"


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_reads_a_number_or_nothing(name):
    read = run.metric_reader(REPO, name).read
    scope_s = {"moe_router": 0.004, "moe_dispatch": 0.010, "moe_experts": 0.012,
               "moe_combine": 0.008, "shortconv": 0.040, "attention": 0.010,
               "attention_kernel": 0.047}
    counters = {"routed_layers": 4, "attention_layers": 1, "seq_len": 8192,
                "assignments_total": 2 * 8192 * 4 * 4.0, "moe/assignments_held": 32768.0,
                "moe/load_max_over_mean": 1.1}
    value = read(_reading({"scope_s": scope_s}, counters))
    assert value is not None and value > 0
    if name.endswith("roofline"):
        assert value < 100
    if name == "moe_assignments_held_share.train":
        assert value == pytest.approx(0.125)
    if name == "moe_ms.train":
        assert value == pytest.approx(34.0)
    # a program without the scopes and the counters (the parent commit), or a
    # driver that hands no scope reduction over: nothing, and no exception
    assert read(_reading(None, None)) is None
    assert read(_reading({"scope_s": {k: 0.0 for k in scope_s}},
                         dict(counters, **{"moe/assignments_held": 0.0}))) is None
    assert read({"trace": {}, "config_file": config_file(), "batch": 2, "chips": 1,
                 "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
                 "log": print}) is None

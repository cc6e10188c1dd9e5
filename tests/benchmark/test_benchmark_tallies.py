"""Scope tallies and counters that a configuration's file names
(``scope_tallies``, ``counters``; drivers/train_tokens.py says what they are).

Without the keys the reduction is the parent commit's, to the last digit: on
the step recorded on the chip (data/program_rt1_tpu_v5e.json.gz) and on
hand-made events that carry the token step's scope names (no token-family
profile is recorded here).  With them the family's groups still partition the
step, a tally counts every op whose scope holds its pattern, and the two
attention tallies of mellum2-12b-a2.5b.json add up to ``attention_kernel``.
A counter is read by its name; one the step does not return is a fault of the
run.  And a configuration that is only data (data/dry-tally-test.json with two
readers of its own under data/metrics/) goes through the rehearsal and the
readers with no file under benchmarks/ edited."""

import json
import os
import shutil
from collections import defaultdict

import pytest

from bench_testlib import (DATA, REPO, add_rehearsal_cell, manifest, pretend_chip, run_cell,
                           temp_checkout)
from benchmarks import program, run
from benchmarks.drivers import train_tokens
from benchmarks.trace import program as trace_program
from benchmarks.trace import reduce

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = reduce.OPS_LINE + "#3", reduce.MODULES_LINE + "#2"
US = 1000
FWD = "jit(train_step_guarded)/jvp(DecoderLM)/"
BWD = "jit(train_step_guarded)/transpose(jvp(DecoderLM))/"
MELLUM = "mellum2-12b-a2.5b"
NEW_READERS = ("attention_window_ms.train", "attention_full_ms.train",
               "moe_fallback_share.train")

TOKEN_SCOPES = {
    "fusion.1": FWD + "layer_0/mixer/attention/kernel/window/splash_mha_fwd_residuals:",
    "fusion.2": BWD + "layer_0/mixer/attention/kernel/window/splash_mha_dkv_no_residuals:",
    "fusion.3": FWD + "layer_3/mixer/attention/kernel/full/splash_mha_fwd_residuals:",
    "fusion.4": FWD + "layer_3/mixer/attention/q_proj/dot_general:",
    "fusion.5": FWD + "layer_3/ffn/moe/experts/gmm:",
    "fusion.6": BWD + "layer_0/ffn/moe/dispatch/gather:",
    "fusion.7": "jit(train_step_guarded)/optimizer/add:",
    "while.1": FWD + "lm_loss/while:",
    "fusion.8": FWD + "lm_loss/while/body/dot_general:",
    "fusion.9": BWD + "layer_3/mixer/attention/kernel/full/reduce_sum:",
}


def token_events():
    """Two whole runs of a token step, 200 us each, busy end to end."""
    ev = lambda line, name, start, dur: (DEV, line, name, start * US, dur * US, {})  # noqa: E731
    events = [(HOST, "python3#9", "bench/sync", 0, 500 * US, {})]
    for t in (10, 260):
        events += [
            ev(MODS, "jit_train_step_guarded(7)", t, 200),
            ev(OPS, "fusion.1", t, 10), ev(OPS, "fusion.2", t + 10, 25),
            ev(OPS, "fusion.3", t + 35, 40), ev(OPS, "fusion.4", t + 75, 15),
            ev(OPS, "fusion.5", t + 90, 30), ev(OPS, "fusion.6", t + 120, 20),
            ev(OPS, "fusion.7", t + 140, 10),
            ev(OPS, "while.1", t + 150, 30), ev(OPS, "fusion.8", t + 155, 20),
            ev(OPS, "copy.1", t + 180, 5), ev(OPS, "fusion.9", t + 185, 15),
        ]
    return events, TOKEN_SCOPES


def recorded_rt1():
    return trace_program.load_events(os.path.join(DATA, "program_rt1_tpu_v5e.json.gz"))


def config_file(name=MELLUM):
    folder = os.path.join(REPO, "benchmarks", "configs") if name == MELLUM else DATA
    return program.load_config_file(os.path.join(folder, name + ".json"))


def scope_s_as_the_parent_reduced(events, scopes, rules):
    """The parent commit's accumulation, kept as the plain loop it was: every
    op event of a whole run of the step program to the first group whose rule
    matches its scope."""
    device = [e for e in events if e[0] == DEV]
    ops = [e for e in device if e[1].split("#")[0] == reduce.OPS_LINE]
    window = trace_program._window(events)
    runs_of = defaultdict(list)
    for e in device:
        if e[1].split("#")[0] == reduce.MODULES_LINE and (
                window is None or (window[0] <= e[3] and e[3] + e[4] <= window[1])):
            runs_of[e[2]].append(e)
    runs = sorted(max(runs_of.values(), key=lambda r: sum(e[4] for e in r)), key=lambda e: e[3])
    bounds = [(r[3], r[3] + r[4]) for r in runs]
    by_group = defaultdict(int)
    for e, self_ns in trace_program.self_times(ops):
        if any(lo <= e[3] < hi for lo, hi in bounds):
            by_group[trace_program.group_of(scopes.get(e[2]), rules)] += self_ns
    return {g: by_group.get(g, 0) / len(runs) / 1e9 for g in trace_program.group_names(rules)}


CASES = {
    "recorded_rt1": (recorded_rt1, trace_program.RULES_FILE,
                     [{"group": "block_2", "pattern": "/block_2/", "why": "one block"},
                      {"group": "depthwise", "pattern": "/depthwise/", "why": "one kind of conv"},
                      {"group": "nowhere", "pattern": "/no_such_scope/", "why": "matches no op"}]),
    "token_step": (token_events, train_tokens.SCOPE_RULES, config_file()["scope_tallies"]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_without_the_keys_the_reduction_is_the_parents(case):
    make, rules_file, _ = CASES[case]
    events, scopes = make()
    rules = trace_program.load_rules(rules_file)
    out = trace_program.reduce_events(events, scopes, rules)
    assert "tally_s" not in out
    assert out["scope_s"] == scope_s_as_the_parent_reduced(events, scopes, rules)
    assert sum(out["scope_s"].values()) == pytest.approx(out["op_self_s"], rel=1e-12)
    assert not any("tally" in line for line in trace_program.describe(out))


@pytest.mark.parametrize("case", sorted(CASES))
def test_tallies_are_counted_beside_the_partition(case):
    make, rules_file, tallies = CASES[case]
    events, scopes = make()
    rules = trace_program.load_rules(rules_file)
    plain = trace_program.reduce_events(events, scopes, rules)
    out = trace_program.reduce_events(events, scopes, rules, trace_program.compile_rules(tallies))
    assert list(out["tally_s"]) == [t["group"] for t in tallies]
    for key in ("scope_s", "op_self_s", "step_s", "top_ops", "runs", "step_program"):
        assert out[key] == plain[key], key          # to the last digit
    if case == "recorded_rt1":
        t = out["tally_s"]
        # block 2's depthwise convolution is in both tallies and in two groups
        assert 0 < t["block_2"] < t["depthwise"] < out["op_self_s"] and t["nowhere"] == 0.0
        both = trace_program.reduce_events(events, scopes, rules, trace_program.compile_rules(
            [{"group": "both", "pattern": "/block_2/depthwise/"}]))["tally_s"]["both"]
        assert 0 < both < t["block_2"]
        assert t["block_2"] + t["depthwise"] - both < out["op_self_s"]
    else:
        t = {k: round(v * 1e6, 6) for k, v in out["tally_s"].items()}
        assert t == {"attention_kernel_window": 35.0, "attention_kernel_full": 55.0}
        assert sum(out["tally_s"].values()) == pytest.approx(
            out["scope_s"]["attention_kernel"], abs=1e-12)
        assert out["scope_s"]["attention"] == pytest.approx(15e-6)      # not in either tally
    assert sum("  tally " in line for line in trace_program.describe(out)) == len(tallies)


def test_the_drivers_reduction_takes_the_tallies_from_the_configurations_file():
    events, scopes = token_events()
    mellum = train_tokens.reduce_profile(events, scopes, config_file())
    dry = train_tokens.reduce_profile(events, scopes, config_file("dry-tally-test"))
    none = train_tokens.reduce_profile(events, scopes, config_file("mellum-small-test"))
    assert "tally_s" not in none and none["scope_s"] == mellum["scope_s"] == dry["scope_s"]
    assert set(mellum["tally_s"]) == {"attention_kernel_window", "attention_kernel_full"}
    # layer 3 whole: its kernel (40 + 15), its projection (15), its experts (30)
    assert dry["tally_s"] == {"last_layer": pytest.approx(100e-6)}
    assert dry["scope_s"]["moe_experts"] == pytest.approx(30e-6)


# -- counters

def _small_config():
    return program.program_config(config_file("mellum-small-test"))


STEP_METRICS = {"loss": 1.0, "moe/assignments_held": 0.0, "moe/load_max_over_mean": 0.0,
                "moe/fallback_layers": 0.0, "attention/full_layers": 1.0}


@pytest.mark.parametrize("own, names, missing", [
    ([], list(train_tokens.COUNTERS), []),
    (["moe/fallback_layers"], [*train_tokens.COUNTERS, "moe/fallback_layers"], []),
    (["moe/load_max_over_mean", "attention/full_layers"],
     ["moe/assignments_held", "moe/load_max_over_mean", "attention/full_layers"], []),
    (["mtp/accepted"], list(train_tokens.COUNTERS), ["mtp/accepted"]),
])
def test_counter_names(own, names, missing):
    assert train_tokens.counter_names({"counters": own} if own else {}, STEP_METRICS) == (
        names, missing)
    # a step without routed layers logs only what the configuration names
    dense = {"loss": 1.0, "attention/full_layers": 1.0}
    assert train_tokens.counter_names({}, dense) == ([], [])


def test_counters_are_read_by_name_over_the_traced_steps():
    names = ["moe/assignments_held", "moe/load_max_over_mean", "moe/fallback_layers"]
    log = [[100.0, 1.0, 0.0]] * 4 + [[200.0, 1.5, 0.0], [400.0, 2.5, 2.0]] + [[9e9, 9.0, 4.0]]
    out = train_tokens._traced_counters(names, log, 4, 2, _small_config(), {"seq_len": 64})
    assert out["moe/assignments_held"] == 300.0 and out["moe/load_max_over_mean"] == 2.0
    assert out["moe/fallback_layers"] == 1.0
    assert (out["routed_layers"], out["attention_layers"], out["seq_len"]) == (4, 1, 64)
    assert out["assignments_total"] == 8 * 64 * 4 * 4.0
    # another order of the names reads the same: nothing goes by position
    back = train_tokens._traced_counters(
        names[::-1], [row[::-1] for row in log], 4, 2, _small_config(), {"seq_len": 64})
    assert back == out
    # without the keys: the two the parent read
    two = train_tokens._traced_counters(
        names[:2], [row[:2] for row in log], 4, 2, _small_config(), {"seq_len": 64})
    assert two == {k: v for k, v in out.items() if k != "moe/fallback_layers"}
    none = train_tokens._traced_counters([], [[]] * 7, 4, 2, _small_config(), {"seq_len": 64})
    assert set(none) == {"routed_layers", "attention_layers", "seq_len", "assignments_total"}


# -- the readers

def _reading(cf, tally_s, counters):
    return {"trace": {"program": {"scope_s": {}, "tally_s": tally_s}, "counters": counters},
            "config_file": cf, "batch": 1, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}, "log": lambda _m: None}


def test_every_new_reader_is_in_the_manifest():
    by_name = {m["name"]: m for m in manifest()["per_layer"]}
    mellum, lfm2 = MELLUM + ".train-tokens-16k", "lfm2-24b-a2b.train-tokens-8k"
    for name in NEW_READERS[:2]:
        assert by_name[name]["workloads"] == [mellum] and by_name[name]["unit"] == "ms"
        assert by_name[name]["source"] == "device_trace"
    share = by_name["moe_fallback_share.train"]
    assert share["workloads"] == [lfm2, mellum] and share["better"] == "lower"
    assert share["source"] == "program_counter"
    for name in NEW_READERS:
        assert by_name[name]["moves"] == "train_samples_per_s"
        assert by_name[name]["layer"] == "Model step"
    # both token configurations name the counter; only mellum's has the tallies
    lfm2_file = program.load_config_file(
        os.path.join(REPO, "benchmarks", "configs", "lfm2-24b-a2b.json"))
    assert lfm2_file["counters"] == config_file()["counters"] == ["moe/fallback_layers"]
    assert "scope_tallies" not in lfm2_file


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_reads_a_number_or_nothing(name):
    read = run.metric_reader(REPO, name).read
    tally_s = {"attention_kernel_window": 0.034, "attention_kernel_full": 0.042}
    counters = {"routed_layers": 4, "moe/fallback_layers": 0.5}
    value = read(_reading(config_file(), tally_s, counters))
    assert value == pytest.approx({"attention_window_ms.train": 34.0,
                                   "attention_full_ms.train": 42.0,
                                   "moe_fallback_share.train": 0.125}[name])
    # no layer fell back: 0 is a reading
    if name == "moe_fallback_share.train":
        assert read(_reading(config_file(), tally_s, dict(counters, **{
            "moe/fallback_layers": 0.0}))) == 0.0
    # a parent without the tally or the counter, a driver without the reduction
    assert read(_reading(config_file(), {}, {"routed_layers": 4})) is None
    assert read({"trace": {}, "config_file": config_file(), "log": print}) is None


def test_the_experts_roofline_counts_by_the_configurations_own_shapes():
    """moe_experts_roofline lists mellum's cell too: its count is from
    hidden_size, moe_intermediate_size and experts_held of the file it is
    handed, not lfm2's."""
    read = run.metric_reader(REPO, "moe_experts_roofline").read
    counters = {"routed_layers": 4, "moe/assignments_held": 112000.0}
    reading = _reading(config_file(), {}, counters)
    reading["trace"]["program"]["scope_s"] = {"moe_experts": 0.060}
    # 112,000 rows x 3 products x 2 x 2304 x 896 x 3 (both ways) = 4.161 TFLOP = 21.12 ms
    assert read(reading) == pytest.approx(35.2, abs=0.1)


# -- a configuration that is only data

def dry_checkout(tmp_path):
    root = temp_checkout(tmp_path)
    for reader in os.listdir(os.path.join(DATA, "metrics")):
        shutil.copy(os.path.join(DATA, "metrics", reader),
                    os.path.join(root, "benchmarks", "metrics"))
    own = [{"name": name, "unit": "ms", "better": "lower", "source": source,
            "layer": "Model step", "moves": "train_samples_per_s", "workloads": ["small.dry"]}
           for name, source in (("last_layer_ms.train", "device_trace"),
                                ("full_layers.train", "program_counter"))]
    return add_rehearsal_cell(root, "small.dry", "dry-tally-test", per_layer=own)


def test_a_configuration_brings_a_tally_a_counter_and_their_readers_as_data(tmp_path, monkeypatch):
    pretend_chip(monkeypatch)
    root = dry_checkout(tmp_path)
    rc, line, _ = run_cell(root, "small.dry")
    assert rc == 0 and line["correct"] is True, line
    # what a traced run hands the readers, from the hand-made events and a log
    m = run.load_manifest(root)
    cf = program.load_config_file(os.path.join(root, run.config_path(m, "dry-tally-test")))
    events, scopes = token_events()
    names, missing = train_tokens.counter_names(cf, STEP_METRICS)
    assert names[-1] == "attention/full_layers" and not missing
    row = [7.0] * (len(names) - 1) + [1.0]
    trace = {"program": train_tokens.reduce_profile(events, scopes, cf),
             "counters": train_tokens._traced_counters(
                 names, [row] * 3, 0, 3, program.program_config(cf), {"seq_len": 64})}
    reading = dict(_reading(cf, {}, {}), trace=trace)
    own = [x["name"] for x in run.cell_metrics(m, "per_layer", "small.dry")
           if x.get("workloads") == ["small.dry"]]
    assert len(own) == 2
    values = {name: run.metric_reader(root, name).read(reading) for name in own}
    assert values["last_layer_ms.train"] == pytest.approx(0.1)
    assert values["full_layers.train"] == 1.0
    # the readers of other configurations' tallies find nothing here
    assert run.metric_reader(root, "attention_full_ms.train").read(reading) is None


def test_a_counter_the_step_does_not_return_is_a_fault_of_the_run(tmp_path, monkeypatch, capfd):
    pretend_chip(monkeypatch)
    root = dry_checkout(tmp_path)
    path = os.path.join(root, "benchmarks", "configs", "dry-tally-test.json")
    with open(path) as f:
        cf = json.load(f)
    cf["counters"].append("mtp/accepted")
    with open(path, "w") as f:
        json.dump(cf, f)
    rc, line, _ = run_cell(root, "small.dry")
    assert rc == 0 and line["correct"] is False
    assert all(n["value"] <= n["limit"] for n in line["compared"].values())
    assert "FAULT: the configuration's counters ['mtp/accepted']" in capfd.readouterr().err

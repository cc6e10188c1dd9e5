"""``xing4.0-29b-a4b`` in the harness, on the CPU at a rehearsal size
(data/xing-small-test.json, data/train-tokens-test.json): the configuration and
its mix load and keep every published width, the window loop prints the
contract's line with the configuration's four tallies' counters in its log,
``correct`` passes for the sound program and fails for the int8 control and for
the three controls that take one of the configuration's mechanisms away (the
streams' maps, YaRN and its softmax factor, the second loss term); the
yardstick against the frozen counts and ISSUE 34's arithmetic; the six readers
on hand-made events: a number where the program has the scope, ``None`` where it
has not (as the parent commit has not); and the two other token configurations'
small sizes still build the spec and the parameter tree they built before this
family came."""

import io
import json
import os
from contextlib import redirect_stdout

import pytest

from bench_testlib import (DATA, REPO, add_rehearsal_cell, manifest, pretend_chip, run_cell,
                           temp_checkout)
from benchmarks import flops_lm_mla, program, run, traffic
from benchmarks.drivers import train_tokens

CONFIG = "xing4.0-29b-a4b"
CELL = CONFIG + ".train-tokens-8k"
TALLIES = {"latent_projection_ms.train": "latent_projections",
           "hyper_connection_ms.train": "hyper_connection",
           "mtp_ms.train": "mtp", "shared_expert_ms.train": "shared_expert"}
ROOFLINES = ("latent_attention_roofline", "hyper_connection_roofline")
NEW_READERS = tuple(TALLIES) + ROOFLINES
CONTROLS = ("int8", "plain_residual", "no_yarn_scale", "no_mtp")
GENERIC = ("feeder_wait_ms.train", "h2d_ms.train", "device_step_ms.train", "train_mfu_pct",
           "train_step_roofline", "device_idle_pct.train")
# the per-layer entries that stood before this configuration came
EARLIER = GENERIC + (
    "moe_ms.train", "mixer_ms.train", "moe_experts_roofline", "attention_roofline",
    "moe_assignments_held_share.train", "attention_ms.train", "attention_masked_roofline",
    "lm_loss_ms.train", "attention_window_ms.train", "attention_full_ms.train",
    "moe_fallback_share.train")


def xing_checkout(tmp_path):
    """bench_testlib's temporary checkout with the rehearsal cell."""
    return add_rehearsal_cell(temp_checkout(tmp_path), "small.xing", "xing-small-test")


def config_file():
    return program.load_config_file(os.path.join(REPO, "benchmarks", "configs", CONFIG + ".json"))


def catalog_entry():
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        for line in f:
            row = json.loads(line)
            if row["name"] == "Xing4.0-29B-A4B":
                return row
    return None


def test_the_configuration_and_its_mix_load():
    m = manifest()
    cell = run.find_cell(m, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (CONFIG, "train-tokens-8k", 1)
    assert run.config_path(m, CONFIG) == "benchmarks/configs/" + CONFIG + ".json"
    cf = config_file()
    config = program.program_config(cf)
    lm = config.model.lm
    assert config.model.family == "xing4_0" and config.per_host_batch_size == 1
    # every published width as published; the cut is depth, experts, heads and rows held
    assert (lm.hidden_size, lm.q_lora_rank, lm.kv_lora_rank, lm.qk_nope_head_dim,
            lm.qk_rope_head_dim, lm.v_head_dim, lm.intermediate_size, lm.moe_intermediate_size,
            lm.num_experts, lm.num_experts_per_tok, lm.n_shared_experts, lm.hc_mult) == (
                3584, 768, 512, 128, 64, 128, 9216, 1024, 64, 4, 1, 4)
    assert tuple(lm.layer_types) == ("latent_attention",) * 5 and lm.num_dense_layers == 1
    assert tuple(lm.experts_held) == (0, 8) and lm.vocab_held == 16384
    assert tuple(lm.heads_held) == (0, cf["num_attention_heads"]) and lm.num_attention_heads == 32
    assert lm.rope_scaling.to_dict() == {
        k: float(v) if k != "type" and k != "original_max_position_embeddings" else v
        for k, v in cf["rope_scaling"].items()}
    for key in ("published", "deployment", "reduced_how", "assumed", "left_out", "limits",
                "limits_from", "scope_tallies", "counters"):
        assert cf[key], key
    entry = next(c for c in m["configs"] if c["name"] == CONFIG)
    assert sorted(entry["reduced"]) == sorted(cf["reduced_how"]) == sorted(cf["published"])
    mix = traffic.load_traffic_file(
        os.path.join(REPO, "benchmarks", "traffic", "train-tokens-8k.json"))
    assert (mix["kind"], mix["seq_len"]) == ("train_tokens", 8192) and lm.seq_len == 8192
    # what drivers/train_tokens.py reads by name
    for key in ("layer_types", "num_dense_layers", "num_experts_per_tok", "vocab_held"):
        assert key in lm


def test_every_number_of_the_catalogs_config_is_in_the_file():
    row = catalog_entry()
    if row is None:
        pytest.skip("no catalog beside the model-configs guide here")
    cf, entry = config_file(), next(c for c in manifest()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == cf["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cf["published"][key] == value and cf[key] != value, key
        else:
            assert cf[key] == value, key
    widths = [k for k in entry["reduced"] if k.endswith(("_dim", "_rank", "_size"))
              and k != "vocab_size"]
    assert not widths


def test_the_frozen_counts_are_the_yardsticks():
    cf = config_file()
    y = flops_lm_mla.yardstick(cf)
    assert cf["flops_per_sample"] == pytest.approx(y["flops_per_sample"], rel=1e-9)
    assert cf["min_bytes_per_step"] == pytest.approx(y["min_bytes_per_step"], rel=1e-9)
    lm = flops_lm_mla.lm_sizes(cf["overrides"])
    assert lm["heads_held"] == [0, 4]
    # ISSUE 34's arithmetic at the share the AOT rule chose (4 of 32 heads), by hand
    parts = flops_lm_mla.parameters(lm)
    assert parts["latent_mixer"] == (3584 * 768 + 768 * 4 * 192 + 3584 * 576 + 512 * 4 * 256
                                     + 4 * 128 * 3584 + 768 + 512) == 7767296
    assert parts["maps_a_sublayer"] == 4 * 3584 * 24 + 4 * 3584 + 3 + 24
    assert parts["dense_ffn"] == 3 * 3584 * 9216 == 99090432
    assert parts["routed_ffn"] == 9 * 3 * 3584 * 1024 + 3584 * 64 + 64
    assert parts["embedding_and_head"] == 2 * 16384 * 3584 == 117440512
    assert y["parameters"] == 789782660             # the ISSUE's 789.8 M; x 16 B = 12.64 GB
    # and at the shares above it, which the reference's memory refused: 807.5 M, 842.9 M
    assert flops_lm_mla.parameters(dict(lm, heads_held=[0, 8]))["total"] == 807477380
    above = dict(lm, heads_held=[0, 16])
    assert flops_lm_mla.parameters(above)["total"] == 842866820
    forward = flops_lm_mla.forward_flops_per_token(lm, 8192)
    assert forward["attention_scores"] == 2 * 4 * (192 + 128) * 4096
    assert forward["experts"] == 0.5 * 2 * 3 * 3584 * 1024      # top-4 x 8 of 64 held
    assert forward["shared_expert"] == 2 * 3 * 3584 * 1024
    assert forward["head"] == 2 * 3584 * 16384
    mixer = forward["latent_projections"] + forward["attention_scores"] + forward["maps"]
    routed = forward["router"] + forward["experts"] + forward["shared_expert"]
    assert forward["total"] == (6 * mixer + forward["dense_ffn"] + 5 * routed
                                + forward["module_merge"] + 2 * forward["head"])
    assert y["flops_per_sample"] == pytest.approx(20.06e12, rel=1e-3)
    assert 3 * 8192 * flops_lm_mla.forward_flops_per_token(above, 8192)["total"] == (
        pytest.approx(27.3e12, rel=2e-3))
    # latent attention (projections and scores) is 19 % of the step's FLOPs at 4 heads
    # (28 % at 8, 41 % at 16), the head's two passes 29 %, the dense layer 24 %
    share = 6 * (forward["latent_projections"] + forward["attention_scores"]) / forward["total"]
    assert share == pytest.approx(0.191, abs=0.005)
    assert 2 * forward["head"] / forward["total"] == pytest.approx(0.288, abs=0.005)
    flops, nbytes = flops_lm_mla.attention_cost(1, 8192, lm)
    assert flops == 6 * 3 * 2 * 4 * (192 + 128) * 8192 * 8192 / 2
    assert nbytes == 6 * 2 * 8192 * 4 * (2 * 192 + 2 * 128) * 2
    flops, nbytes = flops_lm_mla.hyper_connection_cost(1, 8192, lm)
    assert nbytes == 12 * 2 * 2 * 8192 * 4 * 3584 * 2          # 11.27 GB: 13.8 ms at 819 GB/s
    assert flops == 3 * 12 * 8192 * 2 * 4 * 3584 * 24


def _reading(program_summary, counters, cf=None):
    lines = []
    return {"trace": {"program": program_summary, "counters": counters},
            "config_file": cf or config_file(), "batch": 1, "chips": 1,
            "peaks": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
            "log": lines.append}


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_entry_lists_the_cell_alone(name):
    by_name = {m["name"]: m for m in manifest()["per_layer"]}
    entry = by_name[name]
    assert entry["workloads"] == [CELL] and entry["moves"] == "train_samples_per_s"
    assert entry["source"] == "device_trace"
    assert entry["unit"] == ("%" if name in ROOFLINES else "ms")
    assert entry["layer"] == ("Kernels" if name in ROOFLINES else "Model step")
    # every entry without a list reports in the new cell as in the others,
    # the generic six among them, however many are appended later
    unlisted = {m["name"] for m in manifest()["per_layer"] if "workloads" not in m}
    assert set(GENERIC) <= unlisted
    assert {m["name"] for m in run.cell_metrics(manifest(), "per_layer", CELL)} == (
        unlisted | set(NEW_READERS))


def test_the_manifest_gained_entries_and_lost_none():
    """Found by name, wherever later entries put them."""
    m = manifest()
    assert CONFIG in {c["name"] for c in m["configs"]}
    assert CELL in {w["name"] for w in m["workloads"]}
    by_name = {x["name"]: x for x in m["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL], name
    # the entries that were there are there still, and their lists leave the cell out
    for name in EARLIER:
        assert CELL not in by_name[name].get("workloads", []), name


XING_SCOPES = {
    "fusion.1": "jit(train_step_guarded)/jvp(DecoderLM)/layer_1/mixer/attention/latent/q_a_proj/dot_general:",
    "fusion.2": "jit(train_step_guarded)/jvp(DecoderLM)/layer_1/mixer/attention/kernel/full/splash_mha_fwd_residuals:",
    "fusion.3": "jit(train_step_guarded)/jvp(DecoderLM)/layer_1/mixer_hc/hyper_connection/maps/dot_general:",
    "fusion.4": "jit(train_step_guarded)/transpose(jvp(DecoderLM))/layer_1/hyper_connection/mix/mul:",
    "fusion.5": "jit(train_step_guarded)/jvp(DecoderLM)/layer_1/ffn/shared_expert/moe/shared/w1/dot_general:",
    "fusion.6": "jit(train_step_guarded)/jvp(DecoderLM)/mtp/mtp/layer/mixer/attention/latent/kv_b_proj/dot_general:",
    "fusion.7": "jit(train_step_guarded)/jvp(DecoderLM)/mtp/lm_loss/while/body/dot_general:",
    "fusion.8": "jit(train_step_guarded)/optimizer/add:",
}


def xing_events():
    """Two whole runs of a step, 200 us each, busy end to end."""
    from benchmarks.trace import reduce

    dev, ops, mods = "/device:TPU:0", reduce.OPS_LINE + "#3", reduce.MODULES_LINE + "#2"
    ev = lambda line, name, start, dur: (dev, line, name, start * 1000, dur * 1000, {})  # noqa: E731
    events = [("/host:CPU", "python3#9", "bench/sync", 0, 500 * 1000, {})]
    for t in (10, 260):
        events += [ev(mods, "jit_train_step_guarded(7)", t, 200)]
        events += [ev(ops, f"fusion.{k}", t + 25 * (k - 1), 25) for k in range(1, 9)]
    return events, XING_SCOPES


def test_the_files_tallies_on_hand_made_events():
    """An op under ``mtp/`` and ``attention/latent/`` counts in both tallies and
    once in the family's partition."""
    events, scopes = xing_events()
    out = train_tokens.reduce_profile(events, scopes, config_file())
    tallies = {k: round(v * 1e6, 6) for k, v in out["tally_s"].items()}
    assert tallies == {"latent_projections": 50.0, "hyper_connection": 50.0,
                       "shared_expert": 25.0, "mtp": 50.0}
    assert out["scope_s"]["attention_kernel"] == pytest.approx(25e-6)
    assert out["scope_s"]["attention"] == pytest.approx(50e-6)
    assert out["scope_s"]["lm_loss"] == pytest.approx(25e-6)
    assert sum(out["scope_s"].values()) == pytest.approx(out["op_self_s"], rel=1e-12)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_reader_reads_a_number_or_nothing(name):
    read = run.metric_reader(REPO, name).read
    events, scopes = xing_events()
    summary = train_tokens.reduce_profile(events, scopes, config_file())
    counters = {"routed_layers": 4, "attention_layers": 0, "seq_len": 8192}
    value = read(_reading(summary, counters))
    assert value is not None and value > 0
    if name in TALLIES:
        assert value == pytest.approx(summary["tally_s"][TALLIES[name]] * 1e3)
    cf = config_file()
    lm = flops_lm_mla.lm_sizes(cf["overrides"])
    if name == "latent_attention_roofline":         # 31.4 ms by FLOPs over 25 us
        flops, _ = flops_lm_mla.attention_cost(1, 8192, lm)
        assert value == pytest.approx(flops / 197e12 / 25e-6 * 100)
    if name == "hyper_connection_roofline":         # bound by bytes
        flops, nbytes = flops_lm_mla.hyper_connection_cost(1, 8192, lm)
        assert nbytes / 819e9 > flops / 197e12
        assert value == pytest.approx(nbytes / 819e9 / 50e-6 * 100)
    # a program without the scopes (the parent), a file without the tallies, a
    # driver without the reduction
    bare = {"scope_s": {"optimizer": 0.007}, "tally_s": {k: 0.0 for k in TALLIES.values()}}
    assert read(_reading(bare, counters)) is None
    assert read(_reading({"scope_s": {"attention_kernel": 0.02}}, {})) is None
    assert read(_reading(None, {})) is None
    if name in ROOFLINES:       # another family's file has nothing to count
        other = program.load_config_file(
            os.path.join(REPO, "benchmarks", "configs", "mellum2-12b-a2.5b.json"))
        assert read(_reading(summary, counters, other)) is None


def test_rehearsal_prints_the_contracts_line(tmp_path, monkeypatch, capfd):
    pretend_chip(monkeypatch)
    rc, line, _ = run_cell(xing_checkout(tmp_path), "small.xing")
    assert rc == 0
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"setup_s", "train_samples_per_s", "train_step_ms_p95"}
    assert line["attempted"] >= 2 and line["failed"] == 0
    assert list(line)[-1] == "compared" and all(
        n["value"] <= n["limit"] for n in line["compared"].values())
    assert "FAULT" not in capfd.readouterr().err         # every counter the file names is there


def _readings(root, *options):
    from benchmarks import readings_controls

    out = io.StringIO()
    with redirect_stdout(out):
        rc = readings_controls.main(["small.xing", "3000000019", *options], root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_the_controls_are_not_correct_and_the_program_is(tmp_path, monkeypatch):
    """Three Adam steps through make_train_step_fns against check.follow; the
    reference one precision down, and with each mechanism taken away, in the
    program's place, fails ``check.judge``."""
    pretend_chip(monkeypatch)
    options = [x for name in CONTROLS for x in ("--control", name)]
    out = _readings(xing_checkout(tmp_path), *options)
    assert out["program"]["correct"] is True, out["program"]
    assert out["skips"] == 0
    assert set(out["controls"]) == set(CONTROLS)
    for name, control in out["controls"].items():
        assert control["correct"] is False and control["over"], name
    assert all(c["moe/fallback_layers"] == 0.0 for c in out["counters"])


@pytest.mark.parametrize("fault", ["half_targets", "one_leaf"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    pretend_chip(monkeypatch)
    out = _readings(xing_checkout(tmp_path), "--fault", fault,
                    "--leaf", "layer_2']['mixer']['o_proj']['kernel")
    assert out["program"]["correct"] is False and out["program"]["over"]


# -- the families that were here: their small sizes build what they built

BEFORE = {      # spec fields and leaves at the parent commit (476275c), by this test's own count
    "lfm2-small-test": {"mixers": ["conv", "full_attention", "conv", "conv", "conv"],
                        "ffns": ["dense", "moe", "moe", "moe", "moe"], "leaves": 53,
                        "tied": True, "scoring": "sigmoid"},
    "mellum-small-test": {"mixers": ["sliding_attention"] * 3 + ["full_attention"],
                          "ffns": ["moe"] * 4, "leaves": 51, "tied": False,
                          "scoring": "softmax"},
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_other_token_families_build_what_they_built(name):
    import flax
    import jax

    from rt1_tpu.train.train import build_family

    cf = program.load_config_file(os.path.join(DATA, name + ".json"))
    config = program.program_config(cf)
    model, init_fn, _ = build_family(config.model)
    spec, want = model.spec, BEFORE[name]
    assert [b.mixer for b in spec.blocks] == want["mixers"]
    assert [b.ffn for b in spec.blocks] == want["ffns"]
    assert (spec.tie_word_embeddings, spec.scoring_func) == (want["tied"], want["scoring"])
    # nothing of the new family is switched on
    assert (spec.hc_mult, spec.mtp_layers, spec.n_shared_experts, spec.heads_held,
            spec.kv_lora_rank, spec.softmax_scale_factor) == (1, 0, 0, None, 0, 1.0)
    batch = train_tokens.batch_spec(config, int(config.model.lm.seq_len))
    shapes = jax.eval_shape(lambda r, o, a: init_fn(model, r, o, a), jax.random.PRNGKey(0), *batch)
    leaves = flax.traverse_util.flatten_dict(shapes["params"], sep="/")
    assert len(leaves) == want["leaves"]
    assert not any(part in path for path in leaves
                   for part in ("_hc/", "mtp/", "shared_expert", "q_a_proj", "kv_a_proj"))
    out = jax.eval_shape(lambda r, o, a: model.apply(init_fn(model, r, o, a), o, a),
                         jax.random.PRNGKey(0), *batch)
    assert "loss_next" not in out and not any(
        k.startswith(("mtp/", "hyper_connection/")) for k in out["counters"])

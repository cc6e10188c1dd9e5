"""``correct`` has to be able to fail.  The control (the reference in the
precision below the configuration's) and each fault a one-chip training cell
can have, at a size a test run can hold: every one reads ``correct`` false.
So does a fault in a minority of the leaves (the decoder alone left where it
was), which no median leaf sees: the 90th-percentile leaf is compared for it."""

import json
import os

import numpy as np
import pytest

from bench_testlib import DATA, pretend_chip, run_cell, temp_checkout
from benchmarks import check, program


_STEP = program.Program.step


def _fault_unchanged_state(self, batch, rng):
    state, skips = self.state, self.skips
    if self.fns.guarded:
        _, _, metrics = self.fns.train_step(state, skips, batch, rng)
    else:
        _, metrics = self.fns.train_step(state, batch, rng)
    return metrics


def _fault_half_batch(self, batch, rng):
    # the second half never reaches the step: the first half stands in its
    # rows, so every mean is the mean over the first half alone (and the
    # batch keeps a size the test's eight virtual devices divide)
    import jax
    import jax.numpy as jnp

    def first_half_twice(x):
        h = x.shape[0] // 2
        return jax.device_put(jnp.concatenate([x[:h], x[:h]]), x.sharding)

    return _STEP(self, jax.tree.map(first_half_twice, batch), rng)


def _fault_decoder_unchanged(self, batch, rng):
    # the step runs, and the decoder's leaves (a fifth of the tree) are put
    # back where they were: their Adam moments too
    import jax

    before = self.state
    metrics = _STEP(self, batch, rng)

    def put_back(path, new, old):
        return old if "transformer" in jax.tree_util.keystr(path) else new

    self.state = self.state.replace(
        params=jax.tree_util.tree_map_with_path(put_back, self.state.params, before.params),
        opt_state=jax.tree_util.tree_map_with_path(
            put_back, self.state.opt_state, before.opt_state))
    return metrics


def _fault_one_leaf_unchanged(self, batch, rng):
    # one kernel of 570 put back where it was: no median, no percentile sees it
    import jax

    before = self.state
    metrics = _STEP(self, batch, rng)

    def put_back(path, new, old):
        return old if "layer_1']['attn']['query']['kernel" in jax.tree_util.keystr(path) else new

    self.state = self.state.replace(
        params=jax.tree_util.tree_map_with_path(put_back, self.state.params, before.params))
    return metrics


@pytest.mark.parametrize(
    "fault", [_fault_unchanged_state, _fault_half_batch, _fault_decoder_unchanged,
              _fault_one_leaf_unchanged],
    ids=["state_unchanged", "half_batch", "decoder_unchanged", "one_leaf_unchanged"])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    pretend_chip(monkeypatch)
    import rt1_tpu.trainer as trainer

    # a state handed back unchanged has to outlive the step it went into
    real = trainer.make_train_step_fns
    monkeypatch.setattr(trainer, "make_train_step_fns",
                        lambda *a, **k: real(*a, **dict(k, donate=False)))
    monkeypatch.setattr(program.Program, "step", fault)
    rc, line, _ = run_cell(temp_checkout(tmp_path), "small.pool")
    assert rc == 0 and line["correct"] is False
    over = [k for k, v in line["compared"].items() if not v["value"] <= v["limit"]]
    assert over, line["compared"]
    if fault is _fault_decoder_unchanged:       # the medians do not see it
        assert "change_3_p90_leaf" in over and "grad_1_p90_leaf" in over, line["compared"]
        assert "grad_1_median_leaf" not in over, line["compared"]
    if fault is _fault_one_leaf_unchanged:      # nor do the percentiles
        assert line["compared"]["change_3_worst_ratio"]["value"] > 1e6
        assert not [k for k in over if "median" in k or "p90" in k], line["compared"]


def test_the_control_is_not_correct():
    """The reference itself, one precision down, against the reference."""
    import jax

    with open(os.path.join(DATA, "rt1-small-test.json")) as f:
        cf = json.load(f)
    config = program.program_config(cf)
    _, model, init_fn, _, tx = program.build_model(config, devices=jax.devices()[:1])
    shapes = program.abstract_state(config, model, init_fn, tx)
    abstract = (shapes.params, shapes.batch_stats)
    rng = np.random.default_rng(0)
    b, t = cf["overrides"]["per_host_batch_size"], cf["overrides"]["model.time_sequence_length"]
    h, w = cf["overrides"]["data.height"], cf["overrides"]["data.width"]
    batches = [{
        "observations": {
            "image": rng.integers(0, 256, (b, t, h, w, 3), dtype=np.uint8),
            "natural_language_embedding": rng.standard_normal((b, t, 512)).astype(np.float32)},
        "actions": {"terminate_episode": np.zeros((b, t), np.int32),
                    "action": rng.uniform(-0.1, 0.1, (b, t, 2)).astype(np.float32)},
    } for _ in range(3)]
    quiet = lambda _msg: None  # noqa: E731
    ref = check.reference_readings(cf, abstract, 7, batches, "highest", quiet)
    control = check.reference_readings(cf, abstract, 7, batches, cf["control_precision"], quiet)
    same = check.judge(check.numbers(ref, ref), cf["limits"])
    assert all(c["ok"] and c["value"] == (1 if c["name"].endswith("_ratio") else 0)
               for c in same)
    verdict = check.judge(check.numbers(control, ref), cf["limits"])
    assert not all(c["ok"] for c in verdict), verdict


def test_a_leaf_gap_is_a_gap_of_norms_over_the_larger_floor():
    ref = {"a": 1.0, "b": 1e-9, "c": 2.0}
    prog = {"a": 1.1, "b": 1e-3, "c": 2.0}
    gaps = check.leaf_gaps(prog, ref, ["a", "b", "c"])
    assert gaps["a"] == pytest.approx(0.1) and gaps["c"] == 0.0
    assert gaps["b"] == pytest.approx(1e-3)       # held to the median leaf, not to 1e-9

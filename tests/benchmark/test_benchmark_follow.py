"""``check.follow``, the reference's three Adam steps, at the rehearsal sizes
of all three families: it reads what it read before it donated the parameters
and stopped returning the gradient tree (data/follow_at_parent.json, recorded
at the parent commit), and its compiled step holds four copies of the
parameters' size (masters, two moments, one gradient) beside the loss's own
temporaries, not six."""

import json
import os

import numpy as np
import pytest

from bench_testlib import DATA
from benchmarks import check, program
from benchmarks.drivers import train_tokens

CONFIGS = ("rt1-small-test", "lfm2-small-test", "mellum-small-test")
SEED = 7


def small_case(name):
    """(configuration file, abstract (params, batch_stats), three batches as
    the feed would hand them, the batch-to-model function) of a rehearsal
    configuration, the batches drawn from a fixed generator."""
    import jax
    import jax.numpy as jnp

    with open(os.path.join(DATA, name + ".json")) as f:
        cf = json.load(f)
    config = program.program_config(cf)
    _, model, init_fn, _, tx = program.build_model(config, devices=jax.devices()[:1])
    rng = np.random.default_rng(0)
    over = cf["overrides"]
    b = over["per_host_batch_size"]
    if config.model.get("family", "rt1") == "rt1":
        shapes = program.abstract_state(config, model, init_fn, tx)
        t, h, w = over["model.time_sequence_length"], over["data.height"], over["data.width"]
        batches = [{
            "observations": {
                "image": rng.integers(0, 256, (b, t, h, w, 3), dtype=np.uint8),
                "natural_language_embedding": rng.standard_normal((b, t, 512)).astype(np.float32)},
            "actions": {"terminate_episode": np.zeros((b, t), np.int32),
                        "action": rng.uniform(-0.1, 0.1, (b, t, 2)).astype(np.float32)},
        } for _ in range(3)]
        return cf, (shapes.params, shapes.batch_stats), batches, check.model_batch
    from rt1_tpu.trainer import create_train_state

    s = over["model.lm.seq_len"]
    observations, actions = train_tokens.batch_spec(config, s)
    shapes = jax.eval_shape(
        lambda r, o, a: create_train_state(model, r, (o, a), tx, init_fn=init_fn),
        jax.ShapeDtypeStruct((2,), jnp.uint32), observations, actions)
    batches = []
    for _ in range(3):
        tokens = rng.integers(0, over["model.lm.vocab_held"], (b, s + 1), dtype=np.int32)
        batches.append({"observations": {"tokens": tokens[:, :-1]},
                        "actions": {"targets": tokens[:, 1:].copy()}})
    return cf, (shapes.params, shapes.batch_stats), batches, train_tokens.token_batch


@pytest.fixture(scope="module")
def at_parent():
    with open(os.path.join(DATA, "follow_at_parent.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", CONFIGS)
def test_follow_reads_what_it_read_at_the_parent(name, at_parent):
    cf, abstract, batches, as_model = small_case(name)
    now = check.reference_readings(cf, abstract, SEED, batches, "highest", lambda _m: None,
                                   as_model)
    was = at_parent[name]
    assert now["losses"] == pytest.approx(was["losses"], rel=1e-6)
    assert sorted(now["grad1"]) == sorted(was["grad1"]) == sorted(now["delta"])
    # leaves whose gradient is nought to rounding move under Adam by round-off
    # alone, another fusion's as soon as the gradient is a program of its own:
    # left out of the change by check.numbers' own rule (10 of RT-1's 217)
    median = float(np.median(list(was["grad1"].values())))
    alive = [k for k, g in was["grad1"].items() if g >= check.DEAD_GRADIENT * median]
    assert len(was["grad1"]) - len(alive) == {"rt1-small-test": 10, "lfm2-small-test": 4,
                                              "mellum-small-test": 0}[name]
    for tree, keep in (("grad1", sorted(was["grad1"])), ("delta", alive)):
        gaps = check.leaf_gaps(now[tree], was[tree], keep)
        assert max(gaps.values()) <= 1e-6, (tree, max(gaps, key=gaps.get))
    # and so every number of ``compared``, the parent's reading as the reference
    nums = check.numbers(now, was)
    assert all(nums[k][0] <= 1e-6 for k in check.NUMBERS if not k.endswith("_ratio")), nums
    assert nums["change_3_worst_ratio"][0] <= 1 + 1e-5


def _bytes(tree):
    import jax

    return sum(int(np.prod(a.shape)) * a.dtype.itemsize for a in jax.tree.leaves(tree))


def _held(mem):
    """What a compiled program keeps of its caller's and hands back, without
    its temporaries."""
    return mem.argument_size_in_bytes + mem.output_size_in_bytes - mem.alias_size_in_bytes


@pytest.mark.parametrize("name", CONFIGS)
def test_the_compiled_step_holds_four_copies_of_the_parameters(name):
    """arguments + outputs - aliased of the step's two compiled programs, with
    what ``follow`` holds beside each call.  The gradient's: the masters in,
    one gradient out, both moments waiting: four copies beside the loss's own
    temporaries.  The update's: masters, moments and the gradient in, the three
    results in place, no temporaries: four copies.  (The parent's one program
    took three and returned four with two in place, and its caller kept the
    seed's weights alive beside them: six.)"""
    import jax
    import jax.numpy as jnp

    cf, (params, batch_stats), batches, as_model = small_case(name)
    ref = check.load_reference(cf["reference"])
    sz = ref.sizes(cf["overrides"])
    batch = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), as_model(batches[0]))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    count = jax.ShapeDtypeStruct((), jnp.float32)
    gradient, update = check._reference_step(
        ref, sz, float(cf["overrides"]["learning_rate"]), "highest")
    grad = gradient.lower(params, batch_stats, batch, key).compile().memory_analysis()
    adam = update.lower(params, params, params, params, count).compile().memory_analysis()
    if grad is None or not grad.argument_size_in_bytes:
        pytest.skip("this backend reports no memory analysis")
    one = _bytes(params)
    small = _bytes(batch) + 2 * _bytes(batch_stats) + 0.5 * one
    assert 2 * one <= _held(grad) <= 2 * one + small
    assert _held(grad) + 2 * one <= 4.5 * one + _bytes(batch) + 2 * _bytes(batch_stats)
    assert adam.alias_size_in_bytes >= 3 * one         # masters and both moments in place
    assert 4 * one <= _held(adam) + adam.temp_size_in_bytes <= 4.5 * one

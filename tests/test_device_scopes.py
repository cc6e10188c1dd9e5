"""Device scopes: every op of the train step carries a name a profile can be
grouped by.  Flax names the ops of every module (``flax_profile``, on by
default); ``jax.named_scope`` names what runs outside one: ``preprocess``
and ``loss`` in RT1Policy, ``optimizer``, ``health`` and ``cast_bf16`` in
the trainer.  The names are metadata: the lowered step's text holds them,
forward and backward, and benchmarks/trace/scopes.json groups by them."""

import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def op_names():
    """The ``loc("...")`` scope paths of the small rehearsal step, lowered and not compiled."""
    import jax
    import numpy as np

    from benchmarks import program
    from rt1_tpu.obs import health

    config_file = program.load_config_file(
        os.path.join(REPO, "tests", "benchmark", "data", "rt1-small-test.json"))
    prog = program.build(config_file, 7, ("a", "b"))
    obs, actions = program.batch_spec(prog.config)
    obs[health.TASK_ID_KEY] = jax.ShapeDtypeStruct((prog.config.per_host_batch_size,), np.int32)
    key = jax.ShapeDtypeStruct((2,), np.uint32)
    assert prog.fns.guarded
    text = prog.fns.train_step.lower(prog.state, prog.skips, (obs, actions), key).as_text(
        debug_info=True)
    return set(re.findall(r'loc\("([^"]+)"', text))


FORWARD, BACKWARD = "jvp(RT1Policy)", "transpose(jvp(RT1Policy))"


@pytest.mark.parametrize("scope, under", [
    ("preprocess", FORWARD),
    ("loss", FORWARD),
    ("loss", BACKWARD),
    ("optimizer", "jit(train_step_guarded)"),
    ("health", "jit(train_step_guarded)"),
    # Flax's own: a module's name, forward and backward
    ("token_learner", FORWARD),
    ("token_learner", BACKWARD),
    ("transformer", FORWARD),
    ("transformer", BACKWARD),
])
def test_the_lowered_step_names_its_scopes(op_names, scope, under):
    assert any(f"{under}/" in n and f"/{scope}/" in n for n in op_names), (scope, under)


def test_the_guards_select_and_the_norm_are_the_optimizers(op_names):
    assert "jit(train_step_guarded)/optimizer/jit(_where)" in op_names
    assert any("/optimizer/" in n and n.endswith("sqrt") for n in op_names)


def test_the_bf16_copy_is_scoped():
    import jax
    import jax.numpy as jnp

    from rt1_tpu.trainer.train import _bf16_compute_copy

    text = jax.jit(_bf16_compute_copy).lower({"w": jnp.ones((2,), jnp.float32)}).as_text(
        debug_info=True)
    assert "/cast_bf16/" in text

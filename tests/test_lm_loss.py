"""The output head and the loss of the decoder LMs (models/lm/model.py):
``next_token_loss`` makes its gradients in the loop that makes its logits (a
``custom_vjp``); ``next_token_loss_plain`` is the same arithmetic as a plain
function, and ``jax.grad`` of it is what those gradients have to equal.

In float32 both sides do the same sums in another order (tolerance 1e-5 of a
gradient's largest element; read 1e-7 to 5e-7).  With bfloat16 operands the
rule rounds ``d_logits`` to bfloat16 before its two products, as a TPU's
default precision rounds a float32 operand, where jax's own transpose on a CPU
multiplies in float32: 2e-2 (read 4e-3 to 8e-3).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import program, weights
from benchmarks.drivers import train_tokens
from rt1_tpu.models.lm import model as lm_model
from rt1_tpu.models.lm.spec import IGNORE
from rt1_tpu.train.train import build_family

DATA = os.path.join(os.path.dirname(__file__), "benchmark", "data")
BLOCK, D, ROWS = 16, 32, 96
TOL = {"float32": 1e-5, "bfloat16": 2e-2}


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(lm_model, "LOSS_BLOCK", BLOCK)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)


def inputs(dtype, tokens, seed=0):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (2, tokens // 2, D), jnp.float32).astype(dtype)
    head = (0.3 * jax.random.normal(jax.random.fold_in(key, 1), (ROWS, D))).astype(dtype)
    targets = jax.random.randint(jax.random.fold_in(key, 2), (2, tokens // 2), 0, ROWS)
    # tail padding, as the packed feed leaves it
    targets = targets.at[:, -3:].set(IGNORE)
    return x, head, targets


def both(loss_of, *args):
    """(value, gradients) through the rule and through the plain oracle."""
    wrt = tuple(range(len(args)))
    return [jax.value_and_grad(lambda *a, fn=fn: loss_of(fn, *a), argnums=wrt)(*args)
            for fn in (lm_model.next_token_loss, lm_model.next_token_loss_plain)]


def assert_same(got, want, tol):
    (loss, grads), (want_loss, want_grads) = got, want
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * max(1.0, abs(float(want_loss)))
    for g, w in zip(grads, want_grads):
        assert g.dtype == w.dtype and g.shape == w.shape
        if float(jnp.max(jnp.abs(w.astype(jnp.float32)))) == 0.0:
            assert float(jnp.max(jnp.abs(g.astype(jnp.float32)))) == 0.0
        else:
            assert gap(g, w) <= tol, gap(g, w)


# the case's name -> (tokens, what happens to the targets, how the loss is used)
CASES = {
    "several_blocks": (4 * BLOCK, None, "plain"),
    "one_block_where_the_size_does_not_divide": (4 * BLOCK + 6, None, "plain"),
    "a_block_with_every_target_ignored": (4 * BLOCK, "second_block", "plain"),
    "every_target_ignored": (4 * BLOCK, "all", "plain"),
    "an_upstream_cotangent_of_0.3": (4 * BLOCK, None, "scaled"),
    "a_tied_head": (4 * BLOCK, None, "tied"),
    "two_passes_over_one_head": (4 * BLOCK, None, "two_passes"),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", list(CASES))
def test_the_gradients_are_those_of_the_plain_function(case, dtype):
    tokens, hidden, use = CASES[case]
    x, head, targets = inputs(jnp.dtype(dtype), tokens)
    if hidden == "second_block":
        flat = targets.reshape(-1).at[BLOCK:2 * BLOCK].set(IGNORE)
        targets = flat.reshape(targets.shape)
    elif hidden == "all":
        targets = jnp.full_like(targets, IGNORE)
    if use == "plain":
        got, want = both(lambda fn, x, head: fn(x, head, targets), x, head)
    elif use == "scaled":
        got, want = both(lambda fn, x, head: 0.3 * fn(x, head, targets), x, head)
    elif use == "tied":
        # the table is gathered for the tokens and is the head: both gradients land in it
        tokens_in = jnp.maximum(targets, 0)[:, ::-1]
        got, want = both(lambda fn, table: fn(jnp.tanh(table[tokens_in]), table, targets), head)
    else:
        # the trunk's pass and a prediction module's, as DecoderLM calls them
        y, _, _ = inputs(jnp.dtype(dtype), tokens, seed=1)
        shifted = jnp.concatenate([targets[:, 1:], jnp.full_like(targets[:, :1], IGNORE)], axis=1)
        second = jnp.where(targets != IGNORE, shifted, IGNORE)
        got, want = both(lambda fn, x, y, head: fn(x, head, targets) + 0.3 * fn(y, head, second),
                         x, y, head)
    assert_same(got, want, TOL[dtype])
    if hidden == "all":
        assert float(got[0]) == 0.0


def vocabulary_products(jaxpr):
    """The ``dot_general``s of a jaxpr, loops and calls opened, with the
    vocabulary's dimension among an operand's or the result's."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                shapes = [v.aval.shape for v in list(eqn.invars) + list(eqn.outvars)]
                if any(ROWS in s for s in shapes):
                    found.append(shapes)
            for value in eqn.params.values():
                for inner in value if isinstance(value, (list, tuple)) else [value]:
                    inner = getattr(inner, "jaxpr", inner)
                    if hasattr(inner, "eqns"):
                        walk(inner)

    walk(jaxpr.jaxpr)
    return found


@pytest.mark.parametrize("passes", [1, 2])
def test_three_products_a_pass_both_ways_and_one_in_the_primal(passes):
    x, head, targets = inputs(jnp.float32, 4 * BLOCK)

    def loss(fn):
        return lambda x, head: sum(
            (0.3 ** i) * fn(x + i, head, targets) for i in range(passes))

    both_ways = jax.make_jaxpr(jax.value_and_grad(loss(lm_model.next_token_loss), (0, 1)))(x, head)
    assert len(vocabulary_products(both_ways)) == 3 * passes
    text = str(both_ways)
    assert "checkpoint" not in text and "remat" not in text
    # the plain function differentiated by jax: the logits again on the way back
    plain = jax.make_jaxpr(jax.value_and_grad(loss(lm_model.next_token_loss_plain), (0, 1)))(
        x, head)
    assert len(vocabulary_products(plain)) == 3 * passes    # no checkpoint: logits kept
    primal = jax.make_jaxpr(loss(lm_model.next_token_loss))(x, head)
    products = vocabulary_products(primal)
    assert len(products) == passes
    assert all(shapes[-1] == (BLOCK, ROWS) for shapes in products)    # the logits, nothing else


def test_the_residuals_are_the_two_gradients():
    x, head, targets = inputs(jnp.bfloat16, 4 * BLOCK)
    loss, kept = lm_model._loss_and_gradients(x, head, targets)
    assert [(k.shape, k.dtype) for k in kept] == [(x.shape, x.dtype), (head.shape, head.dtype)]
    d_x, d_head, d_targets = lm_model._scaled_gradients(kept, jnp.float32(1.0))
    assert d_targets is None
    np.testing.assert_array_equal(np.asarray(d_x, np.float32), np.asarray(kept[0], np.float32))
    assert float(loss) == pytest.approx(float(lm_model.next_token_loss_plain(x, head, targets)),
                                        rel=1e-5)


@pytest.mark.parametrize("name,passes", [("lfm2-small-test", 1), ("mellum-small-test", 1),
                                         ("xing-small-test", 2)])
def test_the_counter_says_how_many_passes_the_head_makes(name, passes, caplog):
    with open(os.path.join(DATA, name + ".json")) as f:
        config_file = json.load(f)
    config = program.program_config(config_file)
    model, init_fn, loss_fn = build_family(config.model)
    observations, actions = train_tokens.batch_spec(config, 64)
    abstract = jax.eval_shape(lambda r, o, a: init_fn(model, r, o, a), jax.random.PRNGKey(0),
                              observations, actions)["params"]
    params, _ = weights.make_weights(abstract, {}, 3, program.weight_gains(config_file))
    tokens = jax.random.randint(jax.random.PRNGKey(1), observations["tokens"].shape, 0, 100)
    batch = ({"tokens": tokens}, {"targets": jnp.roll(tokens, -1, axis=1).at[:, -5:].set(IGNORE)})
    lm_model._announce_loss.cache_clear()
    with caplog.at_level("INFO", logger=lm_model.__name__):
        (_, (out, _)), _ = jax.value_and_grad(loss_fn, has_aux=True)(
            params, {}, batch, jax.random.PRNGKey(2), True)
    assert float(out["counters"]["lm_loss/grad_in_forward_passes"]) == passes
    lines = [r.getMessage() for r in caplog.records
             if "gradient in the forward loop" in r.getMessage()]
    assert lines and all("'accumulator': 'float32'" in line for line in lines), lines
    assert f"'block_tokens': {BLOCK}, 'blocks_a_pass': {tokens.size // BLOCK}" in lines[0]

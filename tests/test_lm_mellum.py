"""The ``mellum`` decoder (sliding-window and full attention 3:1, a rotary rule
per kind of layer, softmax-routed experts, an untied head) at a small size on
the CPU against the plain reference (benchmarks/references/mellum.py), and the
family record that makes it one entry beside ``lfm2_moe``.

Four layers in the published pattern, d 64, 4 heads / 2 KV heads of 16, window
16, 16 experts top-4 of which 4 are held, vocabulary slice 128.  Tolerance 1e-5
(of a leaf's largest element) in float32: both sides do the same arithmetic in
another order of sums, which reads 1e-6 to 2e-6 (tests/test_lm_family.py).
"""

import math

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.references import mellum as ref
from rt1_tpu.data.tokens import IGNORE, feed_from_config
from rt1_tpu.models.lm import layers, moe
from rt1_tpu.models.lm.moe import RoutedFFN
from rt1_tpu.models.lm.spec import LMSpec, RotaryRule
from rt1_tpu.train import families
from rt1_tpu.train.configs import lfm2_moe, mellum
from rt1_tpu.train.train import build_family

TOL = 1e-5
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             moe_intermediate_size=32, num_experts=16, num_experts_per_tok=4,
             experts_held=(4, 4), vocab_held=128, seq_len=64, sliding_window=16,
             doc_len_median=24)


def small_config(dtype="float32", **changes):
    config = mellum.get_config()
    for k, v in dict(SMALL, **changes).items():
        config.model.lm[k] = v
    config.model.dtype = dtype
    return config


def overrides_of(config):
    """The configuration file's spelling: nested groups as dotted keys."""
    out = {}

    def walk(prefix, node):
        for k, v in node.items():
            if isinstance(v, dict):
                walk(f"{prefix}{k}.", v)
            else:
                out[prefix + k] = list(v) if isinstance(v, tuple) else v

    walk("model.lm.", config.model.lm.to_dict())
    return out


def reference_sizes(config):
    return dict(ref.sizes(overrides_of(config)), query_block=16, token_block=32)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)


def close(a, b, what="", tol=TOL):
    assert gap(a, b) <= tol, (what, gap(a, b))


@pytest.fixture(scope="module")
def world():
    config = small_config()
    model, init_fn, loss_fn = build_family(config.model)
    feed = feed_from_config(config, 3)
    host = next(feed)
    feed.close()
    batch = (host["observations"], host["actions"])
    abstract = jax.eval_shape(
        lambda r: init_fn(model, r, *batch), jax.random.PRNGKey(0))["params"]
    params, _ = weights.make_weights(abstract, {}, 11, {"experts": 2.0})
    return config, model, loss_fn, batch, params


# The bfloat16 program against the float32 reference, as benchmarks/check.py
# compares them on the chip: the loss, and the gap of each leaf's gradient norm
# over max(the reference's norm of that leaf, of the median leaf).  Read over five
# seeds of weights at this size: loss 1e-6 to 8e-5, median leaf 0.0014-0.0036,
# worst leaf 0.007-0.046 (a router or an expert stack: bfloat16 activations into
# the float32 router flip a near-tie or two of 2,048 assignments), cosine of the
# whole gradient 0.9991-0.9998.  Element by element a leaf differs by up to half
# its largest element at this size, so no element-wise band is stated.
BF16_BAND = {"loss": 1e-3, "median_leaf": 0.01, "worst_leaf": 0.1, "cosine": 0.995}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_against_the_reference(world, dtype):
    """Loss and every leaf's gradient; in float32 the logits of the live positions too."""
    from benchmarks import check

    config, model, loss_fn, batch, params = world
    if dtype != "float32":
        model, _, loss_fn = build_family(small_config(dtype).model)
    sz = reference_sizes(config)
    with jax.default_matmul_precision("highest"):
        out = model.apply({"params": params}, *batch, return_logits=True)
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, {}, batch, None, True), has_aux=True)(params)
        ref_logits = ref.logits_fn(params, batch[0]["tokens"], sz)
        (ref_loss, _), ref_grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, {}, batch, None, sz), has_aux=True)(params)
    counted = np.asarray(batch[1]["targets"]) != IGNORE
    live = np.flip(np.cumsum(np.flip(counted, 1), 1), 1) > 0
    assert 0 < (~live).sum() < live.size // 2
    got, want = (flax.traverse_util.flatten_dict(t, sep="/") for t in (grads, ref_grads))
    # 4 x (4 projections, 2 head norms, 2 norms, router, 3 stacks) + 2 tables + the last norm
    assert set(got) == set(want) and len(want) == 51
    assert "lm_head/embedding" in want and not any("expert_bias" in k for k in want)
    loss_gap = abs(float(loss) - float(ref_loss)) / abs(float(ref_loss))
    if dtype == "float32":
        close(np.asarray(out["logits"])[live], np.asarray(ref_logits)[live], "logits")
        assert loss_gap <= TOL
        for path in want:
            close(got[path], want[path], path)
    else:
        gaps = check.leaf_gaps(check.leaf_norms(grads), check.leaf_norms(ref_grads), sorted(want))
        flat = [np.concatenate([np.ravel(t[k]) for k in sorted(want)]) for t in (got, want)]
        cosine = float(flat[0] @ flat[1] / np.linalg.norm(flat[0]) / np.linalg.norm(flat[1]))
        assert loss_gap <= BF16_BAND["loss"]
        assert float(np.median(list(gaps.values()))) <= BF16_BAND["median_leaf"]
        assert max(gaps.values()) <= BF16_BAND["worst_leaf"], max(gaps, key=gaps.get)
        assert cosine >= BF16_BAND["cosine"]
    counters = out["counters"]
    assert float(counters["attention/window_layers"]) == 3.0
    assert float(counters["attention/full_layers"]) == 1.0
    assert 0 < float(counters["moe/assignments_held"]) <= live.sum() * 4 * 4
    assert float(counters["moe/fallback_layers"]) == 0.0


def test_a_decoder_of_full_layers_alone_keeps_its_counters():
    """``lfm2_moe``'s step has the outputs it had and the head's counter, which
    every decoder of the family has since PR 37; none of a sliding layer's."""
    config = lfm2_moe.get_config()
    for k, v in dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                     intermediate_size=96, moe_intermediate_size=32, num_experts=16,
                     experts_held=(4, 4), vocab_held=128, seq_len=64).items():
        config.model.lm[k] = v
    model, init_fn, _ = build_family(config.model)
    batch = ({"tokens": jnp.zeros((1, 64), jnp.int32)}, {"targets": jnp.zeros((1, 64), jnp.int32)})
    out = jax.eval_shape(
        lambda r: model.apply(init_fn(model, r, *batch), *batch), jax.random.PRNGKey(0))
    assert sorted(out["counters"]) == [
        "lm_loss/grad_in_forward_passes",
        "moe/assignments_held", "moe/fallback_layers", "moe/load_max_over_mean"]
    assert model.spec.tie_word_embeddings and model.spec.scoring_func == "sigmoid"
    assert model.spec.rotary == (("full_attention", RotaryRule("default", 1000000.0)),)


# ------------------------------------------------------------------ the window

def _mask_from_positions(s, window):
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    return (j <= i) & (i - window < j)


def _masked_attention(q, k, v, scale, window):
    """The (s, s) square with a mask built from positions, float32."""
    b, s, kvh, g, d = q.shape
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) * scale
    scores = jnp.where(_mask_from_positions(s, window)[None, None, None], scores, -jnp.inf)
    return jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(scores, axis=-1), v)


def _operands(s, d, dtype=jnp.float32, seed=6):
    key = jax.random.PRNGKey(seed)
    q = jax.random.normal(key, (2, s, 2, 2, d), dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 2, d), dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, 2, d), dtype)
    return q, k, v, jax.random.normal(jax.random.fold_in(key, 3), q.shape)


def _both_ways(fn, q, k, v, probe):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * probe),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("path,s,window", [
    ("square", 256, 100), ("square", 64, 100),          # several windows; shorter than one
    ("blockwise", 256, 100), ("blockwise", 256, 300), ("blockwise", 256, 64),
])
def test_the_window_is_the_mask_from_positions(path, s, window):
    """Output and the three gradients of the square and of the query blocks
    (which hand a block only the keys its window reaches) against the square
    under a mask built from positions."""
    q, k, v, probe = _operands(s, 16)
    fn = {"square": lambda q, k, v: layers.dense_attention(q, k, v, 0.25, window),
          "blockwise": lambda q, k, v: layers.blockwise_attention(q, k, v, 0.25, 64, window)}[path]
    with jax.default_matmul_precision("highest"):
        got, got_grads = _both_ways(fn, q, k, v, probe)
        want, want_grads = _both_ways(
            lambda q, k, v: _masked_attention(q, k, v, 0.25, window), q, k, v, probe)
        causal, _ = _both_ways(lambda q, k, v: layers.dense_attention(q, k, v, 0.25), q, k, v, probe)
    assert abs(float(got) - float(want)) <= TOL * abs(float(want))
    for a, b in zip(got_grads, want_grads):
        close(a, b, "attention gradient")
    # the window does something exactly where the sequence is longer than it
    assert (abs(float(causal) - float(want)) > 1e-3 * abs(float(want))) == (s > window)


SPLASH_TOL = 2.0 ** -6      # as tests/test_lm_family.py: bfloat16 keeps 8 bits


@pytest.mark.parametrize("s,window,blocks", [(1536, 300, 3), (256, 300, 1)])
def test_the_windowed_kernel_is_the_mask_from_positions(s, window, blocks):
    """The kernel a TPU runs for a sliding layer, in the library's interpret
    mode: three blocks a side under a window shorter than one (diagonal, cut and
    skipped blocks all there), and a sequence shorter than the window."""
    q, k, v, probe = _operands(s, 64, jnp.bfloat16, seed=8)
    assert s // layers.splash_blocks(s, window) == blocks
    scale = 64 ** -0.5
    out, grads = _both_ways(
        lambda q, k, v: layers.splash_attention(q, k, v, scale, window, interpret=True),
        q, k, v, probe)
    with jax.default_matmul_precision("highest"):
        want, want_grads = _both_ways(
            lambda q, k, v: _masked_attention(q, k, v, scale, window),
            *(x.astype(jnp.float32) for x in (q, k, v)), probe)
    assert abs(float(out) - float(want)) <= SPLASH_TOL * abs(float(want))
    for a, b, what in zip(grads, want_grads, "qkv"):
        assert a.dtype == jnp.bfloat16 and gap(a, b) <= SPLASH_TOL, (what, gap(a, b))


def test_one_kernel_object_a_shape_and_mask(monkeypatch):
    """The cache is keyed by the mask too; the span says which kernel a run
    timed, once a kernel object."""
    said = []
    monkeypatch.setattr(layers, "span", lambda name, **args: (
        said.append((name, args)), __import__("contextlib").nullcontext())[1])
    layers._splash_kernel.cache_clear()
    full = layers._splash_kernel(512, 4, None, True)
    local = layers._splash_kernel(512, 4, 128, True)
    assert full is not local
    assert layers._splash_kernel(512, 4, 128, True) is local
    assert [(n, a["mask"], a["window"], a["block_q"]) for n, a in said] == [
        ("lm/attention_kernel", "causal", 0, 512), ("lm/attention_kernel", "local", 128, 512)]


@pytest.mark.parametrize("backend,s,path", [
    ("tpu", 16384, "splash"), ("tpu", 512, "dense"), ("cpu", 16384, "blockwise")])
def test_a_sliding_layer_takes_its_window_down_every_path(monkeypatch, backend, s, path):
    taken = []
    for name in ("splash", "blockwise", "dense"):
        monkeypatch.setattr(layers, name + "_attention",
                            lambda *a, _name=name, **kw: taken.append((_name, a[-1])))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    layers.causal_attention(jax.ShapeDtypeStruct((1, s, 4, 8, 128), jnp.bfloat16), None, None,
                            0.1, 1024)
    assert taken == [(path, 1024)]


# ------------------------------------------------------------------ the rotary rules

def test_yarn_frequencies_by_hand():
    """D 8, theta 1e4, factor 4 from 64 positions, beta 4 / 1: c(r) = 8 ln(64 /
    (2 pi r)) / (2 ln 1e4); c(4) = 0.406 -> lo 0, c(1) = 1.008 -> hi 2; ramp
    (0, 1/2, 1, 1); f = (1, 0.1, 0.01, 0.001); inv_freq = f / 4 ramp + f (1 - ramp)."""
    rule = RotaryRule("yarn", 1e4, 4.0, 64, 4.0, 1.0, 1.25)
    inv_freq, factor = layers.rotary_frequencies(rule, 8)
    np.testing.assert_allclose(np.asarray(inv_freq), [1.0, 0.0625, 0.0025, 0.00025], rtol=1e-6)
    assert factor == 1.25
    plain, one = layers.rotary_frequencies(RotaryRule("default", 1e4), 8)
    np.testing.assert_allclose(np.asarray(plain), [1.0, 0.1, 0.01, 0.001], rtol=1e-6)
    assert one == 1.0
    # the reference's own, written from the same equations
    theirs, theirs_factor = ref.inverse_frequencies(
        {"rope_type": "yarn", "rope_theta": 1e4, "factor": 4.0, "beta_fast": 4.0,
         "beta_slow": 1.0, "original_max_position_embeddings": 64, "attention_factor": 1.25}, 8)
    np.testing.assert_allclose(np.asarray(theirs), np.asarray(inv_freq), rtol=1e-6)
    assert theirs_factor == 1.25


def test_the_published_yarn_entry():
    """128-wide heads, theta 5e5, factor 16 from 8,192: pairs 0-18 as they are,
    35-63 divided by 16, a ramp between; the factor as published."""
    spec = LMSpec.from_config(mellum.get_config().model.lm, jnp.bfloat16)
    rule = spec.rotary_rule("full_attention")
    assert spec.rotary_rule("sliding_attention") == RotaryRule("default", 500000.0)
    assert (spec.window("sliding_attention"), spec.window("full_attention")) == (1024, None)
    inv_freq, factor = layers.rotary_frequencies(rule, 128)
    plain = 500000.0 ** (-np.arange(64) / 64.0)
    c = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (2 * math.log(500000.0))  # noqa: E731
    assert (math.floor(c(32)), math.ceil(c(1))) == (18, 35)
    ratio = np.asarray(inv_freq) / plain
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-5)
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-5)
    assert np.all(np.diff(ratio[18:36]) < 0)
    assert factor == 1.2772588722239782
    # cos and sin carry the factor: a rotated head's norm is the factor times its own
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 128))
    np.testing.assert_allclose(
        np.linalg.norm(np.asarray(layers.rotary(x, rule)), axis=-1),
        factor * np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)


# ------------------------------------------------------------------ the routed layer

def _routed_layer(config, held):
    lm = config.model.lm.copy_and_resolve_references()
    lm.experts_held = held
    return RoutedFFN(LMSpec.from_config(lm, jnp.float32))


@pytest.mark.parametrize("seq", [32, 512])      # the slot path alone; the row path
def test_the_four_shares_add_up(seq):
    """What the shares [0,4) ... [12,16) of a 4 x 4-expert layer give, summed,
    is the uncut reference's output for the whole layer (softmax over all 16,
    top-4, renormalised)."""
    config = small_config()
    lm = config.model.lm
    layer = _routed_layer(config, (0, lm.num_experts))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, lm.hidden_size))
    abstract = jax.eval_shape(lambda r: layer.init(r, x), jax.random.PRNGKey(0))["params"]
    params, _ = weights.make_weights(abstract, {}, 5, {"experts": 4.0})
    assert set(params) == {"router", "experts"}         # no bias leaf
    total, rows = 0.0, 0.0
    for first in range(0, 16, 4):
        share = dict(params, experts=jax.tree.map(lambda a: a[first:first + 4], params["experts"]))
        out, counters = _routed_layer(config, (first, 4)).apply({"params": share}, x)
        total = total + out
        rows += float(counters["rows_held"])
        assert float(counters["fallback"]) == 0.0
    whole = ref.routed_ffn(x, params, reference_sizes(config), "highest", held=(0, 16))
    close(total, whole, "sum of the shares")
    assert rows == 2 * seq * 4       # every assignment computed once, none dropped
    idx, w = ref.route(x.reshape(-1, lm.hidden_size), params, reference_sizes(config))
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, rtol=1e-6)
    mine = moe.route(x.reshape(-1, lm.hidden_size), params["router"]["kernel"], None,
                     LMSpec.from_config(lm, jnp.float32))
    np.testing.assert_array_equal(np.sort(mine[0], -1), np.sort(idx, -1))


@pytest.mark.parametrize("k,n,tiling", [
    (2048, 3072, (512, 1024, 1024)),     # lfm2-24b-a2b: x [W1 | W3]
    (1536, 2048, (512, 1024, 1024)),     # lfm2-24b-a2b: h W2, as the constant had it
    (2304, 1792, (512, 768, 896)),       # mellum2-12b-a2.5b: x [W1 | W3]
    (896, 2304, (512, 896, 768)),        # mellum2-12b-a2.5b: h W2
    (64, 64, (512, 1024, 1024)),         # smaller than a tile: the kernel clips it
])
def test_the_tiling_rule(k, n, tiling):
    assert moe.megablox_tiling(k, n) == tiling


@pytest.mark.parametrize("s,window,block", [
    (8192, None, 1024), (16384, None, 1024), (1536, None, 512),    # lfm2-24b-a2b's, as they were
    (16384, 1024, 512), (1536, 300, 512), (256, 300, 256), (384, 64, 128)])
def test_the_block_rule(s, window, block):
    assert layers.splash_blocks(s, window) == block


def test_a_windowed_kernel_has_a_dq_kernel_of_its_own():
    """Under a window the fused backward's dq partials are nearly all zero
    (PERF.md section 6, PR 31): the causal layers keep it, the sliding ones do not."""
    said = {}
    layers._splash_kernel.cache_clear()
    import unittest.mock as mock

    with mock.patch.object(layers, "span", side_effect=lambda name, **a: (
            said.setdefault(a["mask"], a), __import__("contextlib").nullcontext())[1]):
        layers._splash_kernel(1024, 4, None, True)
        layers._splash_kernel(1024, 4, 256, True)
    assert said["causal"]["fused_bwd"] is True and said["causal"]["block_q"] == 1024
    assert said["local"]["fused_bwd"] is False and said["local"]["block_q"] == 512


def test_the_cells_row_buffer():
    """16 of 64 experts under top-8: a quarter of a step's 131,072 slots land
    here when the router is balanced; the buffer is twice that."""
    assert moe.row_capacity(16384 * 8, 16, 64) == 65536


# ------------------------------------------------------------------ the family record

def test_every_family_is_a_record():
    from rt1_tpu.train.configs import language_table, lava_tiny, tiny

    assert set(families.FAMILIES) == {"rt1", "lava", "lfm2_moe", "mellum", "xing4_0"}
    # every base config the repo ships names a family that has a record
    named = {module.__name__.rsplit(".", 1)[1]: module.get_config().model.get("family", "rt1")
             for module in (language_table, tiny, lava_tiny, lfm2_moe, mellum)}
    assert named == {"language_table": "rt1", "tiny": "rt1", "lava_tiny": "lava",
                     "lfm2_moe": "lfm2_moe", "mellum": "mellum"}
    # the two decoders are one record under two names: they differ in their base config
    assert families.FAMILIES["lfm2_moe"] is families.FAMILIES["mellum"]
    lm = families.FAMILIES["mellum"]
    assert lm.planned and not lm.pipelined and not lm.task_ids
    assert families.FAMILIES["rt1"].pipelined and families.FAMILIES["rt1"].task_ids
    assert not families.FAMILIES["lava"].planned
    with pytest.raises(ValueError, match="Unknown model family: 'gpt'"):
        families.family_of({"family": "gpt"})


def test_the_train_loop_names_no_language_model_family():
    import inspect

    from rt1_tpu.train import train

    source = inspect.getsource(train)
    for name in ("lfm2_moe", "mellum"):
        assert f'== "{name}"' not in source and f"== '{name}'" not in source
    assert "PLANNED_FAMILIES" not in source


def test_the_batch_spec_is_the_feeds(world):
    config, _, _, batch, _ = world
    spec = families.family_of(config.model).batch_spec(config)
    assert jax.tree.map(lambda a: (a.shape, a.dtype), spec) == jax.tree.map(
        lambda a: (a.shape, jnp.dtype(a.dtype)), batch)


def test_the_plan_has_a_rule_for_every_leaf(world):
    from rt1_tpu.parallel import ShardingPlan
    from rt1_tpu.parallel import sharding as shardlib

    config, _, _, _, params = world
    plan = ShardingPlan.from_config(config)
    assert plan.coverage(params) == []
    paths = [shardlib._path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(paths) == 51 and [p for p in paths if plan.spec_for(p) is None] == []
    assert plan.spec_for("lm_head/embedding") == plan.spec_for("embed/embedding")
    assert plan.spec_for("lm_head/embedding")[0] == "model"


def test_the_trainer_trains_the_family(tmp_path):
    """``python -m rt1_tpu.train.train --config .../mellum.py`` at a small
    size: train_and_evaluate -> make_train_step_fns, guard and health pack on."""
    from rt1_tpu.train.train import train_and_evaluate

    config = small_config(seq_len=32)
    assert config.obs.model_health and config.resilience.guard
    config.per_host_batch_size = 8      # the test's eight virtual devices
    config.num_steps = 3
    config.log_every_steps = 1
    config.eval_every_steps = 0
    config.checkpoint_every_steps = 100
    state = train_and_evaluate(config, str(tmp_path))
    assert int(state.step) == 3
    assert "lm_head" in state.params

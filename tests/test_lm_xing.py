"""The ``xing4_0`` decoder (latent attention with one rotary key shared by the
heads, four residual streams mixed by maps a token of which one is made doubly
stochastic by Sinkhorn rounds, sigmoid-routed experts beside a shared expert,
one multi-token-prediction module) at a small size on the CPU against the plain
reference (benchmarks/references/xing4_0.py), and the family record that makes
it one more entry.

Three layers (one dense, two routed) and the prediction module's block, d 64,
2 of 4 heads held (16 + 8 wide keys, 16 wide values, latents 24 / 16), 4
streams, 16 experts top-4 of which 4 are held, vocabulary slice 128.  Tolerance
1e-5 (of a leaf's largest element) in float32: both sides do the same
arithmetic in another order of sums (tests/test_lm_family.py reads 1e-6 to 2e-6
there); the maps' own leaves (phi, alpha, the maps' bias and norm) and the
norms' scales are held to 1e-4: their gradients are sums over every token of a
step, which the reference adds up a chunk of tokens at a time, and the maps'
pass back through 20 Sinkhorn rounds of divisions a sublayer (read: up to
2.6e-5 and 1.4e-5).
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.references import xing4_0 as ref
from rt1_tpu.data.tokens import IGNORE, feed_from_config
from rt1_tpu.models.lm import layers, model as lm_model, moe
from rt1_tpu.models.lm.spec import BlockSpec, LMSpec, RotaryRule
from rt1_tpu.train import families
from rt1_tpu.train.configs import xing4_0
from rt1_tpu.train.train import build_family

TOL = 1e-5
MAPS_TOL = 1e-4
GAINS = {"experts": 2.0, "expert_bias": 0.001, "phi": 0.5}
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=96, moe_intermediate_size=32, num_experts=16,
             experts_held=(4, 4), heads_held=(0, 2), vocab_held=128, seq_len=64,
             doc_len_median=24, num_hidden_layers=3, layer_types=("latent_attention",) * 3)


def small_config(dtype="float32", **changes):
    config = xing4_0.get_config()
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
    return dict(ref.sizes(overrides_of(config)), token_chunk=16, query_block=8, token_block=32, segment=2)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)


def tolerance(path):
    return MAPS_TOL if "_hc/" in path or path.endswith("/scale") else TOL


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
    params, _ = weights.make_weights(abstract, {}, 11, GAINS)
    return config, model, loss_fn, batch, params


@pytest.fixture(scope="module")
def gradients(world):
    """(program's out and gradient, reference's loss, terms and gradient), float32."""
    config, model, loss_fn, batch, params = world
    sz = reference_sizes(config)
    with jax.default_matmul_precision("highest"):
        (loss, (out, _)), grads = jax.value_and_grad(
            lambda p: loss_fn(p, {}, batch, None, True), has_aux=True)(params)
        (ref_loss, _), ref_grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, {}, batch, None, sz), has_aux=True)(params)
        terms = ref.loss_terms(params, batch, sz)
    return loss, out, grads, ref_loss, terms, ref_grads


def test_the_loss_and_both_its_terms(world, gradients):
    loss, out, _, ref_loss, (l_next, l_mtp), _ = gradients
    assert abs(float(loss) - float(ref_loss)) <= TOL * abs(float(ref_loss))
    assert abs(float(out["loss_next"]) - float(l_next)) <= TOL * float(l_next)
    assert abs(float(out["counters"]["mtp/loss"]) - float(l_mtp)) <= TOL * float(l_mtp)
    assert float(loss) == pytest.approx(float(l_next) + 0.3 * float(l_mtp), rel=1e-6)
    counters = out["counters"]
    assert float(counters["hyper_connection/sinkhorn_iters"]) == 20.0
    # the largest over every token and sublayer of the step: a tail (the test of
    # the rounds below says what 20 of them reach)
    assert 0 <= float(counters["hyper_connection/res_sum_err"]) <= 0.05
    assert float(counters["moe/fallback_layers"]) == 0.0
    # two routed layers and the module's block: every live token's held assignments
    assert 0 < float(counters["moe/assignments_held"]) <= 64 * 4 * 3


def test_the_logits_of_the_live_positions(world):
    config, model, _, batch, params = world
    with jax.default_matmul_precision("highest"):
        out = model.apply({"params": params}, *batch, return_logits=True)
        want = ref.logits_fn(params, batch[0]["tokens"], reference_sizes(config))
    live = np.asarray(lm_model.live_positions(batch[1]["targets"]))
    assert 0 < (~live).sum() < live.size // 2
    assert gap(np.asarray(out["logits"])[live], np.asarray(want)[live]) <= TOL


def test_the_first_gradient_leaf_by_leaf(gradients):
    _, _, grads, _, _, ref_grads = gradients
    got, want = (flax.traverse_util.flatten_dict(t, sep="/") for t in (grads, ref_grads))
    # a block: 5 projections, 2 latent norms, 2 norms, 2 x 4 leaves of maps = 17, + the FFN:
    # dense 3; routed router, bias, 3 stacks, 3 of the shared expert = 8.  3 blocks + the
    # module's (8 + 17 + merge, 3 norms) + 2 tables + the last norm
    assert set(got) == set(want) and len(want) == 20 + 25 + 25 + 29 + 3
    for path in want:
        if "expert_bias" in path:       # enters the selection only
            assert not np.any(np.asarray(got[path])) and not np.any(np.asarray(want[path]))
            continue
        assert np.any(np.asarray(want[path])), path
        assert gap(got[path], want[path]) <= tolerance(path), (path, gap(got[path], want[path]))


def _adam_steps(gradient, params, steps=3, lr=5e-4):
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    for i in range(1, steps + 1):
        grads = gradient(params)
        mu = jax.tree.map(lambda m, g: 0.9 * m + 0.1 * g, mu, grads)
        nu = jax.tree.map(lambda v, g: 0.999 * v + 0.001 * g * g, nu, grads)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - 0.9 ** i)) / (jnp.sqrt(v / (1 - 0.999 ** i)) + 1e-8),
            params, mu, nu)
    return params


def test_the_change_after_three_steps(world):
    """Three Adam steps on one batch, each side by its own gradient: the change
    of every leaf that has a gradient."""
    config, _, loss_fn, batch, params = world
    sz = reference_sizes(config)
    with jax.default_matmul_precision("highest"):
        mine = _adam_steps(jax.jit(jax.grad(lambda p: loss_fn(p, {}, batch, None, True)[0])), params)
        theirs = _adam_steps(
            jax.jit(jax.grad(lambda p: ref.loss_fn(p, {}, batch, None, sz)[0])), params)
    start, got, want = (flax.traverse_util.flatten_dict(t, sep="/")
                        for t in (params, mine, theirs))
    for path in want:
        if "expert_bias" in path:
            continue
        # Adam divides by the root of the second moment: where an element's
        # gradient is round-off the two sides may take opposite steps of one
        # learning rate, so the change is held as a whole leaf's norm (the maps'
        # leaves, whose gradients are the smallest, ten times looser: read 6e-3)
        d_got, d_want = (np.asarray(t[path] - start[path], np.float64) for t in (got, want))
        loose = 10 if "_hc/" in path else 1
        assert np.linalg.norm(d_got - d_want) <= loose * 2e-3 * np.linalg.norm(d_want), path
        assert abs(np.linalg.norm(d_got) / np.linalg.norm(d_want) - 1) <= loose * 1e-4, path


@pytest.mark.parametrize("control", ref.CONTROLS + ("int8",))
def test_a_control_is_not_the_program(world, gradients, control):
    """The reference with one mechanism taken away (or a precision down)
    differs from the program by far more than the tolerance."""
    config, _, _, batch, params = world
    _, _, grads, ref_loss, _, _ = gradients
    with jax.default_matmul_precision("highest"):
        (loss, _), control_grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, {}, batch, None, reference_sizes(config), control),
            has_aux=True)(params)
    assert abs(float(loss) - float(ref_loss)) > 10 * TOL * float(ref_loss)
    got, want = (flax.traverse_util.flatten_dict(t, sep="/") for t in (grads, control_grads))
    gaps = [gap(got[k], want[k]) for k in want if "expert_bias" not in k and np.any(want[k])]
    assert np.median(gaps) > 100 * TOL
    if control == "no_mtp":     # the module's leaves have no gradient at all
        assert not any(np.any(np.asarray(v)) for k, v in want.items() if k.startswith("mtp/"))


# ------------------------------------------------------------------ the shares

def _spec(config, **changes):
    lm = config.model.lm.copy_and_resolve_references()
    for k, v in changes.items():
        lm[k] = v
    return LMSpec.from_config(lm, jnp.float32)


@pytest.mark.parametrize("seq", [32, 512])      # the slot path alone; the row path
def test_the_shares_of_a_routed_block_add_up(seq):
    """One routed block, 2 head shares x 4 expert shares.  What every chip
    computes alike counts once: the maps, the latent down-projections and
    norms (the same leaves in every head share), the shared expert.  The head
    shares' mixer outputs add up to the uncut layer's, the expert shares'
    routed sums add up beside the shared expert's output once, and the streams
    written back from those sums are the uncut reference's block output."""
    config = small_config()
    lm = config.model.lm
    n, d = lm.hc_mult, lm.hidden_size
    whole = _spec(config, heads_held=(0, 4), experts_held=(0, 16))
    block = lm_model.Block(whole, BlockSpec("latent_attention", "moe"))
    streams = jax.random.normal(jax.random.PRNGKey(1), (n, 2, seq, d))
    abstract = jax.eval_shape(
        lambda r: block.init(r, streams, jnp.ones((2, seq), bool)), jax.random.PRNGKey(0))
    params, _ = weights.make_weights(abstract["params"], {}, 5, dict(GAINS, experts=4.0))
    sz = dict(reference_sizes(config), heads_held=[0, 4], experts_held=[0, 16])
    eps = lm.rms_norm_eps

    def maps_of(name, x):
        return lm_model.HyperConnection(whole).apply({"params": params[name]}, x)[:3]

    def norm(name, x):
        return layers.RMSNorm(eps, jnp.float32).apply({"params": params[name]}, x)

    with jax.default_matmul_precision("highest"):
        h_pre, h_post, h_res = maps_of("mixer_hc", streams)
        x = norm("mixer_norm", lm_model.mix_in(streams, h_pre))
        mixed = 0.0
        for first in (0, 2):            # W_qb's, W_kvb's columns and W_o's rows of two heads
            p = dict(params["mixer"])
            p["q_b_proj"] = {"kernel": params["mixer"]["q_b_proj"]["kernel"].reshape(
                24, 4, 24)[:, first:first + 2].reshape(24, 48)}
            p["kv_b_proj"] = {"kernel": params["mixer"]["kv_b_proj"]["kernel"].reshape(
                16, 4, 32)[:, first:first + 2].reshape(16, 64)}
            p["o_proj"] = {"kernel": params["mixer"]["o_proj"]["kernel"].reshape(
                4, 16, d)[first:first + 2].reshape(32, d)}
            mixed = mixed + layers.LatentAttention(
                _spec(config, heads_held=(first, 2))).apply({"params": p}, x)
        streams_2 = lm_model.mix_out(streams, h_res, h_post, mixed)
        h_pre, h_post, h_res = maps_of("ffn_hc", streams_2)
        x = norm("ffn_norm", lm_model.mix_in(streams_2, h_pre))
        routed, rows = 0.0, 0.0
        for first in range(0, 16, 4):
            p = {"router": params["ffn"]["router"], "expert_bias": params["ffn"]["expert_bias"],
                 "experts": jax.tree.map(lambda a: a[first:first + 4], params["ffn"]["experts"])}
            out, counters = moe.RoutedFFN(
                _spec(config, experts_held=(first, 4), n_shared_experts=0)).apply({"params": p}, x)
            routed = routed + out
            rows += float(counters["rows_held"])
        shared = moe.shared_expert(whole, name=None).apply(
            {"params": params["ffn"]["shared_expert"]}, x)
        total = lm_model.mix_out(streams_2, h_res, h_post, routed + shared)
        want = ref.block(jnp.moveaxis(streams, 0, 2), params, "moe", sz, "highest")
        uncut, _, _ = block.apply({"params": params}, streams, jnp.ones((2, seq), bool))
    assert rows == 2 * seq * 4       # every assignment computed once, none dropped
    assert gap(jnp.moveaxis(total, 0, 2), want) <= TOL
    assert gap(jnp.moveaxis(uncut, 0, 2), want) <= TOL
    # and the parts matter: without the second head share, or the shared expert, it is not
    assert gap(shared, jnp.zeros_like(shared) + 1e-30) > 1 and gap(mixed, 0 * mixed + 1e-30) > 1


# ------------------------------------------------------------------ the maps

@pytest.mark.parametrize("start,scale,worst,median", [
    (0.0, 0.5, 1e-4, 1e-5),                     # logits around zero: every token within 1e-4
    (lm_model.RES_START, 0.5, 1e-2, 1e-5),      # the program's start: a tail of tokens is not
    (4.0, 0.5, 5e-2, 1e-2),                     # nearer the identity the rounds converge slower
])
def test_sinkhorn_rows_and_columns_sum_to_one(start, scale, worst, median):
    """20 rounds: columns to the eps (they are divided last), rows as far as the
    rounds converge, at the square of the limit's second singular value."""
    logits = scale * jax.random.normal(jax.random.PRNGKey(2), (4, 4, 3, 500))
    logits = logits + start * jnp.eye(4)[:, :, None, None]
    m = np.asarray(lm_model.sinkhorn(logits, 20, 1e-6, (-30.0, 30.0)))
    assert np.all(m > 0)
    assert np.abs(m.sum(axis=0) - 1).max() <= 1e-5
    rows = np.abs(m.sum(axis=1) - 1).max(axis=0)
    assert rows.max() <= worst and np.median(rows) <= median
    # one round is not enough: the rounds do the work
    once = np.asarray(lm_model.sinkhorn(logits, 1, 1e-6, (-30.0, 30.0)))
    assert np.abs(once.sum(axis=1) - 1).max() > max(1e-3, 10 * rows.max())
    # the reference's plain loop, token-major
    theirs = np.asarray(ref.sinkhorn(jnp.moveaxis(logits, (0, 1), (2, 3)), 20, 1e-6, [-30.0, 30.0]))
    np.testing.assert_allclose(np.moveaxis(theirs, (2, 3), (0, 1)), m, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("beyond", [31.0, 80.0, 1e4])
def test_the_clamp_engages_at_thirty(beyond):
    """Past +-30 a logit reads as +-30 (exp(80) would overflow float32's
    sums; exp(1e4) is inf)."""
    base = np.zeros((4, 4, 1, 1), np.float32)
    base[0, 1], base[2, 3] = 30.0, -30.0
    past = base.copy()
    past[0, 1], past[2, 3] = beyond, -beyond
    at, over = (np.asarray(lm_model.sinkhorn(jnp.asarray(x), 20, 1e-6, (-30.0, 30.0)))
                for x in (base, past))
    assert np.all(np.isfinite(over)) and np.array_equal(at, over)
    inside = base.copy()
    inside[0, 1] = 29.0
    assert not np.array_equal(
        at, np.asarray(lm_model.sinkhorn(jnp.asarray(inside), 20, 1e-6, (-30.0, 30.0))))


def test_a_zero_leaf_is_a_diagonally_dominant_map():
    """b_res = RES_START I + the bias leaf: with phi and the bias at zero every
    token's H_res has e^2 / (e^2 + 3) on its diagonal, H_pre is 1/2, H_post 1."""
    spec = _spec(small_config())
    streams = jax.random.normal(jax.random.PRNGKey(3), (4, 1, 8, 64))
    module = lm_model.HyperConnection(spec)
    params = module.init(jax.random.PRNGKey(0), streams)["params"]
    params = dict(params, phi={"kernel": jnp.zeros_like(params["phi"]["kernel"])})
    h_pre, h_post, h_res, err = module.apply({"params": params}, streams)
    diagonal = np.exp(2.0) / (np.exp(2.0) + 3)
    np.testing.assert_allclose(np.asarray(h_res[0, 0]), diagonal, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_res[0, 1]), (1 - diagonal) / 3, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_pre), 0.5)
    np.testing.assert_allclose(np.asarray(h_post), 1.0)
    assert float(err) <= 1e-5


# ------------------------------------------------------------------ the second loss's targets

def test_the_second_targets_at_tails_and_at_ignore():
    """t_{i+2} counts where t_{i+1} does too: never at a sequence's last
    position, nor at the last counted one before the padding."""
    targets = jnp.asarray([[5, 6, 7, 8, IGNORE, IGNORE],      # a padded tail
                           [1, 2, 3, 4, 5, 6],                # a full sequence
                           [IGNORE] * 6,                      # nothing counts
                           [9, IGNORE, 3, 4, IGNORE, 2]])     # holes (no feed makes them)
    want = [[6, 7, 8, IGNORE, IGNORE, IGNORE],
            [2, 3, 4, 5, 6, IGNORE],
            [IGNORE] * 6,
            [IGNORE, IGNORE, 4, IGNORE, IGNORE, IGNORE]]
    np.testing.assert_array_equal(np.asarray(ref.mtp_targets(targets)), want)
    # the program's own, read back through its loss: the feed's batch counts
    # one target fewer a sequence in the second term than in the first
    config = small_config()
    feed = feed_from_config(config, 4)
    host = next(feed)
    feed.close()
    first = np.asarray(host["actions"]["targets"])
    second = np.asarray(ref.mtp_targets(jnp.asarray(first)))
    counted = (first != IGNORE).sum(axis=1)
    assert np.array_equal((second != IGNORE).sum(axis=1), np.maximum(counted - 1, 0))
    live_1 = np.asarray(lm_model.live_positions(jnp.asarray(first)))
    live_2 = np.asarray(lm_model.live_positions(jnp.asarray(second)))
    assert np.array_equal(live_2.sum(axis=1), np.maximum(live_1.sum(axis=1) - 1, 0))


def test_the_program_counts_the_same_second_targets(world):
    """Hiding a target from the batch hides two terms of the second loss and
    one of the first, in the program as in the reference."""
    config, model, loss_fn, batch, params = world
    observations, actions = batch
    hidden = np.asarray(actions["targets"]).copy()
    hidden[:, 5] = IGNORE
    changed = (observations, {"targets": jnp.asarray(hidden)})
    with jax.default_matmul_precision("highest"):
        out = model.apply({"params": params}, *changed)
        l_next, l_mtp = ref.loss_terms(params, changed, reference_sizes(config))
    assert abs(float(out["loss_next"]) - float(l_next)) <= TOL * float(l_next)
    assert abs(float(out["counters"]["mtp/loss"]) - float(l_mtp)) <= TOL * float(l_mtp)


# ------------------------------------------------------------------ the spec and the record

def test_the_spec_from_the_published_keys():
    spec = LMSpec.from_config(xing4_0.get_config().model.lm, jnp.bfloat16)
    assert [b.mixer for b in spec.blocks] == ["latent_attention"] * 5
    assert [b.ffn for b in spec.blocks] == ["dense"] + ["moe"] * 4
    assert (spec.hidden_size, spec.q_lora_rank, spec.kv_lora_rank, spec.qk_nope_head_dim,
            spec.qk_rope_head_dim, spec.v_head_dim, spec.head_dim) == (
                3584, 768, 512, 128, 64, 128, 192)
    assert (spec.num_heads, spec.heads_held, spec.experts_held, spec.vocab_held) == (
        32, (0, 4), (0, 8), 16384)
    assert (spec.intermediate_size, spec.moe_intermediate_size, spec.num_experts,
            spec.experts_per_tok, spec.n_shared_experts) == (9216, 1024, 64, 4, 1)
    assert (spec.scoring_func, spec.use_expert_bias, spec.norm_topk_prob,
            spec.routed_scaling_factor, spec.tie_word_embeddings) == (
                "sigmoid", True, True, 2.0, False)
    assert (spec.hc_mult, spec.hc_sinkhorn_iters, spec.hc_eps, spec.hc_clamp) == (
        4, 20, 1e-6, (-30.0, 30.0))
    assert (spec.mtp_layers, spec.mtp_loss_weight) == (1, 0.3)
    # YaRN from 4,096 by 64: m = 0.1 ln 64 + 1; cos and sin carry mscale / mscale_all_dim = 1
    rule = spec.rotary_rule("latent_attention")
    assert rule == RotaryRule("yarn", 10000.0, 64.0, 4096, 32.0, 1.0, 1.0)
    assert spec.softmax_scale_factor == pytest.approx(1.4158883 ** 2, rel=1e-6)
    assert spec.window("latent_attention") is None


def test_yarn_over_the_rotary_part():
    """32 pairs of the 64-wide rotary key, theta 1e4, factor 64 from 4,096, beta
    32 / 1: the program's frequencies are the reference's, written from the
    same equations, and the slow pairs are divided by 64."""
    spec = LMSpec.from_config(xing4_0.get_config().model.lm, jnp.bfloat16)
    mine, factor = layers.rotary_frequencies(spec.rotary_rule("latent_attention"), 64)
    sz = ref.sizes(overrides_of(xing4_0.get_config()))
    theirs, their_factor, scale = ref.inverse_frequencies(sz["theta"], sz["rope_scaling"], 64)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(theirs), rtol=1e-6)
    assert factor == their_factor == 1.0 and scale == pytest.approx(2.00474, rel=1e-5)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    ratio = np.asarray(mine) / plain
    np.testing.assert_allclose(ratio[:11], 1.0, rtol=1e-5)      # lo = floor(c(32)) = 10
    np.testing.assert_allclose(ratio[23:], 1 / 64, rtol=1e-5)   # hi = ceil(c(1)) = 23
    plain_ref, one, unit = ref.inverse_frequencies(sz["theta"], {}, 64)
    np.testing.assert_allclose(np.asarray(plain_ref), plain, rtol=1e-6)
    assert one == 1.0 and unit == 1.0


@pytest.mark.parametrize("wrong,message", [
    (dict(heads_held=(3, 2)), "heads_held"),
    (dict(layer_types=("latent_attention", "full_attention", "latent_attention")), "mix with no"),
    (dict(num_nextn_predict_layers=2), "one multi-token-prediction module"),
])
def test_a_share_outside_the_model_is_refused(wrong, message):
    with pytest.raises(ValueError, match=message):
        LMSpec.from_config(small_config(**wrong).model.lm, jnp.float32)


def test_the_family_is_one_more_name_for_the_record():
    assert families.FAMILIES["xing4_0"] is families.FAMILIES["mellum"]
    assert xing4_0.get_config().model.family == "xing4_0"
    shapes = families.family_of(xing4_0.get_config().model).batch_spec(xing4_0.get_config())
    assert shapes[0]["tokens"].shape == (1, 8192)


def test_the_plan_has_a_rule_for_every_leaf(world):
    """``planned``: parallel/plan.py's rules name every leaf of the family once."""
    from rt1_tpu.parallel import ShardingPlan

    config, _, _, _, params = world
    plan = ShardingPlan.from_config(config, devices=jax.devices()[:1])
    paths = list(flax.traverse_util.flatten_dict(params, sep="/"))
    assert len(paths) == 102 and all(plan.spec_for(path) is not None for path in paths)
    plan.check_coverage(params)         # strict mode would abort on a leaf no rule names


# ------------------------------------------------------------------ the kernel with two widths

SPLASH_TOL = 2.0 ** -6      # as tests/test_lm_family.py: bfloat16 keeps 8 bits


def test_the_kernel_takes_values_narrower_than_keys():
    """The path a TPU takes for the latent layer, in the library's interpret
    mode: keys of 192, values of 128, output and the three gradients against
    the square in float32."""
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (1, 1024, 2, 1, 192), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (1, 1024, 2, 192), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (1, 1024, 2, 128), jnp.bfloat16)
    probe = jax.random.normal(jax.random.fold_in(key, 3), (1, 1024, 2, 1, 128))
    scale = 192 ** -0.5 * 2.0

    def both_ways(fn, q, k, v):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * probe),
            argnums=(0, 1, 2))(q, k, v)

    out, grads = both_ways(
        lambda q, k, v: layers.splash_attention(q, k, v, scale, interpret=True), q, k, v)
    with jax.default_matmul_precision("highest"):
        want, want_grads = both_ways(
            lambda q, k, v: layers.dense_attention(q, k, v, scale),
            *(x.astype(jnp.float32) for x in (q, k, v)))
    assert abs(float(out) - float(want)) <= SPLASH_TOL * abs(float(want))
    for a, b, what in zip(grads, want_grads, "qkv"):
        assert a.shape == b.shape and gap(a, b) <= SPLASH_TOL, (what, gap(a, b))

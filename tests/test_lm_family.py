"""The block-spec decoder family (``lfm2_moe``) at a small size on the CPU,
float32, against the plain reference (benchmarks/references/lfm2_moe.py):
5 layers in the published pattern, d 64, 4 heads / 2 KV heads, 16 sigmoid-routed
experts top-4 of which 4 are held, vocabulary slice 128.

Tolerance 1e-5 (of a leaf's largest element): both sides are float32 and do
the same arithmetic; they differ in the order of sums only (a sort and a
grouped product against a masked loop over experts, query blocks against the
keys before them against the whole masked square), which reads 1e-6 to 2e-6.
"""

import flax
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import weights
from benchmarks.references import lfm2_moe as ref
from rt1_tpu.data.tokens import IGNORE, PackedTokenFeed, feed_from_config
from rt1_tpu.models.lm import layers, moe
from rt1_tpu.models.lm.moe import RoutedFFN
from rt1_tpu.models.lm.spec import LMSpec
from rt1_tpu.train.configs import lfm2_moe
from rt1_tpu.train.train import build_family

TOL = 1e-5
SMALL = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
             intermediate_size=96, moe_intermediate_size=32, num_experts=16, experts_held=(4, 4),
             vocab_held=128, seq_len=64)


def small_config(**changes):
    config = lfm2_moe.get_config()
    for k, v in dict(SMALL, **changes).items():
        config.model.lm[k] = v
    config.model.dtype = "float32"
    return config


def overrides_of(config):
    return {"model.lm." + k: (list(v) if isinstance(v, tuple) else v)
            for k, v in config.model.lm.to_dict().items()}


def reference_sizes(config):
    return dict(ref.sizes(overrides_of(config)), query_block=16, token_block=32)


def close(a, b, what=""):
    a, b = np.asarray(a), np.asarray(b)
    gap = float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)
    assert gap <= TOL, (what, gap)


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
    params, _ = weights.make_weights(abstract, {}, 11, {"experts": 2.0, "expert_bias": 0.02})
    return config, model, loss_fn, batch, params


def test_program_against_the_reference(world):
    config, model, loss_fn, batch, params = world
    sz = reference_sizes(config)
    with jax.default_matmul_precision("highest"):
        out = model.apply({"params": params}, *batch, return_logits=True)
        (loss, _), grads = jax.value_and_grad(
            lambda p: loss_fn(p, {}, batch, None, True), has_aux=True)(params)
        ref_logits = ref.logits_fn(params, batch[0]["tokens"], sz)
        (ref_loss, _), ref_grads = jax.value_and_grad(
            lambda p: ref.loss_fn(p, {}, batch, None, sz), has_aux=True)(params)
    # the positions after a sequence's last counted target get no rows in the
    # routed layers: nothing the loss reads depends on them, their logits do
    counted = np.asarray(batch[1]["targets"]) != IGNORE
    live = np.flip(np.cumsum(np.flip(counted, 1), 1), 1) > 0
    assert 0 < (~live).sum() < live.size // 2
    close(np.asarray(out["logits"])[live], np.asarray(ref_logits)[live], "logits")
    assert float(out["counters"]["moe/assignments_held"]) <= live.sum() * 4 * 4
    assert abs(float(loss) - float(ref_loss)) <= TOL * abs(float(ref_loss))
    got, want = (flax.traverse_util.flatten_dict(t, sep="/") for t in (grads, ref_grads))
    assert set(got) == set(want) and len(want) == 53
    for path in want:
        close(got[path], want[path], path)
    # the expert bias enters the selection only
    assert all(float(jnp.max(jnp.abs(v))) == 0.0 for k, v in got.items() if "expert_bias" in k)
    assert float(out["counters"]["moe/assignments_held"]) > 0
    assert float(out["counters"]["moe/fallback_layers"]) == 0.0


def _routed_layer(config, held):
    lm = config.model.lm.copy_and_resolve_references()
    lm.experts_held = held
    return RoutedFFN(LMSpec.from_config(lm, jnp.float32))


def _full_layer_params(config, seed=5):
    """A routed layer's leaves with ALL the router's experts' stacks."""
    lm = config.model.lm
    layer = _routed_layer(config, (0, lm.num_experts))
    x = jnp.zeros((2, 8, lm.hidden_size))
    abstract = jax.eval_shape(lambda r: layer.init(r, x), jax.random.PRNGKey(0))["params"]
    params, _ = weights.make_weights(abstract, {}, seed, {"experts": 4.0, "expert_bias": 0.02})
    return params


def _share(params, first, count):
    cut = flax.core.unfreeze(jax.tree.map(lambda a: a, params))
    cut["experts"] = jax.tree.map(lambda a: a[first:first + count], params["experts"])
    return cut


# A layer of 2 x 32 tokens has fewer slots than one row tile, so its row buffer
# is all of them (the slot path, no branch); at 2 x 512 a quarter share's buffer
# is half the slots (the row path, the slot path behind it).
SEQ_BY_PATH = {"slots": 32, "rows": 512}


@pytest.mark.parametrize("path", list(SEQ_BY_PATH))
def test_the_shares_add_up(path):
    """What the shares [0,4) ... [12,16) give, summed, is the uncut layer."""
    config = small_config()
    params = _full_layer_params(config)
    seq = SEQ_BY_PATH[path]
    assert (moe.row_capacity(2 * seq * 4, 4, 16) < 2 * seq * 4) == (path == "rows")
    x = jax.random.normal(jax.random.PRNGKey(1), (2, seq, config.model.lm.hidden_size))
    total = 0.0
    rows = 0.0
    for first in range(0, 16, 4):
        out, counters = _routed_layer(config, (first, 4)).apply(
            {"params": _share(params, first, 4)}, x)
        total = total + out
        rows += float(counters["rows_held"])
        assert float(counters["fallback"]) == 0.0
    whole = ref.routed_ffn(x, params, reference_sizes(config), "highest", held=(0, 16))
    close(total, whole, "sum of the shares")
    assert rows == 2 * seq * 4       # every assignment computed once, none dropped


@pytest.mark.parametrize("capacity", [None, 64, 96])
def test_dropless_under_imbalance(world, monkeypatch, capacity):
    """Every token selects held expert 5: its group is the whole batch, and
    with the others' 35 rows it overflows a row buffer of 64 or 96 rows."""
    if capacity is not None:
        monkeypatch.setattr(moe, "row_capacity", lambda *shape: capacity)
    config = small_config()
    params = _full_layer_params(config)
    lm = config.model.lm
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, lm.hidden_size))
    # a router whose expert 5 wins everywhere, through the selection bias
    params = flax.core.unfreeze(params)
    params["expert_bias"]["kernel"] = params["expert_bias"]["kernel"].at[5].set(10.0)
    first, count = lm.experts_held
    out, counters = _routed_layer(config, (first, count)).apply(
        {"params": _share(params, first, count)}, x)
    sz = reference_sizes(config)
    want = ref.routed_ffn(x, _share(params, first, count), sz, "highest")
    close(out, want, "skewed router")
    idx, _ = ref.route(x.reshape(-1, lm.hidden_size), params, sz)
    per_expert = np.array([(np.asarray(idx) == e).sum() for e in range(first, first + count)])
    assert per_expert[5 - first] == 64
    assert float(counters["rows_max"]) == 64.0
    assert float(counters["rows_max"] / counters["rows_mean"]) == pytest.approx(
        per_expert.max() / per_expert.mean())
    assert per_expert.sum() == 99
    assert float(counters["fallback"]) == (0.0 if capacity is None else 1.0)


def _layer_both_ways(layer, params, x, probe):
    def loss(params, x):
        out, counters = layer.apply({"params": params}, x)
        return jnp.sum(out * probe), (out, counters)

    (_, (out, counters)), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(
        params, x)
    return out, counters, grads


@pytest.mark.parametrize("room", ["spare", "none", "short"])
def test_the_row_path_is_the_slot_path(monkeypatch, room):
    """Outputs and gradients (x, the three stacks, the router) with a row
    buffer that has rows to spare, exactly the rows held, and one too few
    (the slot path under the branch) against the slot path with no branch."""
    config = small_config()
    lm = config.model.lm
    first, count = lm.experts_held
    layer = _routed_layer(config, (first, count))
    params = _share(_full_layer_params(config), first, count)
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 32, lm.hidden_size))
    probe = jax.random.normal(jax.random.PRNGKey(10), x.shape)
    with jax.default_matmul_precision("highest"):
        want, counters, want_grads = _layer_both_ways(layer, params, x, probe)
        held = int(counters["rows_held"])
        assert 32 < held < 128 and float(counters["fallback"]) == 0.0
        capacity = {"spare": 128, "none": held, "short": held - 1}[room]
        monkeypatch.setattr(moe, "row_capacity", lambda *shape: capacity)
        got, counters, got_grads = _layer_both_ways(layer, params, x, probe)
    assert float(counters["fallback"]) == (1.0 if room == "short" else 0.0)
    assert int(counters["rows_held"]) == held
    flat_got = flax.traverse_util.flatten_dict(got_grads[0], sep="/")
    flat_want = flax.traverse_util.flatten_dict(want_grads[0], sep="/")
    assert set(flat_got) == {"router/kernel", "expert_bias/kernel", "experts/w1/kernel",
                             "experts/w3/kernel", "experts/w2/kernel"}
    for what, a, b in [("out", got, want), ("x", got_grads[1], want_grads[1])] + [
            (path, flat_got[path], flat_want[path]) for path in flat_want]:
        a, b = np.asarray(a), np.asarray(b)
        assert float(np.max(np.abs(a - b))) <= 1e-6 * float(np.max(np.abs(b))) + 1e-30, what
    assert float(jnp.max(jnp.abs(flat_got["router/kernel"]))) > 0.0


def _poisoned_grouped_matmul():
    """``moe.grouped_matmul`` as megablox leaves it on the chip: the rows after
    the last group are not computed, and here hold NaN both ways
    (``lax.ragged_dot`` on the CPU writes zeros there, which hides a read)."""
    def live_rows(rows, group_sizes):
        return (jnp.arange(rows.shape[0]) < jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def product(rows, stack, group_sizes):
        live = live_rows(rows, group_sizes)
        out = jax.lax.ragged_dot(jnp.where(live, rows, 0), stack, group_sizes)
        return jnp.where(live, out, jnp.nan)

    def forward(rows, stack, group_sizes):
        return product(rows, stack, group_sizes), (rows, stack, group_sizes)

    def backward(res, d_out):
        rows, stack, group_sizes = res
        live = live_rows(rows, group_sizes)
        _, back = jax.vjp(lambda r, s: jax.lax.ragged_dot(r, s, group_sizes),
                          jnp.where(live, rows, 0), stack)
        d_rows, d_stack = back(jnp.where(live, d_out, 0))
        return jnp.where(live, d_rows, jnp.nan), d_stack, None

    product.defvjp(forward, backward)
    return product


# rows held in a buffer of 64 rows: none, one, a few, one short of all, all
@pytest.mark.parametrize("rows_held", [0, 1, 16, 63, 64])
def test_the_row_path_reads_no_row_after_the_last_held(monkeypatch, rows_held):
    """The row path (the rows after the last held row unspecified: NaN here,
    both ways) against the slot path with no branch: the output and the
    gradients of x, the three stacks and the router."""
    monkeypatch.setattr(moe, "grouped_matmul", _poisoned_grouped_matmul())
    config = small_config()
    spec = _routed_layer(config, (4, 4)).spec
    tokens, k, d = 64, spec.experts_per_tok, spec.hidden_size
    n, capacity = tokens * k, 64
    assert capacity < n
    params = _share(_full_layer_params(config), 4, 4)
    stacks = [params["experts"][name]["kernel"] for name in ("w1", "w3", "w2")]
    x = jax.random.normal(jax.random.PRNGKey(3), (tokens, d))
    probe = jax.random.normal(jax.random.PRNGKey(4), (tokens, d))
    # every assignment to an absent expert, then ``rows_held`` of them moved to
    # held experts 4..7: slots 0, 3, 6, ... so that some tokens hold two rows
    idx = np.tile(np.arange(8, 8 + k), (tokens, 1))
    flat = idx.reshape(-1)
    chosen = (np.arange(rows_held) * 3) % n
    flat[chosen] = 4 + np.arange(rows_held) % 4
    idx = jnp.asarray(flat.reshape(tokens, k), jnp.int32)
    live = jnp.ones((tokens,), bool)

    def both_ways(capacity):
        def loss(x, router, w1, w3, w2):
            scores = jax.nn.sigmoid(x @ router)
            weights = jnp.take_along_axis(scores, idx, axis=-1)
            out, group_sizes, fell_back = moe.held_experts_ffn(
                x, idx, weights, live, w1, w3, w2, spec, capacity)
            return jnp.sum(out * probe), (out, group_sizes, fell_back)

        (_, aux), grads = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True)(
            x, params["router"]["kernel"], *stacks)
        return aux, grads

    with jax.default_matmul_precision("highest"):
        (want, _, _), want_grads = both_ways(n)
        (got, group_sizes, fell_back), got_grads = both_ways(capacity)
    assert int(jnp.sum(group_sizes)) == rows_held and not bool(fell_back)
    for what, a, b in [("out", got, want)] + [
            (name, a, b) for name, a, b in zip(
                ("x", "router", "w1", "w3", "w2"), got_grads, want_grads)]:
        a, b = np.asarray(a), np.asarray(b)
        assert np.isfinite(a).all() and np.isfinite(b).all(), what
        assert float(np.max(np.abs(a - b))) <= 1e-6 * float(np.max(np.abs(b))) + 1e-30, what
        assert rows_held == 0 or float(np.max(np.abs(b))) > 0.0, what


def test_the_combine_goes_back_as_jax_would_take_it():
    """``token_sums``'s hand-written way back (bfloat16 rows gathered, widened
    after) against jax's own transpose of the same sums, in bfloat16 as the
    step runs it: the same numbers to the last bit."""
    tokens, k, d, capacity = 32, 4, 16, 48
    key = jax.random.PRNGKey(5)
    out_rows = jax.random.normal(key, (capacity, d), jnp.bfloat16)
    weights = jax.random.uniform(jax.random.fold_in(key, 1), (tokens, k))
    d_out = jax.random.normal(jax.random.fold_in(key, 2), (tokens, d), jnp.bfloat16)
    slot = jax.random.permutation(jax.random.fold_in(key, 3), tokens * k)[:capacity]
    index = (slot // k, slot, jnp.arange(capacity) < 40)

    def plain(out_rows, weights):
        return moe.token_sums.__wrapped__(out_rows, weights, *index)

    want, back = jax.vjp(plain, out_rows, weights)
    got, hand = jax.vjp(lambda r, w: moe.token_sums(r, w, *index), out_rows, weights)
    assert np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    for a, b in zip(hand(d_out), back(d_out)):
        assert a.dtype == b.dtype
        assert np.array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    assert float(jnp.max(jnp.abs(hand(d_out)[1]))) > 0.0


@pytest.mark.parametrize("n, held, experts, rows", [
    (2 * 8192 * 4, 8, 64, 16384),       # the token cell: a quarter of the slots
    (2 * 512 * 4, 4, 16, 2048),         # twice the balanced share
    (2 * 8192 * 4, 3, 64, 6144),        # 2 x 3,072, a whole number of row tiles
    (2 * 520 * 4, 4, 16, 2560),         # 2 x 1,040 = 2,080, rounded up to the tile
    (2 * 32 * 4, 4, 16, 256),           # fewer slots than a tile: all of them
    (2 * 512 * 4, 16, 16, 4096),        # every expert held: all the slots
    (2 * 512 * 4, 9, 16, 4096),         # more than half held: all the slots
])
def test_the_capacity_rule(n, held, experts, rows):
    assert moe.row_capacity(n, held, experts) == rows
    assert rows == n or (rows % moe.ROW_TILE == 0 and rows >= 2 * n * held / experts)


@pytest.mark.parametrize("held, branches", [((4, 4), True), ((0, 16), False)])
def test_a_branch_only_where_the_buffer_is_smaller(held, branches):
    """A layer that holds every expert has all the slots for a buffer: the
    slot path, forward and backward, with no ``cond`` traced."""
    config = small_config()
    layer = _routed_layer(config, held)
    x = jnp.zeros((2, 512, config.model.lm.hidden_size))
    params = jax.eval_shape(lambda r: layer.init(r, x), jax.random.PRNGKey(0))["params"]

    def loss(params, x):
        return jnp.sum(layer.apply({"params": params}, x)[0])

    forward = str(jax.make_jaxpr(loss)(params, x))
    backward = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, x))
    assert (" cond[" in forward) == branches
    assert (backward.count(" cond[") == 2) == branches      # forward, and the way back
    assert branches or " cond[" not in backward


@pytest.mark.parametrize("path", list(SEQ_BY_PATH))
def test_tail_padding_takes_no_rows(path):
    """Positions that are not live are routed but computed by no expert."""
    config = small_config()
    lm = config.model.lm
    seq = SEQ_BY_PATH[path]
    cut = seq * 5 // 8
    # all the experts held (every slot a row), or a quarter of them (the row path)
    first, count = (0, lm.num_experts) if path == "slots" else (4, 4)
    params = _share(_full_layer_params(config), first, count)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, seq, lm.hidden_size))
    live = jnp.arange(seq)[None, :] < jnp.array([[cut], [seq]])
    layer = _routed_layer(config, (first, count))
    out, counters = layer.apply({"params": params}, x, live)
    whole, _ = layer.apply({"params": params}, x)
    idx, _ = ref.route(x.reshape(-1, lm.hidden_size), params, reference_sizes(config))
    held = (np.asarray(idx) >= first) & (np.asarray(idx) < first + count)
    assert float(counters["rows_held"]) == held[np.asarray(live).reshape(-1)].sum()
    assert path == "rows" or float(counters["rows_held"]) == (cut + seq) * 4
    assert float(counters["fallback"]) == 0.0
    assert float(jnp.max(jnp.abs(out[0, cut:]))) == 0.0
    close(out[0, :cut], whole[0, :cut], "live positions")
    close(out[1], whole[1], "a sequence with no padding")


@pytest.mark.parametrize("mixer", ["conv", "full_attention"])
def test_a_mixer_is_causal(mixer):
    """Tokens after t move nothing at or before t, forward and gradient."""
    spec = LMSpec.from_config(small_config().model.lm, jnp.float32)
    module = (layers.ShortConv if mixer == "conv" else layers.GQAttention)(spec)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, spec.hidden_size))
    params = module.init(jax.random.PRNGKey(4), x)
    t = 13
    moved = x.at[:, t + 1:].add(1.0)
    a, b = module.apply(params, x), module.apply(params, moved)
    np.testing.assert_array_equal(np.asarray(a[:, :t + 1]), np.asarray(b[:, :t + 1]))
    assert float(jnp.max(jnp.abs(a[:, t + 1:] - b[:, t + 1:]))) > 1e-3
    grad = jax.grad(lambda v: jnp.sum(module.apply(params, v)[:, :t + 1] ** 2))(x)
    assert float(jnp.max(jnp.abs(grad[:, t + 1:]))) == 0.0
    assert float(jnp.max(jnp.abs(grad[:, :t + 1]))) > 0.0


def test_blockwise_attention_is_dense_attention_both_ways():
    key = jax.random.PRNGKey(6)
    q = jax.random.normal(key, (2, 256, 2, 2, 16))
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, 256, 2, 16))
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, 256, 2, 16))
    probe = jax.random.normal(jax.random.fold_in(key, 3), q.shape)

    def both_ways(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v) * probe), argnums=(0, 1, 2))(q, k, v)

    with jax.default_matmul_precision("highest"):
        dense, dense_grads = both_ways(lambda q, k, v: layers.dense_attention(q, k, v, 0.25))
        blocks, block_grads = both_ways(
            lambda q, k, v: layers.blockwise_attention(q, k, v, 0.25, 64))
    assert abs(float(dense) - float(blocks)) <= TOL * abs(float(dense))
    for a, b in zip(block_grads, dense_grads):
        close(a, b, "attention gradient")


BF16_TOL = 2.0 ** -6      # of a leaf's largest element: bfloat16 keeps 8 bits, read 0.003-0.004


@pytest.mark.parametrize("s,blocks", [(1024, 1), (1536, 3)])
def test_splash_attention_is_dense_attention_both_ways(s, blocks):
    """The kernel a TPU runs, in the library's interpret mode: bfloat16
    operands, 4 heads over 2 KV heads that go in unrepeated; one block, and
    three a side so that diagonal, whole and skipped blocks are all there;
    against the float32 square on the same rounded operands."""
    key = jax.random.PRNGKey(8)
    q = jax.random.normal(key, (2, s, 2, 2, 64), jnp.bfloat16)
    k = jax.random.normal(jax.random.fold_in(key, 1), (2, s, 2, 64), jnp.bfloat16)
    v = jax.random.normal(jax.random.fold_in(key, 2), (2, s, 2, 64), jnp.bfloat16)
    probe = jax.random.normal(jax.random.fold_in(key, 3), q.shape)
    assert s // layers.splash_blocks(s) == blocks

    def both_ways(fn, *operands):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * probe),
            argnums=(0, 1, 2))(*operands)

    scale = 64 ** -0.5
    out, grads = both_ways(
        lambda q, k, v: layers.splash_attention(q, k, v, scale, interpret=True), q, k, v)
    with jax.default_matmul_precision("highest"):
        dense, dense_grads = both_ways(
            lambda q, k, v: layers.dense_attention(q, k, v, scale),
            *(x.astype(jnp.float32) for x in (q, k, v)))
    assert abs(float(out) - float(dense)) <= BF16_TOL * abs(float(dense))
    for a, b, what in zip(grads, dense_grads, "qkv"):
        assert a.dtype == jnp.bfloat16 and a.shape == b.shape
        gap = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b))) / float(jnp.max(jnp.abs(b)))
        assert gap <= BF16_TOL, (what, gap)
    # the kernel object, mask tables and all, is made once a shape
    before = layers._splash_kernel.cache_info()
    jax.eval_shape(lambda: layers.splash_attention(q, k, v, scale, interpret=True))
    after = layers._splash_kernel.cache_info()
    assert (after.misses, after.hits) == (before.misses, before.hits + 1)


def test_the_kernel_refuses_a_sequence_it_cannot_tile():
    q = jnp.zeros((1, 1000, 2, 2, 64), jnp.bfloat16)
    kv = jnp.zeros((1, 1000, 2, 64), jnp.bfloat16)
    with pytest.raises(ValueError, match=r"sequence 1000 .* block 128"):
        layers.splash_attention(q, kv, kv, 0.125)


@pytest.mark.parametrize("backend,s,path", [
    ("tpu", 8192, "splash"), ("tpu", 1024, "splash"), ("tpu", 512, "dense"),
    ("cpu", 8192, "blockwise"), ("cpu", 512, "dense")])
def test_which_attention_a_backend_and_a_length_take(monkeypatch, backend, s, path):
    """Short sequences the square; past 512 positions the kernel on a TPU and
    query blocks in ``lax`` elsewhere: from the backend and the shape alone."""
    taken = []
    for name in ("splash", "blockwise", "dense"):
        monkeypatch.setattr(layers, name + "_attention",
                            lambda *a, _name=name, **kw: taken.append(_name))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    q = jax.ShapeDtypeStruct((1, s, 2, 2, 64), jnp.bfloat16)
    layers.causal_attention(q, None, None, 0.125)
    assert taken == [path]


def _batches(seed, n=3, **kw):
    feed = PackedTokenFeed(batch_size=2, seq_len=256, vocab=128, seed=seed, documents=64,
                           doc_len_median=48, doc_len_sigma=1.0, doc_len_min=4, **kw)
    out = [next(feed) for _ in range(n)]
    share = feed.padding_share
    feed.close()
    return out, share


def test_the_token_feed():
    from rt1_tpu.models.lm import spec

    assert IGNORE == spec.IGNORE        # one contract, stated on both sides
    a, share = _batches(7)
    b, _ = _batches(7)
    other, _ = _batches(8)
    for x, y in zip(a, b):          # same seed, same batches
        np.testing.assert_array_equal(x["observations"]["tokens"], y["observations"]["tokens"])
        np.testing.assert_array_equal(x["actions"]["targets"], y["actions"]["targets"])
    assert any((x["observations"]["tokens"] != y["observations"]["tokens"]).any()
               for x, y in zip(a, other))
    assert 0.0 < share < 0.5
    for batch in a:
        tokens, targets = batch["observations"]["tokens"], batch["actions"]["targets"]
        assert tokens.dtype == np.int32 and tokens.shape == targets.shape == (2, 256)
        assert tokens.min() >= 0 and tokens.max() <= 127        # inside the slice
        for row, want in zip(tokens, targets):
            counted = np.flatnonzero(want != IGNORE)
            filled = counted.max() + 2          # the last token has no next
            # padding only at the tail, and nothing after it counts
            assert (want[filled - 1:] == IGNORE).all() and (want[:filled - 1] != IGNORE).all()
            np.testing.assert_array_equal(want[:filled - 1], row[1:filled])
            assert row[filled - 1] == 127 and (row[filled:] == 127).all()   # end-of-document


def test_the_plan_has_a_rule_for_every_leaf(world):
    from rt1_tpu.parallel import ShardingPlan
    from rt1_tpu.parallel import sharding as shardlib

    config, _, _, _, params = world
    plan = ShardingPlan.from_config(config)
    assert plan.coverage(params) == []
    paths = [shardlib._path_str(p) for p, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    assert len(paths) == 53
    assert [p for p in paths if plan.spec_for(p) is None] == []
    experts = [p for p in paths if "/experts/" in p]
    assert experts and all(plan.spec_for(p)[0] == "model" for p in experts)


def test_the_trainer_trains_the_family(tmp_path):
    """``python -m rt1_tpu.train.train --config .../lfm2_moe.py`` at a small
    size: train_and_evaluate -> make_train_step_fns, guard and health pack on."""
    from rt1_tpu.train.train import train_and_evaluate

    config = small_config(seq_len=32)
    config.per_host_batch_size = 8      # the test's eight virtual devices
    config.num_steps = 3
    config.log_every_steps = 1
    config.eval_every_steps = 0
    config.checkpoint_every_steps = 100
    state = train_and_evaluate(config, str(tmp_path))
    assert int(state.step) == 3

"""The hyper-connection passes as Pallas kernels (rt1_tpu/models/lm/streams.py)
against the plain functions of models/lm/model.py, on the CPU in Pallas'
interpret mode: each kernel's output and every gradient, a whole sublayer and a
whole small decoder both ways on both paths, the rule that picks the path, and
that a decoder with one stream holds none of it.

Tolerances (of a leaf's largest element): float32 streams 2e-6 for a kernel and
1e-5 through a sublayer or a decoder, the same arithmetic in another order of
sums; bfloat16 streams 2e-2, because the plain path rounds each of the three
contributions to the streams' cotangent to bfloat16 and adds them in bfloat16,
and the kernels add them in float32 and round once (read: up to 7e-3).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rt1_tpu.data.tokens import feed_from_config
from rt1_tpu.models.lm import model as lm_model, streams
from rt1_tpu.models.lm.spec import LMSpec
from rt1_tpu.train.configs import xing4_0
from rt1_tpu.train.train import build_family

N, TOKENS, D = 4, 256, 256
WIDTH = N * (N + 2)
TOL = {"float32": 2e-6, "bfloat16": 2e-2}
SMALL = dict(hidden_size=128, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=24,
             kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             intermediate_size=96, moe_intermediate_size=32, num_experts=16,
             experts_held=(4, 4), heads_held=(0, 2), vocab_held=128, seq_len=128,
             doc_len_median=48, num_hidden_layers=2, layer_types=("latent_attention",) * 2)


@pytest.fixture
def interpret(monkeypatch):
    monkeypatch.setattr(streams, "INTERPRET", True)


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b))) / (float(np.max(np.abs(b))) + 1e-30)


def draws(*shapes, dtype=jnp.float32, key=0):
    keys = jax.random.split(jax.random.PRNGKey(key), len(shapes))
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(keys, shapes)]


def mix_out_case(dtype):
    x, f = draws((N, 1, TOKENS, D), (1, TOKENS, D), dtype=dtype)
    res, post, probe = draws((N, N, 1, TOKENS), (N, 1, TOKENS), x.shape, key=1)
    args = (x, jax.nn.softmax(res, axis=1), 2 * jax.nn.sigmoid(post), f)

    def run(fn):
        return lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * probe)

    return run(streams.mix_out), run(lm_model.mix_out), args, "streams h_res h_post out".split()


def maps_case(dtype):
    (x,) = draws((N, 1, TOKENS, D), dtype=dtype)
    (w,) = draws((N, D, WIDTH), key=2)
    w = (0.5 * w / (N * D) ** 0.5).astype(dtype)
    gate, bias = jnp.array([0.7, 1.1, 0.9, 1.3]), jnp.array([0.1, -0.2, 0.3, 0.0])
    p_mixed, p_raw, p_held = draws((1, TOKENS, D), (WIDTH, 1, TOKENS), x.shape, key=3)

    def read(mixed, raw, held):
        return (jnp.sum(mixed.astype(jnp.float32) * p_mixed) + jnp.sum(raw * p_raw)
                + jnp.sum(held.astype(jnp.float32) * p_held))

    def kernels(x, w, gate, bias):
        return read(*streams.maps_and_mix_in(x, w, gate, bias, 1e-6))

    def plain(x, w, gate, bias):
        raw = lm_model._normed_projection(x, w, 1e-6)
        h_pre = jax.nn.sigmoid(raw[:N] * gate[:, None, None] + bias[:, None, None])
        return read(lm_model.mix_in(x, h_pre), raw, x)

    return kernels, plain, (x, w, gate, bias), "streams phi gate bias".split()


def sinkhorn_case(dtype):
    (z,) = draws((N, N, 1, 1024), dtype=dtype)
    z = (2.0 * jnp.eye(N)[:, :, None, None] + 0.5 * z).at[0, 1, 0, :5].set(40.0)   # past the clamp
    (probe,) = draws(z.shape, key=4)

    def run(fn):
        return lambda z: jnp.sum(fn(z, 20, 1e-6, (-30.0, 30.0)) * probe)

    return run(streams.sinkhorn), run(lm_model.sinkhorn), (z,), ["logits"]


@pytest.mark.parametrize("case,dtype", [
    (mix_out_case, "bfloat16"), (mix_out_case, "float32"),
    (maps_case, "bfloat16"), (maps_case, "float32"), (sinkhorn_case, "float32")])
def test_a_kernel_and_its_backward_against_the_plain_function(interpret, case, dtype):
    kernels, plain, args, names = case(jnp.dtype(dtype))
    argnums = tuple(range(len(args)))
    out, grads = jax.value_and_grad(kernels, argnums)(*args)
    want, want_grads = jax.value_and_grad(plain, argnums)(*args)
    assert abs(float(out) - float(want)) <= TOL[dtype] * max(abs(float(want)), 100.0)
    for name, a, b in zip(names, grads, want_grads):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert gap(a, b) <= TOL[dtype], (name, gap(a, b))


def _sublayer(dtype):
    lm = xing4_0.get_config().model.lm
    lm.hidden_size = D
    module = lm_model.HyperConnection(LMSpec.from_config(lm, jnp.dtype(dtype)))
    (x,) = draws((N, 2, TOKENS // 2, D), dtype=jnp.dtype(dtype))
    params = module.init(jax.random.PRNGKey(1), x)
    params = jax.tree.map(       # phi as a seed draws it, alpha and the bias away from their start
        lambda a: (0.5 * jax.random.normal(jax.random.PRNGKey(2), a.shape) / a.shape[0] ** 0.5
                   if a.ndim == 2 else a + 0.3 * jax.random.normal(jax.random.PRNGKey(3), a.shape)),
        params)
    (probe,) = draws(x.shape, key=5)

    def both_ways(params, x):
        def loss(params, x):
            inside, held, h_post, h_res, err = module.apply(params, x, method="enter")
            out = lm_model.leave(held, h_res, h_post, jnp.tanh(inside))
            return jnp.sum(out.astype(jnp.float32) * probe), err
        return jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(params, x)

    return both_ways, params, x


@pytest.mark.parametrize("dtype,rounds_in_kernel", [
    ("bfloat16", True), ("float32", True), ("float32", False)])
def test_a_sublayer_both_ways_through_the_module(monkeypatch, dtype, rounds_in_kernel):
    """``HyperConnection.enter`` -> a sublayer -> ``leave`` on the kernels'
    path against the plain path: the streams written back, the gap of
    ``H_res``'s sums (what ``hyper_connection/res_sum_err`` reads), and the
    gradients of the streams, phi, the norm's scale, alpha and the bias."""
    both_ways, params, x = _sublayer(dtype)
    (want, want_err), want_grads = both_ways(params, x)
    monkeypatch.setattr(streams, "INTERPRET", True)
    monkeypatch.setattr(streams, "SINKHORN_IN_KERNEL", rounds_in_kernel)
    (out, err), grads = both_ways(params, x)
    tol = 5 * TOL[dtype]
    assert abs(float(out) - float(want)) <= tol * max(abs(float(want)), 100.0)
    assert float(err) == pytest.approx(float(want_err), rel=1e-3, abs=1e-6)
    flat, want_flat = (dict(jax.tree_util.tree_leaves_with_path(g)) for g in (grads, want_grads))
    assert len(flat) == 5       # norm, phi, alpha, maps_bias and the streams
    for path, a in flat.items():
        b = want_flat[path]
        assert a.shape == b.shape and a.dtype == b.dtype
        assert gap(a, b) <= tol, (jax.tree_util.keystr(path), gap(a, b))


def _decoder(**changes):
    config = xing4_0.get_config()
    for k, v in dict(SMALL, **changes).items():
        config.model.lm[k] = v
    config.model.dtype = "float32"
    model, init_fn, loss_fn = build_family(config.model)
    feed = feed_from_config(config, 1)
    host = next(feed)
    feed.close()
    batch = (host["observations"], host["actions"])
    params = jax.jit(lambda r: init_fn(model, r, *batch))(jax.random.PRNGKey(0))["params"]
    return loss_fn, params, batch


def test_a_decoder_both_ways_on_both_paths(monkeypatch):
    """Two blocks and the prediction module's (six sublayers, recomputed on the
    way back): the loss, ``res_sum_err`` and every leaf's gradient equal on both
    paths; ``hyper_connection/fused_sublayers`` reads 6 where the kernels ran
    and 0 on the CPU's own path."""
    loss_fn, params, batch = _decoder()

    def both_ways():
        return jax.jit(jax.value_and_grad(
            lambda p: loss_fn(p, {}, batch, None, True), has_aux=True))(params)

    (want, (want_out, _)), want_grads = both_ways()
    monkeypatch.setattr(streams, "INTERPRET", True)
    (loss, (out, _)), grads = both_ways()
    assert float(want_out["counters"]["hyper_connection/fused_sublayers"]) == 0
    assert float(out["counters"]["hyper_connection/fused_sublayers"]) == 6
    assert float(out["counters"]["hyper_connection/sinkhorn_iters"]) == 20
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(out["counters"]["hyper_connection/res_sum_err"]) == pytest.approx(
        float(want_out["counters"]["hyper_connection/res_sum_err"]), rel=1e-3, abs=1e-6)
    worst = max((gap(a, b), jax.tree_util.keystr(path)) for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want_grads)))
    assert worst[0] <= 1e-4, worst


@pytest.mark.parametrize("interpreted,tokens,hidden", [
    (False, 256, 256),      # a CPU backend
    (True, 192, 256),       # a token count that is no multiple of the tile
    (True, 256, 192),       # d no multiple of 128
], ids=["cpu", "tokens", "hidden"])
def test_the_plain_path_is_taken_where_the_kernels_do_not_fit(monkeypatch, interpreted,
                                                              tokens, hidden):
    monkeypatch.setattr(streams, "INTERPRET", interpreted)
    assert not streams.fits(tokens, hidden)
    assert streams.fits(256, 256) == interpreted

    def no_kernel(*args, **kwargs):
        raise AssertionError("a kernel was built")

    monkeypatch.setattr(streams, "_call", no_kernel)
    lm = xing4_0.get_config().model.lm
    lm.hidden_size = hidden
    module = lm_model.HyperConnection(LMSpec.from_config(lm, jnp.float32))
    (x,) = draws((N, 1, tokens, hidden))
    params = module.init(jax.random.PRNGKey(1), x)
    inside, held, h_post, h_res, _ = module.apply(params, x, method="enter")
    h_pre, want_post, want_res, _ = module.apply(params, x)
    assert held is x
    np.testing.assert_array_equal(inside, lm_model.mix_in(x, h_pre))
    np.testing.assert_array_equal(lm_model.leave(held, h_res, h_post, inside),
                                  lm_model.mix_out(x, want_res, want_post, inside))


def test_a_decoder_with_one_stream_holds_none_of_it(interpret):
    """``hc_mult`` 1 is ``Block.__call__``'s plain branch: no scope of the
    maps or the mixes and no kernel in the lowered step, even where the
    kernels could be built."""
    loss_fn, params, batch = _decoder(hc_mult=1)
    lowered = jax.jit(jax.grad(lambda p: loss_fn(p, {}, batch, None, True)[0])).lower(params)
    text = lowered.as_text(debug_info=True)
    assert "latent" in text         # the scopes are in the text
    for word in ("hyper_connection", "streams_maps", "streams_mix_out", "pallas"):
        assert word not in text, word
    out = loss_fn(params, {}, batch, None, True)[1][0]
    assert "hyper_connection/fused_sublayers" not in out.get("counters", {})

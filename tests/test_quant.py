"""Low-precision serving engine (tier-1): per-channel quant op error
bounds, the plan's quant rules deciding every leaf, quantize-at-restore
structure, Quant layer f32 bit-identity, the int8-vs-f32 engine parity
gate (the ship-blocking acceptance bar), bf16-restore ≡ bf16-compute, and
quantized hot-swap (standby = f32 masters, requantized, compile_count 1).

The engine fixtures go through `build_serve_engine(inference_dtype=)` on
the tiny config — the exact restore path `python -m rt1_tpu.serve
--inference_dtype` takes — so the gate here covers what production serves.
"""

import numpy as np
import pytest

from rt1_tpu.models import quant
from rt1_tpu.parallel.plan import (
    QUANT_F32,
    QUANT_INT8,
    quant_coverage,
    quant_group_for_path,
    rt1_quant_rules,
)

EPS = 1e-6


# ------------------------------------------------------------ the quant op


def test_per_channel_round_trip_error_bound():
    """Symmetric per-channel quantization: the round-trip error of every
    entry is at most half a quantization step of ITS channel, and the
    channel's max-abs entry uses the full ±127 range (scale = amax/127)."""
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((48, 24)) * 0.05).astype(np.float32)
    q, scale = quant.quantize_per_channel(w)
    assert q.dtype == np.int8 and q.shape == w.shape
    assert scale.dtype == np.float32 and scale.shape == (24,)
    err = np.abs(quant.dequantize(q, scale) - w)
    assert np.all(err <= scale[None, :] * 0.5 + EPS)
    np.testing.assert_array_equal(np.abs(q).max(axis=0), 127)
    # Relative view: the worst error is ~0.4% of the channel amax.
    amax = np.abs(w).max(axis=0)
    assert np.all(err.max(axis=0) <= amax / (2 * quant.INT8_MAX) + EPS)


def test_per_channel_conv_kernels_and_edge_cases():
    rng = np.random.default_rng(1)
    # Conv layout (kh, kw, cin, cout): scale is per-cout over the whole
    # receptive field.
    k = rng.standard_normal((3, 3, 4, 8)).astype(np.float32)
    q, scale = quant.quantize_per_channel(k)
    assert scale.shape == (8,)
    err = np.abs(quant.dequantize(q, scale) - k)
    assert np.all(err <= scale * 0.5 + EPS)
    # An all-zero output channel (FiLM's zero-init projections) round-trips
    # exactly instead of dividing 0/0.
    z = np.zeros((6, 3), np.float32)
    z[:, 0] = rng.standard_normal(6)
    qz, sz = quant.quantize_per_channel(z)
    assert sz[1] == 1.0 and sz[2] == 1.0
    np.testing.assert_array_equal(quant.dequantize(qz, sz)[:, 1:], 0.0)
    # Rank-1 leaves have no output channel to scale by.
    with pytest.raises(ValueError, match="rank"):
        quant.quantize_per_channel(np.zeros(5, np.float32))


# ------------------------------------------------------- plan quant rules


def test_quant_rules_groups_for_key_paths():
    """The declared split: matmul/conv weights int8; embeddings and the
    action head explicitly full-precision."""
    int8_paths = [
        "params/transformer/layer_0/attn/query/kernel",
        "params/transformer/layer_0/attn/out/kernel",
        "params/transformer/layer_3/ff/kernel",
        "params/image_tokenizer_def/blocks_3/film/projection_add/kernel",
        "params/image_tokenizer_def/net/stem/conv/kernel",
        "params/image_tokenizer_def/token_learner/conv1/kernel",
        "params/image_tokenizer_def/conv1x1/kernel",
        "params/image_tokenizer_def/tok/kernel",
    ]
    f32_paths = [
        "params/transformer/token_emb/embedding",
        "params/transformer/position_emb/embedding",
        "params/transformer/output_tokens/kernel",  # IS the action decode
    ]
    for path in int8_paths:
        assert quant_group_for_path(path) == QUANT_INT8, path
    for path in f32_paths:
        assert quant_group_for_path(path) == QUANT_F32, path
    # Unmatched paths fall through to the master dtype, never to int8.
    assert quant_group_for_path("params/some/new/module/w") == QUANT_F32


def test_quant_rules_decide_every_leaf_of_shipped_configs():
    """`quant_coverage` analogue of the sharding plan's coverage check: on
    the tiny AND flagship serving trees, every rank≥2 leaf is decided by
    an explicit rule — a renamed module cannot silently lose (or gain) the
    int8 memory win."""
    from rt1_tpu.train.configs import language_table, tiny

    for get_config in (tiny.get_config, language_table.get_config):
        shapes = quant.abstract_serving_variables(get_config())
        assert quant_coverage(shapes) == []
        assert quant.quantized_paths(shapes)  # the int8 group is non-empty


def test_flagship_byte_report_meets_3x_reduction():
    """The acceptance headline, from abstract shapes (no init cost): the
    flagship serving tree shrinks ≥3× under int8 and exactly 2× under
    bf16 (BENCH_serve_quant.json records the same accounting)."""
    from rt1_tpu.train.configs import language_table

    report = quant.quant_byte_report(language_table.get_config())
    assert report["int8_reduction"] >= 3.0
    assert report["bf16_reduction"] == 2.0
    assert report["quantized_leaves"] > 100
    assert report["int8_bytes"] < report["bf16_bytes"] < report["f32_bytes"]


# ------------------------------------------------ quantize-at-restore tree


@pytest.fixture(scope="module")
def tiny_model_vars():
    import jax

    from rt1_tpu.specs import language_table_action_space, sample_space
    from tests.test_rt1 import tiny_policy

    model = tiny_policy(time_sequence_length=3)
    rng = jax.random.PRNGKey(0)
    obs = {
        "image": np.zeros((1, 3, 32, 56, 3), np.float32),
        "natural_language_embedding": np.zeros((1, 3, 512), np.float32),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 1), (1, 3)
    )
    variables = model.init(
        {"params": rng, "crop": rng}, obs, actions, train=False
    )
    import jax as _jax

    host = _jax.tree.map(lambda x: np.asarray(x), variables)
    return model, host


def _get_path(tree, path):
    node = tree
    for key in path.split("/"):
        node = node[key]
    return node


def test_quantize_tree_structure_and_scale_sidecar(tiny_model_vars):
    _, variables = tiny_model_vars
    served = quant.quantize_tree(variables)
    paths = quant.quantized_paths(variables)
    assert paths
    for path in paths:
        leaf = _get_path(served, path)
        master = _get_path(variables, path)
        assert leaf.dtype == np.int8, path
        # The sidecar scale mirrors the module path with a `_scale` suffix
        # (exactly where QuantDense/QuantConv look it up) and inverts to
        # within half a step per channel.
        scale_path = path.replace("params/", "", 1) + "_scale"
        scale = _get_path(served[quant.QUANT_COLLECTION], scale_path)
        assert scale.shape == (master.shape[-1],)
        err = np.abs(quant.dequantize(leaf, scale) - master)
        assert np.all(err <= scale * 0.5 + EPS), path
    # Undeclared leaves (biases, norms, embeddings) ride through untouched.
    bias = _get_path(served, "params/transformer/layer_0/attn/query/bias")
    np.testing.assert_array_equal(
        bias, _get_path(variables, "params/transformer/layer_0/attn/query/bias")
    )
    assert bias.dtype == np.float32


def test_quantize_tree_error_cases(tiny_model_vars):
    _, variables = tiny_model_vars
    # An empty rule set would serve a byte-identical f32 tree while
    # reporting an int8 engine — refused loudly.
    with pytest.raises(ValueError, match="no leaf matched"):
        quant.quantize_tree(variables, rules=[])
    with pytest.raises(ValueError, match="'params'"):
        quant.quantize_tree({"batch_stats": {}})
    with pytest.raises(ValueError, match="inference_dtype"):
        quant.check_inference_dtype("fp8")
    # serving_preparer: identity for f32, transforms otherwise.
    assert quant.serving_preparer("f32") is None
    assert quant.serving_preparer("int8") is not None


# ------------------------------------------------------------ quant layers


def test_quant_layers_identical_to_stock_flax_on_f32_trees():
    """QuantDense/QuantConv override only param retrieval: on an f32 tree
    they are bit-identical to nn.Dense/nn.Conv (training and checkpoints
    never see the difference)."""
    import flax.linen as nn
    import jax

    x = np.linspace(-1.0, 1.0, 24, dtype=np.float32).reshape(2, 12)
    params = nn.Dense(6).init(jax.random.PRNGKey(0), x)
    np.testing.assert_array_equal(
        nn.Dense(6).apply(params, x), quant.QuantDense(6).apply(params, x)
    )
    img = np.linspace(0.0, 1.0, 2 * 8 * 8 * 3, dtype=np.float32).reshape(
        2, 8, 8, 3
    )
    cparams = nn.Conv(4, (3, 3)).init(jax.random.PRNGKey(1), img)
    np.testing.assert_array_equal(
        nn.Conv(4, (3, 3)).apply(cparams, img),
        quant.QuantConv(4, (3, 3)).apply(cparams, img),
    )


def test_quant_dense_dequantizes_int8_kernel():
    import jax

    rng = np.random.default_rng(2)
    x = rng.standard_normal((4, 12)).astype(np.float32)
    dense_params = quant.QuantDense(6).init(jax.random.PRNGKey(0), x)
    kernel = np.asarray(dense_params["params"]["kernel"])
    q, scale = quant.quantize_per_channel(kernel)
    out = quant.QuantDense(6).apply(
        {
            "params": {"kernel": q, "bias": dense_params["params"]["bias"]},
            quant.QUANT_COLLECTION: {"kernel_scale": scale},
        },
        x,
    )
    ref = quant.QuantDense(6).apply(dense_params, x)
    # Weight-only quantization error bound: |Δout| ≤ |x| @ (scale/2).
    bound = np.abs(x) @ np.full((12, 6), 1.0) * (scale * 0.5).max() + 1e-5
    assert np.all(np.abs(np.asarray(out) - np.asarray(ref)) <= bound)


def test_int8_kernel_without_scale_is_a_hard_error():
    """Serving raw int8 integers through a matmul would return garbage
    with 200 OK — an int8 leaf with no sidecar scale must refuse."""
    params = {
        "params": {
            "kernel": np.ones((12, 6), np.int8),
            "bias": np.zeros(6, np.float32),
        }
    }
    with pytest.raises(ValueError, match="quantize_tree"):
        quant.QuantDense(6).apply(params, np.ones((2, 12), np.float32))


# -------------------------------------------------------- engine-level gate


@pytest.fixture(scope="module")
def tiny_engines():
    """f32 + int8 engines through the REAL restore path (random init is
    deterministic, so both serve the same master weights)."""
    from rt1_tpu.eval.restore import build_serve_engine
    from rt1_tpu.train.configs import tiny

    config = tiny.get_config()
    engines = {}
    for dtype in ("f32", "int8"):
        engine, step = build_serve_engine(
            config, workdir=None, inference_dtype=dtype, max_sessions=4
        )
        assert step == -1
        engines[dtype] = engine
    return config, engines


def test_int8_engine_parity_gate(tiny_engines):
    """THE acceptance bar: ≥99% action-token agreement int8-vs-f32 on the
    canned episode set, with the single-compile invariant intact."""
    from rt1_tpu.serve.parity import PARITY_THRESHOLD, check_parity

    config, engines = tiny_engines
    shape = (config.data.height, config.data.width, 3)
    stats = check_parity(engines["f32"], engines["int8"], shape)
    assert stats["passed"] and stats["agreement"] >= PARITY_THRESHOLD
    assert stats["tokens_total"] > 0
    assert engines["f32"].compile_count == 1
    assert engines["int8"].compile_count == 1
    assert engines["int8"].inference_dtype == "int8"


def test_parity_gate_raises_below_threshold(tiny_engines):
    """The gate's failure mode is a refusal, not a warning."""
    from rt1_tpu.serve.parity import check_parity

    config, engines = tiny_engines
    shape = (config.data.height, config.data.width, 3)
    with pytest.raises(ValueError, match="parity gate FAILED"):
        check_parity(
            engines["f32"],
            engines["int8"],
            shape,
            threshold=1.01,  # unreachable: forces the refusal path
            episodes=1,
            steps=2,
        )


def test_int8_engine_byte_accounting(tiny_engines):
    """The memory win is real device bytes: the int8 serving tree is
    smaller than f32's, while both report the same f32 master bytes (the
    checkpoint contract reloads validate against)."""
    _, engines = tiny_engines
    f32, int8 = engines["f32"], engines["int8"]
    assert f32.serving_param_bytes == f32.master_param_bytes
    assert int8.master_param_bytes == f32.master_param_bytes
    assert int8.serving_param_bytes < f32.serving_param_bytes


def test_quantized_hot_swap_accepts_masters_rejects_precast(tiny_engines):
    """ISSUE satellite regression: in int8 mode the standby arrives as an
    f32 MASTER checkpoint — `swap_variables` validates it against the
    master spec, requantizes, and keeps compile_count 1; a tree pre-cast
    or pre-quantized to serving dtypes is rejected (it would recompile or
    serve garbage)."""
    import jax

    from rt1_tpu.eval.restore import load_standby_variables

    config, engines = tiny_engines
    engine = engines["int8"]
    rng = np.random.default_rng(11)
    emb = rng.standard_normal(512).astype(np.float32)
    stream = [
        {
            "image": rng.random(
                (config.data.height, config.data.width, 3), dtype=np.float32
            ),
            "natural_language_embedding": emb,
        }
        for _ in range(3)
    ]
    engine.reset("swap")
    before = [engine.act("swap", obs) for obs in stream]

    # The PR 6 contract: workdir=None rebuilds the same deterministic
    # random init, as f32 masters — the reload path of a quantized fleet.
    standby, step = load_standby_variables(config, workdir=None)
    assert step == -1
    info = engine.swap_variables(standby)
    assert info["inference_dtype"] == "int8"
    assert engine.reloads == 1
    assert engine.compile_count == 1

    # Identical masters → identical requantization → bit-identical tokens.
    engine.reset("swap")
    after = [engine.act("swap", obs) for obs in stream]
    for b, a in zip(before, after):
        np.testing.assert_array_equal(b["action_tokens"], a["action_tokens"])
        np.testing.assert_array_equal(b["action"], a["action"])

    # A pre-quantized serving tree has a different structure (the quant
    # collection) — rejected against the master spec.
    with pytest.raises(ValueError, match="master"):
        engine.swap_variables(quant.quantize_tree(standby))
    # A bf16 pre-cast matches the structure but not the master dtypes.
    with pytest.raises(ValueError, match="master spec"):
        engine.swap_variables(quant.cast_tree(standby))
    assert engine.reloads == 1  # both refusals left the engine untouched
    assert engine.compile_count == 1
    engine.release("swap")


def test_bf16_restore_bit_identical_to_bf16_compute():
    """bf16 mode's correctness story: casting every float leaf ONCE at
    restore (half the resident bytes) is bit-identical to flax's own
    compute-dtype cast at use sites — same model, same tokens, same
    actions."""
    from rt1_tpu.eval.restore import (
        _config_with_model_dtype,
        build_serve_engine,
    )
    from rt1_tpu.train.configs import tiny

    config = tiny.get_config()
    restore_engine, _ = build_serve_engine(
        config, workdir=None, inference_dtype="bf16", max_sessions=1
    )
    assert restore_engine.inference_dtype == "bf16"
    # Reference: f32 masters + a bf16-compute model (the cast happens at
    # every use site instead of once at restore).
    compute_engine, _ = build_serve_engine(
        _config_with_model_dtype(config, "bfloat16"),
        workdir=None,
        inference_dtype="f32",
        max_sessions=1,
    )
    rng = np.random.default_rng(21)
    emb = rng.standard_normal(512).astype(np.float32)
    for step in range(3):
        obs = {
            "image": rng.random(
                (config.data.height, config.data.width, 3), dtype=np.float32
            ),
            "natural_language_embedding": emb,
        }
        a = restore_engine.act("s", dict(obs))
        b = compute_engine.act("s", dict(obs))
        np.testing.assert_array_equal(a["action_tokens"], b["action_tokens"])
        np.testing.assert_array_equal(a["action"], b["action"])
    # bf16 at rest is half the f32 master bytes.
    assert (
        restore_engine.serving_param_bytes
        == restore_engine.master_param_bytes // 2
    )

"""Ahead-of-time compiles for the described (not attached) TPU v5e.

The chip's own compiler is installed in the sandbox and compiles for a
topology description, so the kernels of the main path are refused HERE, at
no chip time, when a change breaks their tiling, VMEM budget or
partitioning — none of which interpret mode can see. Nothing runs: a
passing compile is not a chip run. Skipped where the topology cannot be
described (no libtpu).
"""

import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from rt1_tpu.models.action_tokenizer import tokens_per_action
from rt1_tpu.parallel.flash_attention import fused_attention
from rt1_tpu.serve.engine import pow2_buckets
from rt1_tpu.specs import language_table_action_space
from rt1_tpu.train.configs import language_table

# Flagship decoder attention, read off the config the trainer and server
# run: window 6 x (8 image + 3 action tokens) = 66 positions, 8 heads of
# key_dim (= layer_size) 128.
_MODEL = language_table.get_config().model
SEQ = _MODEL.time_sequence_length * (
    _MODEL.num_image_tokens + tokens_per_action(language_table_action_space())
)
HEADS, HEAD_DIM = _MODEL.num_heads, _MODEL.layer_size
# Default `python -m rt1_tpu.serve` ladder: --max_sessions 8, --buckets auto.
BUCKETS = pow2_buckets(8)


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"TPU topology cannot be described here: {exc!r}")
    return topo


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("masked", [True, False], ids=["mask", "nomask"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize("batch", BUCKETS)
def test_fused_attention_compiles_for_v5e(v5e, batch, dtype, masked):
    """The Pallas kernel lowers to Mosaic at the flagship shape for every
    serve bucket size — and the program really holds the kernel."""
    one_chip = SingleDeviceSharding(v5e.devices[0])
    qkv = jax.ShapeDtypeStruct(
        (batch, SEQ, HEADS, HEAD_DIM), dtype, sharding=one_chip
    )
    args = [qkv, qkv, qkv]
    if masked:
        args.append(jax.ShapeDtypeStruct((SEQ, SEQ), jnp.int32, sharding=one_chip))

    def attend(q, k, v, mask=None):
        return fused_attention(q, k, v, mask=mask)

    compiled = jax.jit(attend).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_health_pack_copies_no_state_in_the_compiled_step(v5e):
    """The guarded step with the health pack on, at the rehearsal size of
    tests/benchmark/data, compiled for one described chip: the pack's sums
    leave the optimizer's own fusions as scalars, so the program holds no
    `concatenate` or `dynamic-update-slice` the size of a layer group (the
    flat float32 copies concat + vdot made of the updates and the new
    parameters were 1.5 % of the flagship's step on the chip), and none
    over 1 M elements at all."""
    from benchmarks import program
    from rt1_tpu.obs import health
    from rt1_tpu.trainer import make_train_step_fns

    config = program.program_config(program.load_config_file(os.path.join(
        os.path.dirname(__file__), "benchmark", "data", "rt1-small-test.json")))
    plan, model, init_fn, loss_fn, tx = program.build_model(
        config, devices=v5e.devices[:1])
    shapes = program.abstract_state(config, model, init_fn, tx)
    fns = make_train_step_fns(
        model, plan.mesh, shapes, loss_fn=loss_fn, guard_nonfinite=True,
        model_health=True, health_task_names=("a", "b"), plan=plan,
    )
    one_chip = SingleDeviceSharding(v5e.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
            tree)

    observations, actions = program.batch_spec(config)
    observations[health.TASK_ID_KEY] = jax.ShapeDtypeStruct(
        (config.per_host_batch_size,), jnp.int32)
    text = fns.train_step.lower(
        on_chip(shapes), jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        on_chip((observations, actions)),
        jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip),
    ).compile().as_text()

    def elements(dims):
        return int(np.prod([int(d) for d in dims.split(",") if d]))

    copies = [
        (op, elements(dims), "/health/" in line)
        for line in text.splitlines()
        for dims, op in re.findall(
            r" = \w+\[([\d,]*)\]\S* (concatenate|dynamic-update-slice)\(", line)
    ]
    pack = len(fns.health_names)
    assert ("concatenate", pack, True) in copies  # the pack itself: read right
    group = max(pack, len(jax.tree.leaves(shapes.params)))
    assert not [c for c in copies if c[2] and c[1] > group], copies
    assert not [c for c in copies if c[1] > 1_000_000], copies


def _lm_spec():
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import lfm2_moe

    return LMSpec.from_config(lfm2_moe.get_config().model.lm, jnp.bfloat16)


@pytest.mark.parametrize("seq", [8192, 4096, 1536])
def test_lm_attention_compiles_both_ways_at_the_published_widths(v5e, monkeypatch, seq):
    """The decoder LM family's attention at 2 x 8,192 positions (the cell's), 2 x
    4,096 and 2 x 1,536 (the block rule's smaller blocks), 32 heads over 8 KV
    heads of 64: the kernel the code picks on a TPU, forward and backward,
    inside the chip's VMEM.  The executable holds the library's splash kernels
    by name and none of the old flash kernel's, k and v enter with their own 8
    heads, and the scratch is a quarter of the old path's 3.36 GB."""
    from rt1_tpu.models.lm import layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # as on the chip
    sp = _lm_spec()
    one_chip = SingleDeviceSharding(v5e.devices[0])
    heads, kv_heads, d = sp.num_heads, sp.num_kv_heads, sp.head_dim
    q = jax.ShapeDtypeStruct((2, seq, kv_heads, heads // kv_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((2, seq, kv_heads, d), jnp.bfloat16, sharding=one_chip)

    def both_ways(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            layers.causal_attention(*a, d ** -0.5).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(both_ways).lower(q, kv, kv).compile()
    text = compiled.as_text()
    kernels = {name: line for line in text.splitlines() if "tpu_custom_call" in line
               for name in re.findall(r"%(splash_mha_\w+?)[.\d]* = ", line)}
    # forward, and one backward kernel that makes dq beside dk and dv
    assert sorted(kernels) == ["splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"]
    assert "flash_mha" not in text and text.count("tpu_custom_call") == len(kernels)
    for line in kernels.values():       # q with 32 heads, k and v with their own 8
        operands = line.split("operand_layout_constraints=")[1].split("frontend_attributes")[0]
        assert operands.count(f"bf16[2,{kv_heads},{seq},{d}]") == 2, operands
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6e9 * seq / 8192


@pytest.mark.parametrize("buffer", ["the rule's rows", "all the slots"])
def test_lm_routed_experts_compile_both_ways_at_the_published_widths(v5e, monkeypatch, buffer):
    """One routed layer of the family at 16,384 tokens, 8 of 64 experts held:
    sort, gathers and the megablox products, forward and backward, with the
    row buffer the layer's shapes give (a quarter of the slots, the slot path
    behind a branch) and with all the slots (the slot path alone)."""
    from rt1_tpu.models.lm import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sp = _lm_spec()
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens, d, f, held = 16384, sp.hidden_size, sp.moe_intermediate_size, sp.experts_held[1]
    n = tokens * sp.experts_per_tok
    capacity = moe.row_capacity(n, held, sp.num_experts) if buffer == "the rule's rows" else n
    assert capacity == (16384 if buffer == "the rule's rows" else 65536)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both_ways(x, weights, w1, w3, w2, idx, live):
        return jax.grad(lambda x, weights, w1, w3, w2: jnp.sum(moe.held_experts_ffn(
            x, idx, weights, live, w1, w3, w2, sp, capacity)[0].astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4))(x, weights, w1, w3, w2)

    compiled = jax.jit(both_ways).lower(
        shape((tokens, d), jnp.bfloat16), shape((tokens, sp.experts_per_tok), jnp.float32),
        shape((held, d, f), jnp.float32), shape((held, d, f), jnp.float32),
        shape((held, f, d), jnp.float32), shape((tokens, sp.experts_per_tok), jnp.int32),
        shape((tokens,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert (" conditional(" in text) == (capacity < n)
    # the slot path's gathers go back as gathers, the row path adds up a
    # buffer's rows: no scatter over 65,536 rows of 2048 on either
    assert not re.findall(r"\[65536,2048\]\S* scatter\(", text)


def _mellum_spec():
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import mellum

    return LMSpec.from_config(mellum.get_config().model.lm, jnp.bfloat16)


@pytest.mark.parametrize("mixer", ["sliding_attention", "full_attention"])
def test_mellum_attention_compiles_both_ways_at_the_published_widths(v5e, monkeypatch, mixer):
    """Both kinds of layer of ``mellum2-12b-a2.5b`` at the cell's 1 x 16,384
    positions, 32 heads over 4 KV heads of 128: the splash kernels with the
    layer's mask (a window of 1,024 keys at blocks of 512 with an unfused
    backward, or causal at blocks of 1,024 with the fused one), inside the
    chip's VMEM at head size 128."""
    from rt1_tpu.models.lm import layers

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # as on the chip
    sp = _mellum_spec()
    window = sp.window(mixer)
    assert window == (1024 if mixer == "sliding_attention" else None)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    seq, heads, kv_heads, d = 16384, sp.num_heads, sp.num_kv_heads, sp.head_dim
    q = jax.ShapeDtypeStruct((1, seq, kv_heads, heads // kv_heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, seq, kv_heads, d), jnp.bfloat16, sharding=one_chip)

    def both_ways(q, k, v):
        return jax.grad(lambda *a: jnp.sum(
            layers.causal_attention(*a, d ** -0.5, window).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(both_ways).lower(q, kv, kv).compile()
    text = compiled.as_text()
    kernels = {name for line in text.splitlines() if "tpu_custom_call" in line
               for name in re.findall(r"%(splash_mha_\w+?)[.\d]* = ", line)}
    # a causal layer's backward is one fused kernel; a sliding layer's has a dq kernel of its own
    assert sorted(kernels) == (
        ["splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"] if window is None else
        ["splash_mha_dkv_no_residuals", "splash_mha_dq_no_residuals", "splash_mha_fwd_residuals"])
    # k and v enter with their own 4 heads (a batch of one is squeezed away)
    assert re.search(rf"bf16\[(1,)?{kv_heads},{seq},{d}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 3.2e9


def test_mellum_routed_experts_compile_both_ways_at_the_published_widths(v5e, monkeypatch):
    """One routed layer of ``mellum2-12b-a2.5b`` at 16,384 tokens, top-8, 16 of
    64 experts held: a row buffer of 65,536 of 131,072 slots (the slot path
    behind a branch), the grouped products at 2304 x 1792 and 896 x 2304 with
    the tiles ``megablox_tiling`` gives them."""
    from rt1_tpu.models.lm import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    sp = _mellum_spec()
    one_chip = SingleDeviceSharding(v5e.devices[0])
    tokens, d, f, held = 16384, sp.hidden_size, sp.moe_intermediate_size, sp.experts_held[1]
    n = tokens * sp.experts_per_tok
    capacity = moe.row_capacity(n, held, sp.num_experts)
    assert (n, capacity, d, f, held) == (131072, 65536, 2304, 896, 16)

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def both_ways(x, weights, w1, w3, w2, idx, live):
        return jax.grad(lambda x, weights, w1, w3, w2: jnp.sum(moe.held_experts_ffn(
            x, idx, weights, live, w1, w3, w2, sp, capacity)[0].astype(jnp.float32)),
            argnums=(0, 1, 2, 3, 4))(x, weights, w1, w3, w2)

    compiled = jax.jit(both_ways).lower(
        shape((tokens, d), jnp.bfloat16), shape((tokens, sp.experts_per_tok), jnp.float32),
        shape((held, d, f), jnp.float32), shape((held, d, f), jnp.float32),
        shape((held, f, d), jnp.float32), shape((tokens, sp.experts_per_tok), jnp.int32),
        shape((tokens,), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and " conditional(" in text


@pytest.mark.parametrize("padded", [False, True], ids=["keys_192", "keys_256"])
def test_xing_latent_attention_compiles_both_ways_at_the_published_widths(v5e, monkeypatch,
                                                                          padded):
    """The latent layer's kernel of ``xing4.0-29b-a4b`` at the cell's 1 x 8,192
    positions over the heads held: keys of 192 (128 + the 64-wide shared rotary
    part, no multiple of the 128 lanes) against values of 128, as they are and
    with the keys zero-padded to 256; the causal kernels at blocks of 1,024
    with the fused backward, and an output as wide as the values."""
    from rt1_tpu.models.lm import layers
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import xing4_0

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # as on the chip
    sp = LMSpec.from_config(xing4_0.get_config().model.lm, jnp.bfloat16)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    seq, heads, dv = 8192, sp.heads_held[1], sp.v_head_dim
    qk = 256 if padded else sp.head_dim
    assert (sp.head_dim, dv) == (192, 128)
    q = jax.ShapeDtypeStruct((1, seq, heads, 1, qk), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((1, seq, heads, qk), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, seq, heads, dv), jnp.bfloat16, sharding=one_chip)

    def both_ways(q, k, v):
        out = layers.causal_attention(q, k, v, 192 ** -0.5 * sp.softmax_scale_factor)
        assert out.shape == (1, seq, heads, 1, dv)
        return jax.grad(lambda *a: jnp.sum(layers.causal_attention(
            *a, 192 ** -0.5 * sp.softmax_scale_factor).astype(jnp.float32)),
            argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(both_ways).lower(q, k, v).compile()
    text = compiled.as_text()
    kernels = {name for line in text.splitlines() if "tpu_custom_call" in line
               for name in re.findall(r"%(splash_mha_\w+?)[.\d]* = ", line)}
    assert sorted(kernels) == ["splash_mha_dkv_no_residuals", "splash_mha_fwd_residuals"]
    assert re.search(rf"bf16\[(1,)?{heads},{seq},{qk}\]", text)
    assert re.search(rf"bf16\[(1,)?{heads},{seq},{dv}\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


@pytest.mark.parametrize("held", ["bfloat16", "float32"])
def test_xing_streams_kernels_compile_both_ways_at_the_published_widths(v5e, monkeypatch, held):
    """One hyper-connection sublayer of ``xing4.0-29b-a4b`` both ways at the
    cell's 4 streams x 8,192 tokens x 3,584: ``HyperConnection.enter`` and
    ``leave`` take the six kernels of models/lm/streams.py (both passes and the
    rounds, each with its backward), Mosaic accepts their tiles and VMEM, and
    no float32 copy of the four streams is among the temporaries."""
    from rt1_tpu.models.lm import model as lm_model, streams
    from rt1_tpu.models.lm.spec import LMSpec
    from rt1_tpu.train.configs import xing4_0

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")     # as on the chip
    sp = LMSpec.from_config(xing4_0.get_config().model.lm, jnp.dtype(held))
    n, tokens, d = sp.hc_mult, 8192, sp.hidden_size
    assert (n, d) == (4, 3584) and streams.fits(tokens, d)
    one_chip = SingleDeviceSharding(v5e.devices[0])
    module = lm_model.HyperConnection(sp)
    x = jax.ShapeDtypeStruct((n, 1, tokens, d), sp.dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(module.init, jax.random.PRNGKey(0), x))

    def both_ways(params, x):
        def loss(params, x):
            inside, streams_, h_post, h_res, _ = module.apply(params, x, method="enter")
            return jnp.sum(lm_model.leave(streams_, h_res, h_post, inside).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1))(params, x)

    compiled = jax.jit(both_ways).lower(params, x).compile()
    text = compiled.as_text()
    kernels = {name for line in text.splitlines() if "tpu_custom_call" in line
               for name in re.findall(r"%(streams_\w+?)[.\d]* = ", line)}
    assert sorted(kernels) == ["streams_maps", "streams_maps_back", "streams_mix_out",
                               "streams_mix_out_back", "streams_sinkhorn",
                               "streams_sinkhorn_back"]
    # the streams' cotangent from pass B's backward and one stream's width or two
    whole = n * tokens * d * jnp.dtype(held).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < 1.6 * whole

"""The declarative sharding plan (rt1_tpu/parallel/plan.py) + true mixed
precision (trainer/train.py mixed_precision).

Pins the PR's contracts:

* plan coverage — every weight matrix of the flagship, tiny and
  efficientnet_small configs matches an explicit rule (no
  silent-replication fallthrough), and every rule matches a leaf of a
  shipped configuration; strict mode raises, default warns loudly.
* auto mesh-shape selection by device count (SNIPPETS.md [1] ladder).
* config-only equivalence on a forced multi-device host mesh: dense vs
  fsdp vs tp vs pp train-step losses/updates agree within tolerance
  (conftest forces 8 virtual CPU devices; these tests carve the 4-device
  meshes the acceptance criteria name from that pool — same GSPMD
  partitioner and collective lowering either way).
* the f32 (non-mixed) path is bit-identical to the pre-plan step built
  from the PR-6 hand-written rule list.
* mixed precision keeps f32 masters + optimizer state while computing
  fwd/bwd on a bf16 cast, donation-safe, loss within tolerance of f32.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

from rt1_tpu.parallel import (
    MeshConfig,
    PlanCoverageError,
    ShardingPlan,
    auto_mesh_shape,
    make_mesh,
    mixed_precision_from_config,
)
from rt1_tpu.trainer import create_train_state, make_optimizer, make_train_step_fns

sys.path.insert(0, "tests")
from test_rt1 import make_batch, tiny_policy  # noqa: E402


# --------------------------------------------------------------- coverage


def _param_shapes(model_config):
    """Abstract param tree for a config — eval_shape, so even the flagship
    B3 tokenizer enumerates in milliseconds (param shapes are spatial-dim
    independent, so small images suffice)."""
    from rt1_tpu.specs import language_table_action_space, sample_space
    from rt1_tpu.train.train import build_model

    model = build_model(model_config)
    rng = jax.random.PRNGKey(0)
    t = model_config.time_sequence_length
    obs = {
        "image": jnp.zeros((1, t, 64, 64, 3), jnp.float32),
        "natural_language_embedding": jnp.zeros((1, t, 512), jnp.float32),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 1), (1, t)
    )
    variables = jax.eval_shape(
        lambda r: model.init(
            {"params": r, "crop": r}, obs, actions, train=False
        ),
        rng,
    )
    return variables["params"]


def _flagship_model_config(**overrides):
    from rt1_tpu.train.configs import language_table

    mc = language_table.get_config().model
    for k, v in overrides.items():
        setattr(mc, k, v)
    return mc


def _tiny_model_config(**overrides):
    from rt1_tpu.train.configs import tiny

    mc = tiny.get_config().model
    for k, v in overrides.items():
        setattr(mc, k, v)
    return mc


@pytest.mark.parametrize(
    "name,mc_fn",
    [
        ("tiny", _tiny_model_config),
        ("flagship", _flagship_model_config),
        (
            "effnet_small",
            lambda: _tiny_model_config(image_tokenizer="efficientnet_small"),
        ),
    ],
)
def test_plan_covers_every_weight_matrix(name, mc_fn):
    """Satellite 1: flagship, tiny and efficientnet_small configs match a
    non-default rule for every weight matrix — nothing falls through to
    P()."""
    params = _param_shapes(mc_fn())
    plan = ShardingPlan(mesh=make_mesh(MeshConfig()))
    assert plan.coverage(params) == [], (
        f"{name}: weight matrices with no plan rule"
    )


@pytest.fixture(scope="module")
def shipped_param_paths():
    """Every parameter path of the configurations the repo ships: tiny,
    flagship, efficientnet_small and the lfm2_moe and xing4_0 decoder LMs."""
    from rt1_tpu.parallel import sharding as shardlib
    from rt1_tpu.train.configs import lfm2_moe, xing4_0
    from rt1_tpu.train.train import build_family

    trees = [
        _param_shapes(mc)
        for mc in (
            _tiny_model_config(),
            _flagship_model_config(),
            _tiny_model_config(image_tokenizer="efficientnet_small"),
        )
    ]
    ids = jnp.zeros((1, 16), jnp.int32)
    for base in (lfm2_moe, xing4_0):
        lm_model, lm_init, _ = build_family(base.get_config().model)
        trees.append(jax.eval_shape(
            lambda r: lm_init(lm_model, r, {"tokens": ids}, {"targets": ids}),
            jax.random.PRNGKey(0),
        )["params"])
    return {
        shardlib._path_str(path)
        for tree in trees
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.mark.parametrize("which", ["sharding", "quant"])
def test_every_rule_matches_a_leaf(which, shipped_param_paths):
    """The converse of coverage: a rule no shipped configuration's leaf
    matches describes a module that is gone, and is deleted with it."""
    import re

    from rt1_tpu.parallel.plan import rt1_quant_rules, rt1_sharding_plan

    rules = rt1_sharding_plan() if which == "sharding" else rt1_quant_rules()
    dead = [
        pattern for pattern, _ in rules
        if not any(re.search(pattern, p) for p in shipped_param_paths)
    ]
    assert dead == []


def test_plan_coverage_warns_and_strict_raises(caplog):
    import logging

    mesh = make_mesh(MeshConfig())
    tree = {
        "mystery_module": {"w": jnp.zeros((4, 4))},
        "small": jnp.zeros((4,)),  # rank<2: free to fall through
    }
    plan = ShardingPlan(mesh=mesh)
    assert plan.coverage(tree) == ["mystery_module/w"]
    with caplog.at_level(logging.WARNING, logger="rt1_tpu.parallel.plan"):
        plan.check_coverage(tree)
    assert any("mystery_module/w" in r.message for r in caplog.records)

    strict = ShardingPlan(mesh=mesh, strict=True)
    with pytest.raises(PlanCoverageError, match="mystery_module/w"):
        strict.check_coverage(tree)
    # A fully covered tree passes strict mode (params of the tiny policy).
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=2)
    variables = model.init(
        {"params": rng, "crop": rng}, obs, actions, train=False
    )
    assert strict.check_coverage(variables["params"]) == []


def test_opt_state_masters_follow_param_shardings():
    """Adam mu/nu mirror the param tree under the same rules (the paths
    repeat inside opt_state), so FSDP shards the f32 masters too."""
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=2)
    state = create_train_state(model, rng, (obs, actions), make_optimizer())
    mesh = make_mesh(MeshConfig(data=2, fsdp=2, model=2))
    plan = ShardingPlan(mesh=mesh)
    sh = plan.tree_shardings(state)
    qk = sh.params["transformer"]["layer_0"]["attn"]["query"]["kernel"]
    assert qk.spec == P("fsdp", "model")
    mu = sh.opt_state[0].mu["transformer"]["layer_0"]["attn"]["query"]["kernel"]
    assert mu.spec == P("fsdp", "model")


# --------------------------------------------------------------- resolution


def test_auto_mesh_shape_ladder():
    assert auto_mesh_shape(1) == (1, 1, 1)
    assert auto_mesh_shape(2) == (2, 1, 1)
    assert auto_mesh_shape(4) == (2, 2, 1)
    assert auto_mesh_shape(8) == (2, 2, 2)
    assert auto_mesh_shape(16) == (1, 4, 4)
    assert auto_mesh_shape(32) == (4, 4, 2)
    assert auto_mesh_shape(64) == (8, 4, 2)
    assert auto_mesh_shape(96) == (1, 96, 1)  # fallback: pure fsdp


def test_auto_mesh_shapes_products_equal_their_keys():
    """Satellite (ISSUE 14): every table row must cover its device count
    exactly — a row whose product drifts from its key would make `auto`
    silently build a mesh over the wrong device subset (the pre-table
    failure mode was the `(1, n, 1)` fallback flattening pods to pure
    fsdp)."""
    from rt1_tpu.parallel import AUTO_MESH_SHAPES

    for n, (dp, fsdp, tp) in AUTO_MESH_SHAPES.items():
        assert dp * fsdp * tp == n, (
            f"AUTO_MESH_SHAPES[{n}] = {(dp, fsdp, tp)} has product "
            f"{dp * fsdp * tp}"
        )


def test_auto_mesh_shape_host_contiguous_rebalance():
    """Multi-host rows keep fsdp×tp at or below one host's devices (fsdp
    all-gathers stay on intra-host ICI) by moving factors of 2 from fsdp
    to dp — the product is preserved and a single-host call is
    untouched."""
    for n in (16, 32, 64):
        for local in (2, 4, 8):
            dp, fsdp, tp = auto_mesh_shape(n, local)
            assert dp * fsdp * tp == n
            # tp is never rebalanced; fsdp shrinks until the model axes
            # fit in one host (or fsdp is exhausted).
            assert fsdp * tp <= max(local, tp)
    assert auto_mesh_shape(16, 8) == (2, 2, 4)
    assert auto_mesh_shape(32, 8) == (4, 4, 2)  # already host-contiguous
    assert auto_mesh_shape(64, 4) == (16, 2, 2)
    # local >= global (single host): the table row verbatim.
    assert auto_mesh_shape(16, 16) == (1, 4, 4)
    assert auto_mesh_shape(16, None) == (1, 4, 4)


def test_plan_from_config_parallel_block():
    cfg = {"parallel": {"dp": 2, "fsdp": 2, "tp": 2, "pp": 1}}
    plan = ShardingPlan.from_config(cfg)
    assert plan.mesh.shape == {"data": 2, "stage": 1, "fsdp": 2, "model": 2}
    assert plan.data_parallel_size == 4  # batch shards over dp x fsdp
    assert not plan.strict


def test_plan_from_config_auto():
    plan = ShardingPlan.from_config({"parallel": {"auto": True}})
    assert plan.mesh.shape == {"data": 2, "stage": 1, "fsdp": 2, "model": 2}


def test_plan_from_config_auto_composes_with_pp():
    """auto splits only the devices left after pp takes its own — auto+pp
    on 8 devices used to resolve a 16-device mesh and raise at startup."""
    plan = ShardingPlan.from_config({"parallel": {"auto": True, "pp": 2}})
    assert plan.mesh.shape == {"data": 2, "stage": 2, "fsdp": 2, "model": 1}


def test_serving_plan_honors_auto_and_backend_failure_raises(monkeypatch):
    """serving_plan resolves `auto` against the serve host's own device
    count (data axis collapsed — sessions are slots, not shards) instead of
    silently serving dense, and a backend that fails to initialize (no
    chip, or one another process holds) raises by name instead of quietly
    serving with plain placement."""
    from rt1_tpu.eval import restore as R

    plan = R.serving_plan({"parallel": {"auto": True}})
    # 8 forced host devices -> ladder (2, 2, 2); dp collapses to 1.
    assert plan.mesh.shape == {"data": 1, "stage": 1, "fsdp": 2, "model": 2}

    def _no_backend(*a, **k):
        raise RuntimeError("Backend 'cpu' failed to initialize")

    monkeypatch.setattr(jax, "local_devices", _no_backend)
    with pytest.raises(RuntimeError, match="failed to initialize"):
        R.serving_plan({"parallel": {"auto": True}})


def test_indivisible_dims_fall_back_to_replication():
    """EfficientNet SE bottleneck kernels have cout as small as 6/10 —
    dims the fsdp axis cannot divide. The placement guard replicates
    exactly those dims instead of crashing device_put, so fsdp stays a
    config-only switch on every model size (review-pinned: (1,1,40,10)
    under P(None,None,None,'fsdp') on an fsdp=4 mesh used to raise)."""
    mesh = make_mesh(MeshConfig(data=2, fsdp=4))
    plan = ShardingPlan(mesh=mesh)
    tree = {
        "se": {"fc1": {"kernel": jnp.zeros((1, 1, 40, 10))}},
        "projection_add": {"kernel": jnp.zeros((512, 8))},
    }
    sh = plan.tree_shardings(tree)
    # cout=10 % 4 != 0 -> that dim replicates; the rule still applies
    # where it divides (512 % 4 == 0).
    assert sh["se"]["fc1"]["kernel"].spec == P()
    assert sh["projection_add"]["kernel"].spec == P("fsdp", None)
    placed = plan.place_variables(tree, check=False)  # used to ValueError
    assert placed["se"]["fc1"]["kernel"].shape == (1, 1, 40, 10)
    # Every flagship B3 leaf resolves to a spec its shape can satisfy.
    params = _param_shapes(_flagship_model_config())
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    shardings = jax.tree_util.tree_leaves(plan.tree_shardings(params))
    assert len(leaves) == len(shardings)
    for (path, leaf), sh in zip(leaves, shardings):
        for dim, entry in zip(leaf.shape, tuple(sh.spec)):
            if entry is None:
                continue
            axes = entry if isinstance(entry, (tuple, list)) else (entry,)
            ways = 1
            for a in axes:
                ways *= mesh.shape[a]
            assert dim % ways == 0, (path, leaf.shape, sh.spec)


def test_trainer_check_coverage_gate(caplog):
    """check_coverage=False suppresses the RT-1-plan coverage warning
    (train.py passes it for family != 'rt1', whose param paths the default
    plan does not describe); the default stays loud."""
    import logging

    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=2)
    state = create_train_state(model, rng, (obs, actions), make_optimizer())
    state = state.replace(params={"mystery_module": {"w": jnp.zeros((4, 4))}})
    mesh = make_mesh(MeshConfig())

    def dummy_loss(params, batch_stats, batch, rng, train):
        return jnp.float32(0.0), {}

    with caplog.at_level(logging.WARNING, logger="rt1_tpu.parallel.plan"):
        make_train_step_fns(
            model, mesh, state, loss_fn=dummy_loss, check_coverage=False
        )
    assert not any("mystery_module" in r.message for r in caplog.records)
    with caplog.at_level(logging.WARNING, logger="rt1_tpu.parallel.plan"):
        make_train_step_fns(model, mesh, state, loss_fn=dummy_loss)
    assert any("mystery_module" in r.message for r in caplog.records)


def test_plan_from_config_legacy_mesh_fallback():
    """Configs that predate config.parallel (pinned proof configs) resolve
    through their old mesh block: data->dp, model->tp, stage->pp (a stale
    seq of 1 is accepted)."""
    cfg = {"mesh": {"data": -1, "model": 2, "seq": 1, "stage": 1}}
    plan = ShardingPlan.from_config(cfg)
    assert plan.mesh.shape == {"data": 4, "stage": 1, "fsdp": 1, "model": 2}
    # No block at all -> pure DP over every device.
    plan = ShardingPlan.from_config(None)
    assert plan.mesh.shape["data"] == len(jax.devices())


_REMOVED_SWITCHES = {
    "ffn_impl=moe": ("model", {"ffn_impl": "moe"}, "lfm2_moe"),
    "attention_impl=ring": ("model", {"attention_impl": "ring"}, "ring attention was removed"),
    "parallel.sp=2": ("parallel", {"sp": 2}, "ring attention"),
    "mesh.seq=2": ("mesh", {"seq": 2}, "ring attention"),
}


@pytest.mark.parametrize("case", list(_REMOVED_SWITCHES))
def test_removed_switches_are_refused(case):
    """The Switch MoE FFN and ring attention are gone: a config that still
    selects one is refused at the config seam, by name, with what replaced
    it — never silently built dense."""
    from rt1_tpu.train.train import build_model

    block, values, names = _REMOVED_SWITCHES[case]
    with pytest.raises(ValueError, match=names):
        if block == "model":
            build_model(_tiny_model_config(**values))
        else:
            ShardingPlan.from_config({block: values})


@pytest.mark.parametrize("name", ["language_table", "tiny"])
def test_stale_dense_keys_build_the_same_model(name):
    """A config written before the removal (benchmarks/configs/rt1-b3-lt.json
    is one) still carries ffn_impl/attention_impl="dense" and the four
    num_experts / moe_* keys: it builds the parameter tree of a config
    without them."""
    mc_fn = _flagship_model_config if name == "language_table" else _tiny_model_config
    stale = mc_fn().unlock()
    stale.update(
        ffn_impl="dense", attention_impl="dense", num_experts=4,
        moe_aux_weight=0.01, moe_capacity_factor=2.0, moe_ff_dim=None,
    )
    clean = mc_fn().unlock()
    for key in ("ffn_impl", "attention_impl"):
        if key in clean:
            del clean[key]
    assert _param_shapes(stale) == _param_shapes(clean)


def test_mixed_precision_from_config():
    assert not mixed_precision_from_config(None)
    assert not mixed_precision_from_config({"parallel": {"dp": -1}})
    assert mixed_precision_from_config(
        {"parallel": {"mixed_precision": True}}
    )


def test_write_hparams_emits_parallel_block():
    """Satellite 6: the config.parallel block lands in the TB hparams table
    as dotted keys (the PR 5 flatten fix covers nested blocks)."""
    from rt1_tpu.train.configs import tiny
    from rt1_tpu.trainer.metrics import flatten_hparams

    flat = flatten_hparams(dict(tiny.get_config().to_dict()))
    for key in (
        "parallel.dp", "parallel.fsdp", "parallel.tp", "parallel.pp",
        "parallel.auto", "parallel.strict", "parallel.mixed_precision",
    ):
        assert key in flat, key


# --------------------------------------------------- config-only equivalence


def _train_once(model, mesh, state, batch, **kw):
    fns = make_train_step_fns(model, mesh, state, donate=False, **kw)
    s = fns.shard_state(state)
    b = fns.shard_batch(batch)
    new_state, metrics = fns.train_step(s, b, jax.random.PRNGKey(5))
    return float(metrics["loss"]), new_state


def test_dense_fsdp_tp_pp_equivalence_on_4_devices():
    """The acceptance gate: dense / fsdp / tp / pp are config-only switches
    whose train-step losses and updates agree within tolerance on a
    4-device host mesh. SGD, not Adam: the first Adam step is ~sign(g),
    which amplifies benign 1e-12 float reassociation between layouts into
    visible param deltas wherever g ~ 0 (same reasoning as
    test_pp_train_step_equals_dense)."""
    dev4 = jax.devices()[:4]
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    batch = (obs, actions)
    tx = optax.sgd(1e-2)

    meshes = {
        "dense": make_mesh(MeshConfig(data=4), devices=dev4),
        "fsdp": make_mesh(MeshConfig(data=1, fsdp=4), devices=dev4),
        "dp_fsdp": make_mesh(MeshConfig(data=2, fsdp=2), devices=dev4),
        "tp": make_mesh(MeshConfig(data=2, model=2), devices=dev4),
        "pp": make_mesh(MeshConfig(data=2, stage=2), devices=dev4),
    }
    results = {}
    for name, mesh in meshes.items():
        if name == "pp":
            model = tiny_policy(mesh=mesh, pipeline_microbatches=2)
        else:
            model = tiny_policy()
        state = create_train_state(model, rng, batch, tx)
        results[name] = _train_once(model, mesh, state, batch)

    ref_loss, ref_state = results["dense"]
    for name, (loss, new_state) in results.items():
        np.testing.assert_allclose(loss, ref_loss, rtol=1e-5, err_msg=name)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
                err_msg=name,
            ),
            new_state.params,
            ref_state.params,
        )


# ------------------------------------------------------------- bit identity


# The PR-6 rule list, verbatim — the pre-plan layout the f32 path must
# reproduce bit-for-bit (specs named only the 'model' axis; everything
# else fell through to replication).
_PR6_RULES = [
    (r"transformer/layer_\d+/attn/(query|key|value)/kernel$", P(None, "model")),
    (r"transformer/layer_\d+/attn/(query|key|value)/bias$", P("model")),
    (r"transformer/layer_\d+/attn/out/kernel$", P("model", None)),
    (r"transformer/layer_\d+/ff/kernel$", P(None, "model")),
    (r"transformer/layer_\d+/ff/bias$", P("model")),
    (r"transformer/output_tokens/kernel$", P(None, "model")),
    (r"transformer/output_tokens/bias$", P("model")),
]


@pytest.mark.parametrize(
    "mesh_cfg,bitwise",
    [
        # Pure DP (the reference-parity configuration, and what every
        # existing run used): not a single f32 bit may move.
        (MeshConfig(), True),
        # dp x tp: the plan now shards the embeddings/head rows the old
        # rules replicated — an intentional layout extension, so the
        # program differs by collective schedule; reassociation-level
        # agreement is the contract.
        (MeshConfig(data=2, model=4), False),
    ],
)
def test_f32_path_bit_identical_to_pre_plan_rules(mesh_cfg, bitwise):
    """The plan refactor must not change f32 numerics: the default-plan
    step vs the step built from the PR-6 hand-written rule list."""
    mesh = make_mesh(mesh_cfg)
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    batch = (obs, actions)
    state = create_train_state(model, rng, batch, make_optimizer())

    loss_new, state_new = _train_once(model, mesh, state, batch)
    loss_old, state_old = _train_once(
        model, mesh, state, batch, param_rules=_PR6_RULES,
        batch_axes=("data",),
    )
    if bitwise:
        assert loss_new == loss_old  # bitwise, not allclose
        assert_leaf = lambda a, b: np.testing.assert_array_equal(  # noqa: E731
            np.asarray(a), np.asarray(b)
        )
    else:
        np.testing.assert_allclose(loss_new, loss_old, rtol=1e-6)
        # atol covers Adam's first-step ~sign(g): reassociation between
        # collective schedules lands as O(1e-8) deltas on the ±lr elements
        # wherever g ~ 0 (same amplification test_pp_train_step_equals_
        # dense documents).
        assert_leaf = lambda a, b: np.testing.assert_allclose(  # noqa: E731
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-8
        )
    jax.tree.map(assert_leaf, state_new.params, state_old.params)


def test_mixed_precision_off_is_default_program():
    """mixed_precision=False is a Python-level gate: the step it builds is
    the exact default program (guard/health discipline from PR 4/5)."""
    mesh = make_mesh(MeshConfig())
    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    batch = (obs, actions)
    state = create_train_state(model, rng, batch, make_optimizer())
    loss_off, state_off = _train_once(
        model, mesh, state, batch, mixed_precision=False
    )
    loss_plain, state_plain = _train_once(model, mesh, state, batch)
    assert loss_off == loss_plain
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        state_off.params,
        state_plain.params,
    )


# ---------------------------------------------------------- mixed precision


def test_mixed_precision_masters_stay_f32_and_loss_tracks_f32():
    """True mixed precision: the state's params + Adam moments stay f32
    across a donated step while fwd/bwd runs on the bf16 cast; the loss
    stays within bf16 rounding of the f32 step's."""
    mesh = make_mesh(MeshConfig())
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    batch = (obs, actions)

    model_f32 = tiny_policy()
    model_bf16 = tiny_policy(dtype=jnp.bfloat16)
    state = create_train_state(model_f32, rng, batch, make_optimizer())

    fns = make_train_step_fns(
        model_bf16, mesh, state, mixed_precision=True
    )  # donate=True: the mp cast must be donation-safe
    assert fns.mixed_precision
    s = fns.shard_state(state)
    b = fns.shard_batch(batch)
    s, metrics = fns.train_step(s, b, jax.random.PRNGKey(5))
    s, metrics = fns.train_step(s, b, jax.random.PRNGKey(6))
    mp_loss = float(metrics["loss"])
    assert np.isfinite(mp_loss)
    for leaf in jax.tree_util.tree_leaves(s.params):
        assert leaf.dtype == jnp.float32
    for leaf in jax.tree_util.tree_leaves(s.opt_state):
        assert leaf.dtype in (jnp.float32, jnp.int32), leaf.dtype
    assert int(s.step) == 2

    # f32 reference on the same masters/batch/rng draw.
    loss_f32_0, state_f32 = _train_once(
        model_f32, mesh,
        create_train_state(model_f32, rng, batch, make_optimizer()),
        batch,
    )
    # Step-2 f32 loss (post one update) is the comparable scalar.
    fns32 = make_train_step_fns(model_f32, mesh, state_f32, donate=False)
    _, m32 = fns32.train_step(
        fns32.shard_state(state_f32), fns32.shard_batch(batch),
        jax.random.PRNGKey(6),
    )
    np.testing.assert_allclose(mp_loss, float(m32["loss"]), rtol=0.05)


def test_mixed_precision_casts_compute_not_masters():
    """The cast helper: f32 leaves -> bf16, everything else untouched."""
    from rt1_tpu.trainer.train import _bf16_compute_copy

    tree = {
        "w": jnp.ones((2, 2), jnp.float32),
        "i": jnp.ones((2,), jnp.int32),
        "h": jnp.ones((2,), jnp.bfloat16),
    }
    out = _bf16_compute_copy(tree)
    assert out["w"].dtype == jnp.bfloat16
    assert out["i"].dtype == jnp.int32
    assert out["h"].dtype == jnp.bfloat16
    assert tree["w"].dtype == jnp.float32  # masters untouched

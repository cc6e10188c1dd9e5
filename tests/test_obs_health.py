"""obs/health.py + the health-pack train step (trainer/train.py).

The contract: with model_health on, the step returns ONE replicated
float32 vector of finite statistics whose layout matches
`fns.health_names`; with it off, the step is bit-identical to the
pre-health program (same discipline as the resilience guard); and the
pack composes with the guarded step. Plus the train-loop integration:
health/* scalars reach the TB events, goodput_summary.json lands with
buckets summing to 100%, and scripts/run_report.py merges it all.
"""

import math
import os

import jax
import numpy as np
import pytest

from rt1_tpu.obs import health

from test_rt1 import make_batch, tiny_policy


def _setup(model_health, donate=True, guard=False, task_names=()):
    from rt1_tpu.parallel import MeshConfig, make_mesh
    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )

    model = tiny_policy()
    rng = jax.random.PRNGKey(0)
    obs, actions = make_batch(rng, b=8)
    tx = make_optimizer(learning_rate=1e-3)
    state = create_train_state(model, rng, (obs, actions), tx)
    mesh = make_mesh(MeshConfig())
    fns = make_train_step_fns(
        model, mesh, state, model_health=model_health, donate=donate,
        guard_nonfinite=guard, health_task_names=task_names,
    )
    return fns, fns.shard_state(state), (obs, actions)


# ------------------------------------------------------------- pure module


def test_pack_names_layout_is_deterministic():
    params = {"b": {"x": np.ones(3)}, "a": {"y": np.ones(2), "z": np.ones(2)}}
    names = health.pack_names(params, depth=1, action_dims=2)
    assert names == (
        "health/grad_norm/a",
        "health/grad_norm/b",
        "health/update_ratio/a",
        "health/update_ratio/b",
        "health/param_norm_global",
        "health/update_norm_global",
        "health/logit_entropy",
        "health/token_acc/dim0",
        "health/token_acc/dim1",
    )
    # No action stats when the builder says there are none.
    assert health.pack_names(params, depth=1, action_dims=0) == names[:6]
    # Deeper than the tree: groups bottom out at the leaves, no error.
    deep = health.param_groups(params, depth=5)
    assert "a/y" in deep and "b/x" in deep


def test_param_groups_rejects_bad_depth():
    with pytest.raises(ValueError):
        health.param_groups({"a": np.ones(1)}, depth=0)


def test_unpack_rejects_layout_mismatch():
    with pytest.raises(ValueError):
        health.unpack(("a", "b"), np.zeros(3))


# ----------------------------------------------------------- stepped (jit)


def test_health_pack_finite_and_correctly_shaped():
    fns, state, batch = _setup(model_health=True)
    assert fns.health_names, "builder produced no health layout"
    state, metrics = fns.train_step(
        state, fns.shard_batch(batch), jax.random.PRNGKey(1)
    )
    vec = np.asarray(metrics[health.PACK_KEY])
    assert vec.dtype == np.float32
    assert vec.shape == (len(fns.health_names),)
    assert np.isfinite(vec).all()

    scalars = health.unpack(fns.health_names, vec)
    model = tiny_policy()
    # Per-dimension token accuracy is a probability; entropy is bounded by
    # log(vocab); norms are positive on a real gradient step.
    for k in range(model.tokens_per_action):
        assert 0.0 <= scalars[f"health/token_acc/dim{k}"] <= 1.0
    assert 0.0 <= scalars["health/logit_entropy"] <= math.log(
        model.vocab_size
    ) + 1e-5
    assert scalars["health/param_norm_global"] > 0
    assert scalars["health/update_norm_global"] > 0
    grad_norms = [
        v for n, v in scalars.items() if n.startswith("health/grad_norm/")
    ]
    ratios = [
        v for n, v in scalars.items() if n.startswith("health/update_ratio/")
    ]
    assert grad_norms and ratios
    assert all(v >= 0 for v in grad_norms + ratios)


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of its nested jaxprs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


def test_health_pack_concatenates_no_copy_of_the_state():
    """The pack reduces each leaf where it is made: nothing under the
    `health` scope may concatenate more than one scalar per leaf or per
    pack entry (a flat copy of a layer group's updates and parameters, as
    concat + vdot built, was 1.5 % of the flagship's step on the chip)."""
    fns, state, batch = _setup(model_health=True, donate=False, guard=True)
    jaxpr = jax.make_jaxpr(fns.train_step)(
        state, fns.init_guard_skips(), fns.shard_batch(batch),
        jax.random.PRNGKey(1),
    )
    limit = max(len(fns.health_names), len(jax.tree.leaves(state.params)))
    sizes = [
        int(np.prod(eqn.outvars[0].aval.shape))
        for eqn in _eqns(jaxpr.jaxpr)
        if eqn.primitive.name == "concatenate"
        and "health" in str(eqn.source_info.name_stack)
    ]
    # The pack itself is one of them, so the walk did reach the scope.
    assert len(fns.health_names) in sizes
    assert max(sizes) <= limit, (sorted(sizes)[-5:], limit)


@pytest.mark.parametrize("depth", [1, 2])
def test_health_pack_matches_numpy_reference(depth):
    """Optimizer and pack in one jitted program, as in the step; the
    reference takes the SAME updates, new params and grads to the host
    and reduces them in float64."""
    _, state, _ = _setup(model_health=False, donate=False)
    leaves, treedef = jax.tree.flatten(jax.device_get(state.params))
    rng = np.random.default_rng(depth)
    grads = jax.tree.unflatten(treedef, [
        (rng.standard_normal(p.shape) * 10.0 ** rng.uniform(-4, 0)).astype(p.dtype)
        for p in leaves
    ])

    @jax.jit
    def step(state, grads):
        new_state, updates = state.apply_gradients(grads, return_updates=True)
        pack = health.compute_pack(
            updates, new_state.params, grads, out={}, depth=depth
        )
        return pack, updates, new_state.params

    pack, updates, new_params = jax.device_get(step(state, grads))
    names = health.pack_names(new_params, depth=depth)
    got = health.unpack(names, pack)

    def sumsq(tree):
        out = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            group = health._path_str(path[:depth])
            out[group] = out.get(group, 0.0) + float(
                np.sum(np.square(np.asarray(leaf, np.float64)))
            )
        return out

    grad_sq, upd_sq, new_sq = sumsq(grads), sumsq(updates), sumsq(new_params)
    groups = health.param_groups(new_params, depth)
    assert len(groups) > 1 and len(names) == 2 * len(groups) + 2
    want = {"health/param_norm_global": math.sqrt(sum(new_sq.values())),
            "health/update_norm_global": math.sqrt(sum(upd_sq.values()))}
    for g in groups:
        want[f"health/grad_norm/{g}"] = math.sqrt(grad_sq[g])
        want[f"health/update_ratio/{g}"] = math.sqrt(upd_sq[g]) / (
            math.sqrt(new_sq[g]) + 1e-12
        )
    assert set(want) == set(names)
    for name in names:
        assert got[name] == pytest.approx(want[name], rel=1e-5), name


def test_health_off_step_is_bit_identical():
    """The model_health=False path must trace the exact pre-change program:
    same metrics keys, same params to the ULP as the health-on step's."""
    fns_on, state_on, batch = _setup(model_health=True, donate=False)
    fns_off, state_off, _ = _setup(model_health=False, donate=False)
    assert fns_off.health_names == ()
    rng = jax.random.PRNGKey(7)
    state_on, m_on = fns_on.train_step(
        state_on, fns_on.shard_batch(batch), rng
    )
    state_off, m_off = fns_off.train_step(
        state_off, fns_off.shard_batch(batch), rng
    )
    assert health.PACK_KEY in m_on and health.PACK_KEY not in m_off
    assert float(m_on["loss"]) == float(m_off["loss"])
    for a, b in zip(
        jax.tree.leaves(jax.device_get(state_on.params)),
        jax.tree.leaves(jax.device_get(state_off.params)),
    ):
        np.testing.assert_array_equal(a, b)


def test_health_pack_per_task_segment_reduction():
    """ISSUE 13: with health_task_names and a batch carrying TASK_ID_KEY,
    the pack gains task_loss/task_acc/task_frac per task, computed by the
    in-step one-hot reduction. Invariants: fracs sum to 1, a task absent
    from the batch reports 0/0/0, and the frac-weighted per-task loss and
    accuracy reproduce the batch-level loss / mean token accuracy."""
    names = ("block2block", "corner", "other")
    fns, state, (obs, actions) = _setup(
        model_health=True, donate=False, task_names=names
    )
    for suffix in ("loss", "acc", "frac"):
        for t in names:
            assert f"health/task_{suffix}/{t}" in fns.health_names
    # 5 block2block rows, 3 corner rows, nobody in 'other'.
    task_ids = np.array([0, 0, 0, 0, 0, 1, 1, 1], np.int32)
    obs = dict(obs, task_id=task_ids)
    state, metrics = fns.train_step(
        state, fns.shard_batch((obs, actions)), jax.random.PRNGKey(1)
    )
    scalars = health.unpack(
        fns.health_names, np.asarray(metrics[health.PACK_KEY])
    )
    fracs = {t: scalars[f"health/task_frac/{t}"] for t in names}
    assert fracs["block2block"] == pytest.approx(5 / 8)
    assert fracs["corner"] == pytest.approx(3 / 8)
    assert fracs["other"] == 0.0
    assert scalars["health/task_loss/other"] == 0.0
    assert scalars["health/task_acc/other"] == 0.0
    # Weighted recomposition: sum_k frac_k * task_loss_k == batch loss,
    # and likewise for token accuracy (mean of the per-dim entries).
    recomposed_loss = sum(
        fracs[t] * scalars[f"health/task_loss/{t}"] for t in names
    )
    assert recomposed_loss == pytest.approx(float(metrics["loss"]), rel=1e-5)
    dim_accs = [
        v for n, v in scalars.items() if n.startswith("health/token_acc/")
    ]
    recomposed_acc = sum(
        fracs[t] * scalars[f"health/task_acc/{t}"] for t in names
    )
    assert recomposed_acc == pytest.approx(
        float(np.mean(dim_accs)), rel=1e-5, abs=1e-6
    )


def test_task_ids_stripped_before_model():
    """A batch carrying task ids must produce the exact same update as
    the same batch without them — the step strips TASK_ID_KEY before the
    model forward, so the observation contract is untouched."""
    fns_plain, state_plain, (obs, actions) = _setup(
        model_health=True, donate=False
    )
    fns_task, state_task, _ = _setup(
        model_health=True, donate=False, task_names=("a", "b")
    )
    rng = jax.random.PRNGKey(3)
    obs_tagged = dict(
        obs, task_id=np.zeros((obs["image"].shape[0],), np.int32)
    )
    state_plain, m_plain = fns_plain.train_step(
        state_plain, fns_plain.shard_batch((obs, actions)), rng
    )
    state_task, m_task = fns_task.train_step(
        state_task, fns_task.shard_batch((obs_tagged, actions)), rng
    )
    assert float(m_plain["loss"]) == float(m_task["loss"])
    for a, b in zip(
        jax.tree.leaves(jax.device_get(state_plain.params)),
        jax.tree.leaves(jax.device_get(state_task.params)),
    ):
        np.testing.assert_array_equal(a, b)


def test_health_composes_with_guard():
    from rt1_tpu.resilience import faults

    fns, state, batch = _setup(model_health=True, guard=True)
    assert fns.guarded and fns.health_names
    skips = fns.init_guard_skips()
    state, skips, metrics = fns.train_step(
        state, skips, fns.shard_batch(batch), jax.random.PRNGKey(1)
    )
    assert int(metrics["guard_skips_cum"]) == 0
    assert np.isfinite(np.asarray(metrics[health.PACK_KEY])).all()

    # A poisoned batch: the update is dropped, and the pack honestly shows
    # the non-finite statistics of the dropped update (that is the signal).
    obs, actions = batch
    bad = fns.shard_batch((faults.poison_batch(obs), actions))
    state, skips, metrics = fns.train_step(
        state, skips, bad, jax.random.PRNGKey(2)
    )
    assert int(skips) == 1
    vec = health.unpack(
        fns.health_names, np.asarray(metrics[health.PACK_KEY])
    )
    assert not all(np.isfinite(v) for v in vec.values())


# ----------------------------------------------------------- loop e2e


@pytest.mark.slow
def test_train_loop_emits_per_task_health_live(tmp_path):
    """ISSUE 13 acceptance shape: a live tiny train run over a packed
    MULTI-task corpus with model_health on emits health/task_* scalars to
    TB and rt1_train_health_task_* gauges on a live Prometheus scrape,
    with the task mixture weighted by config.data.task_weights."""
    import json
    import subprocess
    import sys
    import time
    import urllib.request

    import numpy as np

    from rt1_tpu.data import episodes as ep_lib
    from rt1_tpu.data import pack as pack_lib

    # 6 episodes, two tagged families + untagged, at tiny geometry.
    src = tmp_path / "store" / "train"
    src.mkdir(parents=True)
    rng = np.random.default_rng(0)
    paths = []
    for i, task in enumerate(
        ("block2block", "block2block", "block2block",
         "block1_to_corner", "block1_to_corner", None)
    ):
        ep = ep_lib.generate_synthetic_episode(
            rng, num_steps=8, height=32, width=56
        )
        if task:
            ep["task"] = ep_lib.encode_instruction_text(task)
        p = str(src / f"episode_{i}.npz")
        ep_lib.save_episode(p, ep)
        paths.append(p)
    pack_lib.pack_episodes(
        paths, str(tmp_path / "store" / "train_packed"), 32, 56, 0.95
    )

    workdir = str(tmp_path / "run")
    port = 19137
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "rt1_tpu.train.train",
            "--config", "rt1_tpu/train/configs/tiny.py",
            "--workdir", workdir,
            "--config.data.data_dir", str(tmp_path / "store"),
            "--config.data.packed_cache=True",
            "--config.data.task_weights=block2block:2,block1_to_corner:1,"
            "unknown:1",
            "--config.obs.model_health=True",
            f"--config.obs.prometheus_port={port}",
            "--config.num_steps=25",
            "--config.log_every_steps=5",
            "--config.eval_every_steps=0",
        ],
        cwd=os.path.join(os.path.dirname(__file__), ".."),
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    scrape = None
    try:
        deadline = time.time() + 600
        while proc.poll() is None and time.time() < deadline:
            try:
                with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=2
                ) as resp:
                    body = resp.read().decode("utf-8")
                if "rt1_train_health_task_loss_block2block" in body:
                    scrape = body
                    break
            except OSError:
                pass
            time.sleep(1.0)
        out, _ = proc.communicate(timeout=600)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == 0, out[-4000:]
    assert scrape is not None, (
        "no live scrape carried per-task health gauges\n" + out[-4000:]
    )
    for name in (
        "rt1_train_health_task_loss_block2block",
        "rt1_train_health_task_acc_block2block",
        "rt1_train_health_task_frac_block2block",
        "rt1_train_health_task_loss_block1_to_corner",
        "rt1_train_health_task_frac_unknown",
        "rt1_train_health_task_frac_other",
    ):
        assert name in scrape, name

    sys.path.insert(
        0, os.path.join(os.path.dirname(__file__), "..", "scripts")
    )
    import run_report

    tb = run_report.load_tb_scalars(workdir)
    assert tb is not None
    assert "health/task_loss/block2block" in tb
    assert "health/task_acc/block1_to_corner" in tb
    assert "health/task_frac/unknown" in tb
    # The weighted mixture shows in the emitted fracs: block2block got
    # weight 2 of 4 over half the corpus windows — its frac should beat
    # the unweighted 0.5 corpus share... at least be the plurality.
    fracs = {
        t: v for t, (_, v) in tb.items()
        if t.startswith("health/task_frac/")
    }
    assert json.dumps(fracs)  # JSON-clean
    assert fracs["health/task_frac/block2block"] >= max(
        fracs["health/task_frac/block1_to_corner"],
        fracs["health/task_frac/unknown"],
    )


@pytest.mark.slow
def test_train_loop_emits_health_goodput_and_report(tmp_path, monkeypatch):
    """Integration over the tiny synthetic config: health/* scalars land in
    the TB events, goodput_summary.json's buckets sum to 100%±1 with a live
    MFU gauge, and run_report merges both into one report."""
    import sys

    import jax

    from rt1_tpu.obs import flops as flops_lib

    # The MFU gauge arms only for a device with a known peak; give the
    # test's CPU one so the gauge's plumbing is exercised.
    monkeypatch.setitem(
        flops_lib.PEAK_FLOPS_BY_DEVICE_KIND,
        jax.devices()[0].device_kind,
        1e12,
    )

    from rt1_tpu.train.configs import tiny
    from rt1_tpu.train.train import train_and_evaluate

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "scripts"))
    import run_report

    config = tiny.get_config()
    config.data.height, config.data.width = 32, 56
    config.num_steps = 4
    config.log_every_steps = 1
    config.obs.model_health = True
    config.obs.goodput_mfu = True
    workdir = str(tmp_path / "run")
    train_and_evaluate(config, workdir)

    goodput = run_report.load_goodput(workdir)
    assert goodput is not None
    assert sum(goodput["fractions"].values()) == pytest.approx(1.0, abs=0.01)
    assert goodput["steps_productive"] == 4  # step 0 too, less what it compiled
    # The start-up log rides in the summary, and the ledger's compile bucket
    # is what the log measured over this run.
    from rt1_tpu.obs import startup

    snap = goodput["startup"]
    assert {"backend_init", "build_model", "make_optimizer", "init_state", "restore",
            "make_step_fns", "shard_state", "first_batch", "first_step"} <= set(snap["phase_s"])
    step_role = snap["roles"]["train_step"]
    assert step_role["functions"] == ["train_step_guarded"]
    assert step_role["inner_traces"] > 0 and step_role["compiles"] >= 1
    assert 0 < goodput["buckets_s"]["compile"] <= startup.compile_seconds()
    assert goodput["buckets_s"]["compile"] >= (
        step_role["trace_s"] + step_role["lower_s"] + step_role["backend_s"]) * 0.99
    assert "mfu_pct" in goodput and goodput["flops_per_step"] > 0

    tb = run_report.load_tb_scalars(workdir)
    assert tb is not None, "no TB events readable"
    health_tags = [t for t in tb if t.startswith("health/")]
    assert any("grad_norm" in t for t in health_tags)
    assert any("update_ratio" in t for t in health_tags)
    assert "health/logit_entropy" in tb
    assert "health/token_acc/dim0" in tb
    goodput_tags = [t for t in tb if t.startswith("goodput/")]
    assert "goodput/goodput_pct" in goodput_tags
    assert "goodput/mfu_pct" in goodput_tags
    assert {"compile/seconds_total", "compile/compiles_total",
            "compile/recompiles_total"} <= set(tb)

    report = run_report.render_report(
        workdir, goodput, run_report.load_flight(workdir), tb
    )
    assert "Where the hours went" in report
    assert "health/logit_entropy" in report
    assert "MFU" in report

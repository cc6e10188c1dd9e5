"""Guard: `rt1_tpu.obs` must import (and work) with no clu/tensorboard/
tensorflow available — headless serve deployments scrape /metrics without
dragging in the training stack. A fresh interpreter with those imports
poisoned must still import the package and render exposition text.
"""

import os
import subprocess
import sys

_PROBE = r"""
import sys

BLOCKED = ("clu", "tensorboard", "tensorflow")


class Blocker:
    def find_spec(self, name, path=None, target=None):
        if name.split(".")[0] in BLOCKED:
            raise ImportError(f"blocked by test_obs_imports: {name}")


sys.meta_path.insert(0, Blocker())

import rt1_tpu.obs as obs

# The start-up log (PR 36) comes with the package: stdlib at import, jax
# looked up and never imported, and a phase works where jax is not loaded.
assert "jax" not in sys.modules and "tensorflow" not in sys.modules
with obs.startup.phase("probe"):
    pass
assert obs.startup.install() is False
assert obs.startup.snapshot()["phase_s"]["probe"]["count"] == 1
assert obs.startup.compile_seconds() == 0.0

# The pieces a serve-only deployment touches must all be live.
tracer = obs.trace.enable()
with obs.trace.span("probe"):
    pass
assert len(tracer.to_dict()["traceEvents"]) >= 1

tl = obs.StepTimeline(window=4)
tl.start_step(0)
tl.end_step()
assert "stall_pct" in tl.scalars()

rec = obs.FlightRecorder(capacity=4)
rec.record(1, loss=0.5)
assert len(rec) == 1

# PR 5 modules: health / goodput / flops must import and do host-side work
# under the same blocker (jax is allowed; clu/tensorboard/tensorflow not).
clock = iter(range(100)).__next__
ledger = obs.GoodputLedger(clock=lambda: float(clock()))
ledger.note_step({"total_ms": 1000.0, "wait_data_ms": 100.0})
ledger.note_step({"total_ms": 1000.0, "wait_data_ms": 100.0})
s = ledger.summary()
assert abs(sum(s["fractions"].values()) - 1.0) < 1e-9
assert obs.goodput.SUMMARY_BASENAME.endswith(".json")

names = obs.health.pack_names({"a": {"w": [1.0]}}, depth=1, action_dims=2)
assert names[0] == "health/grad_norm/a"
assert names[-1] == "health/token_acc/dim1"
assert obs.health.unpack(("x",), [1.5]) == {"x": 1.5}

assert obs.flops.mfu_pct(100.0, 1.0, n_chips=1, peak_flops=1000.0) == 10.0
assert obs.flops.cost_analysis_flops({"flops": 3.0}) == 3.0
assert obs.flops.cost_analysis_flops(None) == 0.0

from rt1_tpu.serve.metrics import ServeMetrics

text = ServeMetrics().prometheus_text(active_sessions=0)
assert "# TYPE rt1_serve_requests_total counter" in text
assert 'le="+Inf"' in text

# ISSUE 12 serve hot path: the continuous scheduler is stdlib-only (it
# runs in every replica AND in the jax-free stub/fleet rehearsals), and
# the new bucket/pipeline metric families render through the same
# snapshot→text path.
from rt1_tpu.serve.batcher import ContinuousBatcher  # noqa: F401

m12 = ServeMetrics()
m12.observe_batch(2, queued=0, in_flight=2, joined_mid_cycle=2)
m12.observe_bucket(2, 2)
text12 = m12.prometheus_text(bucket_count=2)
assert 'rt1_serve_bucket_batches_total{bucket="2"} 1' in text12
assert "rt1_serve_joined_mid_cycle_total 2" in text12
assert "rt1_serve_batches_in_flight 2" in text12

# Fleet layer: router, supervisor, and the stub replica are the pieces a
# model-free router process runs — all must work under the same blocker.
from rt1_tpu.serve.router import Router
from rt1_tpu.serve.stub import StubReplicaApp
import rt1_tpu.serve.fleet  # noqa: F401 - import-time deps only

router_text = Router().metrics_prometheus()
assert "rt1_serve_replicas_total" in router_text
assert "# TYPE rt1_serve_reloads_total counter" in router_text
# The router's SLO gauges render on the same scrape (PR 8): the ledger
# and the shared quantile math are stdlib-only by contract.
assert "rt1_serve_slo_availability 1" in router_text
assert "rt1_serve_slo_error_budget_burn 0" in router_text
stub = StubReplicaApp(replica_id=7)
assert stub.healthz()["replica_id"] == 7
assert stub.readyz()[0] == 200
# The stub mimics the ISSUE 12 scheduling contract jax-free: bucket
# ladder advertised, compile_count pinned at the bucket count.
stub12 = StubReplicaApp(replica_id=8, buckets=[1, 2, 4])
assert stub12.healthz()["compile_count"] == 3
assert stub12.healthz()["buckets"] == [1, 2, 4]
assert stub12.healthz()["scheduler"] == "continuous"
assert stub12.metrics_snapshot()["bucket_count"] == 3

# ISSUE 15 elastic fleet: the autoscaler decision module and the router's
# admission controller both run inside the model-free router/supervisor
# process — stdlib-only by contract, and the new autoscale/admission
# metric families render through the same snapshot->text path.
from rt1_tpu.serve.autoscale import (
    Autoscaler,
    AutoscalePolicy,
    FleetSignals,
)

policy15 = AutoscalePolicy(
    min_replicas=1, max_replicas=3, up_sustain_ticks=1,
    up_cooldown_ticks=0)
scaler15 = Autoscaler(policy15)
decision15 = scaler15.decide(FleetSignals(
    replicas_total=1, replicas_ready=1, active_sessions=4,
    session_slots=2))
assert decision15 is not None and decision15.direction == "up"

from rt1_tpu.serve.router import AdmissionController

clock15 = {"t": 0.0}
adm15 = AdmissionController(
    rate_per_client=1.0, burst=1.0, clock=lambda: clock15["t"])
assert adm15.reject_reason("c", 0) is None
assert adm15.reject_reason("c", 0) == "client_rate"
assert adm15.gauges()["admission_clients_tracked"] == 1.0

m15 = ServeMetrics()
m15.observe_scale_event("up")
m15.observe_shed("client_rate")
m15.set_autoscale_state(replicas=2, tier_replicas={"f32": 1, "int8": 1})
text15 = m15.prometheus_text()
assert 'rt1_serve_autoscale_scale_events_total{direction="up"} 1' in text15
assert 'rt1_serve_autoscale_shed_total{reason="client_rate"} 1' in text15
assert 'rt1_serve_autoscale_tier_replicas{dtype="int8"} 1' in text15
assert "rt1_serve_autoscale_replicas 2" in text15

# PR 8 serving-observability pieces: the SLO ledger, the shared
# percentile helpers, the request tracer, and the exemplar ring all run
# in the router / replica processes — stdlib + obs only.
from rt1_tpu.obs.quantiles import bucket_quantile, percentile
from rt1_tpu.serve import reqtrace

assert percentile([1.0, 2.0, 3.0], 0.5) == 2.0
assert bucket_quantile((0.1, 1.0), (1, 1), 2, 0.5, 0.99) == 1.0

ledger = obs.SLOLedger(obs.SLOObjectives(availability=0.95))
ledger.observe("ok", 0.01)
ledger.observe("restarted", 0.05)
assert ledger.gauges()["slo_availability"] == 0.5
assert ledger.summary()["by_class"]["restarted"]["count"] == 1

ring = obs.ExemplarRing(capacity=2, threshold_ms=1.0)
assert ring.offer(5.0, request_id="r1", outcome="ok")
assert not ring.offer(0.5, request_id="r2")
assert ring.stats()["retained"] == 1

phases = reqtrace.RequestPhases(reqtrace.request_id_from(
    {reqtrace.REQUEST_ID_HEADER: "probe-id"}))
assert phases.request_id == "probe-id"
assert phases.phases_ms()["queue_wait_ms"] is None

# The fleet aggregation renderer (the router /metrics text path).
from rt1_tpu.obs.prometheus import fleet_metric_names, render_fleet_snapshot

fleet_text = render_fleet_snapshot(
    {"requests_total": 1}, {0: {"compile_count": 1}, 1: None})
assert 'rt1_serve_replica_up{replica_id="0"} 1' in fleet_text
assert 'rt1_serve_replica_up{replica_id="1"} 0' in fleet_text
assert 'rt1_serve_replica_compile_count{replica_id="0"} 1' in fleet_text
assert "rt1_serve_replica_up" in fleet_metric_names()

# Parallelism plan: serve processes resolve the declarative sharding plan
# (engine param placement) without the training stack — the whole module,
# mesh construction, rule matching, and the coverage check must work under
# the blocker (jax is allowed; clu/tensorboard/tensorflow are not).
import numpy as _np

from rt1_tpu.parallel import (
    MeshConfig,
    ShardingPlan,
    auto_mesh_shape,
    make_mesh,
    rt1_sharding_plan,
)

assert auto_mesh_shape(8) == (2, 2, 2)
assert any("ffn/experts" in pat for pat, _ in rt1_sharding_plan())
plan = ShardingPlan(mesh=make_mesh(MeshConfig()))
assert plan.coverage({"transformer": {"layer_0": {"ff": {
    "kernel": _np.zeros((4, 4))}}}}) == []
assert plan.coverage({"mystery": {"w": _np.zeros((4, 4))}}) == ["mystery/w"]
assert plan.spec_for("transformer/layer_0/attn/query/kernel") is not None

from rt1_tpu.eval.restore import serving_plan

assert serving_plan({"parallel": {}}).mesh.devices.size == 1

# ISSUE 14 plan migration + distributed init: serve replicas restore
# pod-trained checkpoints through reshard (abstract target templates,
# host gather->slice fallback) and the distributed options resolve from
# config/env — all without clu/tensorboard/tensorflow.
from rt1_tpu.parallel import reshard
from rt1_tpu.parallel.distributed import DistributedOptions

_tree = {"transformer": {"layer_0": {"ff": {"kernel": _np.ones((4, 4), _np.float32)}}}}
_tpl = reshard.abstract_target(_tree, plan)
_leaf = _tpl["transformer"]["layer_0"]["ff"]["kernel"]
assert _leaf.shape == (4, 4) and _leaf.sharding is not None
_placed = reshard.place_on_plan(_tree, plan)
assert reshard.gathered_equal(_placed, _tree)
_opts = DistributedOptions.from_config({"parallel": {"distributed": {}}})
assert not _opts.enabled
_opts.validate()

# ISSUE 9 low-precision serving: the quant mechanics, the parity gate,
# and the plan's quant rules all run inside serve processes — importable
# and functional under the blocker (flax/jax allowed; the training stack
# is not).
from rt1_tpu.models.quant import (
    dequantize,
    quantize_per_channel,
    serving_preparer,
    tree_bytes,
)

q, s = quantize_per_channel(_np.ones((4, 3), _np.float32))
assert q.dtype == _np.int8 and s.shape == (3,)
assert (dequantize(q, s) == 1.0).all()
assert serving_preparer("f32") is None
assert serving_preparer("int8") is not None
assert tree_bytes({"w": _np.zeros((2, 2), _np.float32)}) == 16

from rt1_tpu.parallel.plan import (
    QUANT_F32,
    QUANT_INT8,
    quant_group_for_path,
    rt1_quant_rules,
)

assert rt1_quant_rules()
assert quant_group_for_path(
    "params/transformer/layer_0/attn/query/kernel") == QUANT_INT8
assert quant_group_for_path(
    "params/transformer/output_tokens/kernel") == QUANT_F32

from rt1_tpu.serve.parity import (
    PARITY_THRESHOLD,
    canned_episodes,
    check_cached_parity,  # noqa: F401 - import-time deps only (jax-free)
)

assert PARITY_THRESHOLD >= 0.99
assert len(canned_episodes((2, 2, 3), episodes=1, steps=2)[0]) == 2

# ISSUE 17 KV-cache observability: a cached-inference stub advertises the
# flag and its cache counter families render through the same
# snapshot->text path (labeled invalidations ride the DICT_GAUGES seam).
_cached_stub = StubReplicaApp(replica_id=2, cached_inference=True)
assert _cached_stub.healthz()["cached_inference"] is True
_cached_stub.act({"session_id": "kv", "image": []})
_cached_stub.reset({"session_id": "kv"})
cache_text = _cached_stub.metrics_prometheus()
assert "# TYPE rt1_serve_cache_cached_steps_total counter" in cache_text
assert 'rt1_serve_cache_invalidations_total{reason="reset"} 1' in cache_text
assert "rt1_serve_cache_bytes_per_slot 2048" in cache_text
assert "rt1_serve_replica_cache_cached_steps_total" in fleet_metric_names()

# A mixed-dtype stub advertises its mode; the fleet renderer turns it
# into the labeled info family the scrape contract names.
assert StubReplicaApp(
    replica_id=1, inference_dtype="int8").healthz()["inference_dtype"] == "int8"
dtype_text = render_fleet_snapshot(
    {}, {0: {"inference_dtype": "int8", "param_bytes_device": 7.0}})
assert (
    'rt1_serve_replica_inference_dtype{replica_id="0",dtype="int8"} 1'
    in dtype_text
)
assert 'rt1_serve_replica_param_bytes_device{replica_id="0"} 7' in dtype_text
assert "rt1_serve_replica_inference_dtype" in fleet_metric_names()

# ISSUE 10 data flywheel: the capture sink runs inside serve replicas and
# the sweep inside the model-free fleet supervisor — importable and
# functional under the blocker (numpy allowed; clu/TF are not).
import tempfile as _tempfile

from rt1_tpu.flywheel import EpisodeCaptureSink, sweep_captures

with _tempfile.TemporaryDirectory() as _cap:
    _sink = EpisodeCaptureSink(_cap, min_steps=1)
    _sink.record_step(
        "probe",
        image=_np.zeros((4, 6, 3), _np.float32),
        action=[0.0, 0.0],
        embedding=_np.zeros((8,), _np.float32),
    )
    assert _sink.finalize("probe", "released")
    assert _sink.stats()["capture_episodes_total"] == 1
    with _tempfile.TemporaryDirectory() as _stage:
        assert sweep_captures([_cap], _stage) == 1

# The capture gauges render through the serve snapshot path, and the
# flywheel gauges through the scalar renderer, all clu/TF-free.
cap_text = ServeMetrics().prometheus_text(
    capture_enabled=1, capture_episodes_total=1)
assert "# TYPE rt1_serve_capture_episodes_total counter" in cap_text
from rt1_tpu.obs.prometheus import render_scalar_gauges

assert "rt1_flywheel_shards 2" in render_scalar_gauges(
    {"shards": 2}, prefix="rt1_flywheel_")

# ISSUE 13 quality-observability plane: the eval-matrix sweep driver is
# import-light by contract (a serve-side promotion controller runs it),
# and the per-task serve labels render through the same snapshot->text
# path — all clu/TF-free.
from rt1_tpu.eval.matrix import EvalMatrixState, checkpoint_steps

st = EvalMatrixState()
st.note_cell("block1_to_corner", "100", 1, 2, 3.0)
mtext = st.render_prometheus()
assert (
    'rt1_eval_success{task="block1_to_corner",checkpoint="100"} 0.5'
    in mtext
)
assert (
    'rt1_eval_episodes_total{task="block1_to_corner",checkpoint="100"} 2'
    in mtext
)
assert checkpoint_steps("/nonexistent/workdir") == []

mt = ServeMetrics()
mt.observe_task_request("unknown:probe", new_session=True)
mt.observe_task_request(None)
ttext = mt.prometheus_text()
assert 'rt1_serve_task_requests_total{task="unknown:probe"} 1' in ttext
assert 'rt1_serve_task_requests_total{task="unlabeled"} 1' in ttext
assert 'rt1_serve_task_sessions_total{task="unknown:probe"} 1' in ttext

# ISSUE 16 continuous deployment: the promotion controller lives inside
# the fleet supervisor process — the whole rt1_tpu.deploy package (state
# machine, burn-window judge, checkpoint watcher, signed verdicts) and
# its rt1_deploy_* exposition must work under the blocker. Only CALLING
# the real gate (deploy/gate.py internals) pays the jax context.
import rt1_tpu.deploy as deploy

judge16 = deploy.CanaryJudge(deploy.CanaryPolicy(clean_window_ticks=1))
from rt1_tpu.deploy.decision import CanarySignals

assert judge16.decide(
    CanarySignals(canary_requests=100, canary_burn=0.0)) == "promote"
assert deploy.latest_checkpoint_step("/nonexistent/ckpts") is None

from rt1_tpu.deploy import verdict as verdict16

with _tempfile.TemporaryDirectory() as _vd:
    _vp = _vd + "/verdict_1.json"
    verdict16.write_verdict(_vp, {"passed": True}, "probe-key")
    _pay, _ok = verdict16.verify_verdict(_vp, "probe-key")
    assert _ok and _pay["passed"]

from rt1_tpu.deploy.controller import PromotionController
from rt1_tpu.obs.prometheus import render_deploy_snapshot

with _tempfile.TemporaryDirectory() as _dw:
    ctrl16 = PromotionController(
        Router(), _dw, gate_fn=lambda c, i: {"passed": True})
    ctrl16.tick()
    dtext = render_deploy_snapshot(ctrl16.deploy_gauges())
assert 'rt1_deploy_state{state="idle"} 1' in dtext
assert "# TYPE rt1_deploy_candidates_seen_total counter" in dtext
assert "rt1_deploy_canary_weight 0.25" in dtext

# The router's canary seam is part of the same jax-free surface.
router16 = Router()
from rt1_tpu.serve.router import Replica as _Replica

router16.add_replica(_Replica(0))
router16.set_canary(0, 0.5)
assert router16.canary_status()["weight"] == 0.5
assert router16.clear_canary() == 0

# ISSUE 18 metrics plane: the TSDB, collector, alert engine, and both
# dashboard skins all live inside the model-free router/supervisor
# process (and the standalone obs scripts) — stdlib + obs only, and the
# whole loop (scrape -> store -> evaluate -> render) must work under the
# blocker.
from rt1_tpu.obs.alerts import AlertManager, default_ruleset
from rt1_tpu.obs.collector import Collector, Target
from rt1_tpu.obs.dashboard import render_console, render_dashboard_html
from rt1_tpu.obs.prometheus import parse_exposition
from rt1_tpu.obs.tsdb import TSDB

_clock18 = {"t": 1000.0}
tsdb18 = TSDB(clock=lambda: _clock18["t"])
mgr18 = AlertManager(
    tsdb18, default_ruleset(), clock=lambda: _clock18["t"])
assert len(mgr18.status()["rules"]) >= 9
col18 = Collector(
    tsdb18,
    [Target("probe", "http://unused/metrics")],
    alert_manager=mgr18,
    clock=lambda: _clock18["t"],
    fetch_fn=lambda url, timeout_s: (
        "# TYPE rt1_serve_replica_up gauge\n"
        'rt1_serve_replica_up{replica_id="0"} 0\n'
    ),
)
_clock18["t"] += 120.0
assert col18.scrape_once()["probe"] == 1
assert mgr18.active() and mgr18.active()[0]["alert"] == "ReplicaDown"
assert tsdb18.query("rt1_serve_replica_up", "latest", 60.0,
                    labels={"replica_id": "0"}) == 0.0
rt18 = parse_exposition(col18.prometheus_text())
assert rt18.value("rt1_obs_collector_up", target="probe") == 1.0
assert "ReplicaDown" in render_console(tsdb18, alert_manager=mgr18)
assert "<html>" in render_dashboard_html(tsdb18, alert_manager=mgr18,
                                         collector=col18)

# The time-windowed SLO burn (satellite of ISSUE 18) is part of the same
# stdlib-only ledger the router scrapes.
_sclock18 = {"t": 0.0}
sled18 = obs.SLOLedger(clock=lambda: _sclock18["t"])
sled18.observe("failed", 1.0)
assert sled18.windowed_burn(60.0) > 0
_sclock18["t"] += 120.0
assert sled18.windowed_burn(60.0) == 0.0

# ISSUE 19 durable sessions: the migration module runs inside the
# model-free router/supervisor process (live migration over HTTP) and
# the stub replica (jax-free snapshots) — stdlib-only by contract, and
# the two new metric family groups render through the same paths.
from rt1_tpu.serve import migrate as migrate19

snap19 = {
    "version": migrate19.SNAPSHOT_VERSION,
    "session_id": "probe",
    "step_index": 3,
    "checkpoint_generation": -1,
    "window": 6,
    "cached_inference": False,
    "schema": [["stub_step", [], "int64"]],
    "state": {"stub_step": {"data": [3]}},
}
migrate19.check_compatibility(
    snap19, checkpoint_generation=-1, window=6, cached_inference=False,
    schema=[("stub_step", (), "int64")])
try:
    migrate19.check_compatibility(snap19, checkpoint_generation=7)
except migrate19.SnapshotCompatibilityError as exc:
    assert "checkpoint_generation" in str(exc)
else:
    raise AssertionError("generation mismatch must refuse by name")
assert migrate19.decode_state(snap19["state"])["stub_step"] == [3]
_rt19 = migrate19.decode_state(migrate19.encode_state({"w": [1.0, 2.0]}))
assert list(_rt19["w"]) == [1.0, 2.0]
with _tempfile.TemporaryDirectory() as _ringd:
    ring19 = migrate19.SnapshotRing(_ringd, capacity=2)
    ring19.save(snap19)
    rec19, age19 = ring19.load("probe")
    assert rec19["step_index"] == 3 and age19 >= 0.0

# The stub speaks the full export/import contract jax-free, and the
# migration counter families render only once armed (or nonzero).
stub19 = StubReplicaApp(replica_id=3)
assert "migration_exports_total" not in stub19.metrics_snapshot()
stub19.act({"session_id": "mig", "image_b64": "AAAA"})
_code19, _body19 = stub19.session_export({"session_id": "mig"})
assert _code19 == 200 and _body19["snapshot"]["step_index"] == 1
stub19b = StubReplicaApp(replica_id=4)
_code19, _imp19 = stub19b.session_import(
    {"snapshot": _body19["snapshot"]})
assert _code19 == 200 and _imp19["step_index"] == 1
assert stub19b.metrics_snapshot()["migration_imports_total"] == 1
mig_text = ServeMetrics().prometheus_text(migration_imports_total=2)
assert "# TYPE rt1_serve_migration_imports_total counter" in mig_text
assert "rt1_serve_replica_migration_imports_total" in fleet_metric_names()
assert "rt1_serve_replica_migration_restores_total" in fleet_metric_names()

offenders = [m for m in sys.modules if m.split(".")[0] in BLOCKED]
assert not offenders, f"training deps leaked into the import: {offenders}"
print("OK")
"""


def test_obs_imports_without_training_deps():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE],
        capture_output=True,
        text=True,
        timeout=180,
        cwd=repo,
        env=env,
    )
    assert proc.returncode == 0, (
        f"rt1_tpu.obs has a hard training-stack dependency:\n"
        f"stdout: {proc.stdout}\nstderr: {proc.stderr}"
    )
    assert "OK" in proc.stdout


def test_the_trainer_imports_without_orbax():
    """Orbax (seconds to import) comes in when a checkpoint manager is made,
    not with ``rt1_tpu.train.train``: a benchmark run builds its step from
    ``build_family`` and ``make_train_step_fns`` and saves nothing."""
    probe = (
        "import sys\n"
        "from rt1_tpu.train.train import build_family\n"
        "from rt1_tpu.trainer import make_train_step_fns\n"
        "from rt1_tpu.trainer.checkpoints import CheckpointConfig, CheckpointManager\n"
        "assert not [m for m in sys.modules if m.split('.')[0] == 'orbax'], 'orbax imported'\n"
        "import tempfile\n"
        "CheckpointManager(CheckpointConfig(directory=tempfile.mkdtemp())).close()\n"
        "assert 'orbax.checkpoint' in sys.modules\n"
        "print('OK')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", probe], capture_output=True, text=True, timeout=180, cwd=repo,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode == 0 and "OK" in proc.stdout, proc.stderr[-2000:]

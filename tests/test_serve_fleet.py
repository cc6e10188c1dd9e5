"""Fleet layer tier-1: router affinity, replica kill + session re-home,
rolling reload — against real SUBPROCESS replicas, using the model-free
stub (`rt1_tpu/serve/stub.py`) so two replicas spawn in ~a second instead
of paying a jax import + AOT compile each. The stub speaks the exact
replica HTTP contract; the jax engine behind that contract is covered by
test_serve_engine/test_serve_server, and the full real-replica chaos run
is the slow-marked loadgen test at the bottom (the BENCH_serve_fleet.json
producer).
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from rt1_tpu.serve.fleet import FleetSupervisor
from rt1_tpu.serve.router import DEAD, READY, Router, make_router_server

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# The module fleet is mixed-dtype (replica 0 f32, replica 1 int8 — the
# ISSUE 9 cheap-replicas-beside-a-reference shape) so every aggregation
# test below doubles as proof the dtype gauge plumbing survives the
# router fan-out.
_STUB_DTYPES = ("f32", "int8")


def _stub_argv(replica_id: int):
    return [
        sys.executable, "-m", "rt1_tpu.serve.stub",
        "--port", "0",
        "--replica_id", str(replica_id),
        "--inference_dtype", _STUB_DTYPES[replica_id % len(_STUB_DTYPES)],
    ]


def _post(url, payload):
    req = urllib.request.Request(
        url,
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=15) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=15) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as exc:
        return exc.code, json.loads(exc.read())


def _act(url, session_id):
    return _post(
        url + "/act",
        {"session_id": session_id, "image_b64": "AAAA", "instruction": "x"},
    )


@pytest.fixture(scope="module")
def fleet():
    """Two supervised stub replicas behind a routed HTTP frontend. The
    kill test at the bottom of the file relies on the supervisor healing
    the fleet back to 2-ready before the module ends."""
    router = Router(replica_timeout_s=10.0)
    supervisor = FleetSupervisor(
        router,
        _stub_argv,
        2,
        poll_interval_s=0.1,
        chaos_interval_s=3600.0,  # no chaos unless a test asks
        warmup_timeout_s=60.0,
    )
    supervisor.start(wait_ready=True)
    httpd = make_router_server(router, host="127.0.0.1", port=0)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    url = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield router, supervisor, url
    httpd.shutdown()
    httpd.server_close()
    thread.join(timeout=5)
    supervisor.stop()


def test_fleet_ready_with_proxied_contract(fleet):
    router, _, url = fleet
    assert router.ready_count() == 2
    status, body = _get(url + "/readyz")
    assert status == 200 and body["ready"] is True
    status, health = _get(url + "/healthz")
    assert status == 200
    # The router proxies the serving contract from a ready replica, so
    # loadgen reads image_shape from the fleet exactly like from one node.
    assert health["image_shape"] == [8, 14, 3]
    assert health["replicas_total"] == 2
    status, fs = _get(url + "/fleet/status")
    assert status == 200
    assert [r["state"] for r in fs["replicas"]] == [READY, READY]
    assert all(
        r["metrics"]["compile_count"] == 1 for r in fs["replicas"]
    )
    # ISSUE 12 scheduling contract rides the stub fleet jax-free: the
    # compile-count invariant's denominator is probed per replica.
    assert all(
        r["metrics"]["bucket_count"] == 1 for r in fs["replicas"]
    )


def test_session_affinity_and_spread(fleet):
    _, _, url = fleet
    # One session's acts all land on one replica, stepping in order...
    homes = set()
    for expected_step in range(3):
        status, body = _act(url, "affine")
        assert status == 200
        assert body["step_index"] == expected_step
        homes.add(body["replica_id"])
    assert len(homes) == 1
    # ...while new sessions spread to the least-loaded replica.
    status, body = _act(url, "affine-2")
    assert status == 200
    assert body["replica_id"] != next(iter(homes))


def test_rolling_reload_hits_every_replica(fleet):
    router, _, url = fleet
    status, body = _post(url + "/reload", {"step": 11})
    assert status == 200, body
    assert body["ok"] is True
    assert [r["status"] for r in body["replicas"]] == [200, 200]
    assert all(r["checkpoint_step"] == 11 for r in body["replicas"])
    # Every replica hot-swapped exactly once and returned to ready.
    status, fs = _get(url + "/fleet/status")
    assert [r["metrics"]["reloads_total"] for r in fs["replicas"]] == [1, 1]
    assert router.ready_count() == 2
    # Traffic still flows after the roll.
    status, _ = _act(url, "post-reload")
    assert status == 200


def test_request_id_propagates_end_to_end(fleet):
    """ISSUE acceptance: ONE request id appears in the router's
    `router_route` span, the replica's `replica_act` span (read back via
    the stub's /trace introspection endpoint), and the response's phase
    breakdown — client-supplied header honored throughout."""
    from rt1_tpu.obs import trace as obs_trace

    router, _, url = fleet
    rid = "e2e-propagation-id"
    tracer = obs_trace.enable(max_events=256)
    try:
        req = urllib.request.Request(
            url + "/act",
            data=json.dumps(
                {
                    "session_id": "traced-sess",
                    "image_b64": "AAAA",
                    "instruction": "x",
                    "debug": True,
                }
            ).encode(),
            headers={
                "Content-Type": "application/json",
                "X-RT1-Request-Id": rid,
            },
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=15) as resp:
            body = json.loads(resp.read())
        # 1. The response: id echoed at top level AND inside the phase
        #    breakdown, with the stub's device step actually measured.
        assert body["request_id"] == rid
        assert body["phases"]["request_id"] == rid
        assert body["phases"]["device_ms"] is not None
        # 2. The router-side span (this process) carries the same id.
        events = tracer.to_dict()["traceEvents"]
        route_spans = [
            e for e in events
            if e.get("name") == "router_route"
            and e.get("args", {}).get("request_id") == rid
        ]
        assert len(route_spans) == 1
        assert route_spans[0]["args"]["session"] == "traced-sess"
    finally:
        obs_trace.disable()
    # 3. The replica-side spans (stub subprocess) carry it too: the
    #    header crossed the HTTP hop.
    replica = next(
        r for r in router.replicas()
        if r.id == body["replica_id"]
    )
    status, trace_body = _get(replica.url + "/trace")
    assert status == 200
    names = {
        e["name"]
        for e in trace_body["traceEvents"]
        if e.get("args", {}).get("request_id") == rid
        or rid in (e.get("args", {}).get("request_ids") or [])
    }
    assert "replica_act" in names
    assert "device_step" in names


def test_fleet_metrics_aggregation_json_and_prometheus(fleet):
    """One scrape target for the whole fleet: the router's /metrics
    carries every live replica's snapshot under `replicas` (JSON) and as
    `rt1_serve_replica_*{replica_id="N"}` labeled families (text), plus
    the SLO ledger's gauges in both formats."""
    router, _, url = fleet
    status, body = _get(url + "/metrics")
    assert status == 200
    # JSON: both replicas present with their full per-replica view.
    assert set(body["replicas"].keys()) == {"0", "1"}
    for rid, snap in body["replicas"].items():
        assert snap is not None, f"replica {rid} probe failed"
        assert snap["compile_count"] == 1
        assert snap["replica_id"] == int(rid)
        assert "requests_total" in snap and "queue_depth" in snap
    # SLO gauges ride the same scrape.
    assert body["slo_requests_total"] > 0
    assert 0.0 <= body["slo_availability"] <= 1.0
    assert body["slo_objective_availability"] == 0.99

    req = urllib.request.Request(
        url + "/metrics", headers={"Accept": "text/plain"}
    )
    with urllib.request.urlopen(req, timeout=15) as resp:
        assert resp.headers["Content-Type"].startswith("text/plain")
        text = resp.read().decode("utf-8")
    # Per-replica labeled families, one sample per live replica.
    for rid in ("0", "1"):
        assert f'rt1_serve_replica_up{{replica_id="{rid}"}} 1' in text
        assert (
            f'rt1_serve_replica_compile_count{{replica_id="{rid}"}} 1'
            in text
        )
        assert f'rt1_serve_replica_requests_total{{replica_id="{rid}"}}' in text
    assert "# TYPE rt1_serve_replica_up gauge" in text
    assert "# TYPE rt1_serve_replica_requests_total counter" in text
    # SLO families render under the serve prefix.
    assert "rt1_serve_slo_availability" in text
    assert "rt1_serve_slo_error_budget_burn" in text


def test_mixed_dtype_fleet_advertises_per_replica_dtype(fleet):
    """ISSUE 9 mixed-dtype fleet plumbing: one replica serving int8 beside
    an f32 reference is visible end to end — replica ready-line and
    /healthz, the router's /fleet/status curated metrics, the aggregated
    JSON snapshots, and the Prometheus info-style labeled family — with
    the param-bytes evidence gauges riding along."""
    router, _, url = fleet
    status, fs = _get(url + "/fleet/status")
    assert status == 200
    by_id = {r["id"]: r for r in fs["replicas"]}
    assert by_id[0]["metrics"]["inference_dtype"] == "f32"
    assert by_id[1]["metrics"]["inference_dtype"] == "int8"
    assert all(
        r["metrics"]["param_bytes_device"] > 0 for r in fs["replicas"]
    )

    status, body = _get(url + "/metrics")
    assert status == 200
    assert body["replicas"]["0"]["inference_dtype"] == "f32"
    assert body["replicas"]["1"]["inference_dtype"] == "int8"
    for rid, snap in body["replicas"].items():
        # The stub's deterministic stand-in bytes prove the gauge path.
        assert snap["param_bytes_device"] == 1000 + int(rid)
        assert snap["param_bytes_master"] == 4000

    req = urllib.request.Request(
        url + "/metrics", headers={"Accept": "text/plain"}
    )
    with urllib.request.urlopen(req, timeout=15) as resp:
        text = resp.read().decode("utf-8")
    assert (
        'rt1_serve_replica_inference_dtype{replica_id="0",dtype="f32"} 1'
        in text
    )
    assert (
        'rt1_serve_replica_inference_dtype{replica_id="1",dtype="int8"} 1'
        in text
    )
    assert 'rt1_serve_replica_param_bytes_device{replica_id="1"} 1001' in text
    assert 'rt1_serve_replica_param_bytes_master{replica_id="0"} 4000' in text


def test_replica_dtype_assignment_for_fleet_argv():
    """`--replica_dtypes` cycles per replica id and beats the fleet-wide
    `--inference_dtype`; both land in the spawned replica argv."""
    import argparse

    from rt1_tpu.serve.fleet import replica_argv_builder, replica_dtype_for

    args = argparse.Namespace(
        stub=True, max_sessions=8, stub_act_delay_s=0.0,
        slow_threshold_ms=0.0, inference_dtype="bf16",
        replica_dtypes="f32,int8",
    )
    assert replica_dtype_for(args, 0) == "f32"
    assert replica_dtype_for(args, 1) == "int8"
    assert replica_dtype_for(args, 2) == "f32"  # cycled
    argv = replica_argv_builder(args)(1)
    assert argv[argv.index("--inference_dtype") + 1] == "int8"
    # Without the per-replica list, the fleet-wide mode applies everywhere.
    args.replica_dtypes = ""
    assert replica_dtype_for(args, 5) == "bf16"


def test_slo_endpoint_and_fleet_slow_requests(fleet):
    """GET /slo returns the ledger's full judgement; GET
    /fleet/slow_requests fans the exemplar rings out of every replica."""
    _, _, url = fleet
    status, slo = _get(url + "/slo")
    assert status == 200
    assert slo["requests_total"] > 0
    assert set(slo["by_class"]) == {
        "ok", "migrated", "restarted", "rejected", "failed",
    }
    assert "error_budget_burn" in slo
    status, body = _get(url + "/fleet/slow_requests")
    assert status == 200
    assert set(body["replicas"].keys()) == {"0", "1"}
    # The traced request from the propagation test is on file in some
    # replica's ring, phase breakdown included.
    all_ids = {
        rec["request_id"]
        for scrape in body["replicas"].values()
        if scrape
        for rec in scrape.get("slow_requests", [])
    }
    assert "e2e-propagation-id" in all_ids


def test_replica_kill_rehomes_sessions_with_restarted_flag(fleet):
    """The headline semantics: SIGKILL a replica mid-conversation; every
    session homed there re-homes to the live replica on its next /act —
    a 200 carrying restarted: true and a fresh window, never a 5xx — and
    the supervisor respawns the dead replica behind warm-up gating."""
    router, supervisor, url = fleet
    # Home two sessions and advance them a few steps.
    victims = {}
    for sid in ("kill-a", "kill-b", "kill-c", "kill-d"):
        for _ in range(3):
            status, body = _act(url, sid)
            assert status == 200
        victims[sid] = body["replica_id"]
    target_id = victims["kill-a"]
    on_target = [s for s, r in victims.items() if r == target_id]
    assert on_target  # at least kill-a
    target = next(r for r in router.replicas() if r.id == target_id)
    restarts_before = target.restarts

    target.proc.kill()
    target.proc.wait(timeout=10)

    # Sessions on the dead replica: next act is a re-homed 200 with the
    # restart surfaced; their windows restart from step 0.
    for sid in on_target:
        status, body = _act(url, sid)
        assert status == 200, body
        assert body["restarted"] is True
        # (No assertion on WHICH replica serves the re-home: if the
        # supervisor respawns the dead slot fast enough it is a legal —
        # least-loaded — placement target again.)
        assert body["step_index"] == 0
        assert body["session_started"] is True
    # Sessions elsewhere never noticed.
    unaffected = [s for s, r in victims.items() if r != target_id]
    for sid in unaffected:
        status, body = _act(url, sid)
        assert status == 200
        assert "restarted" not in body
        assert body["step_index"] == 3
    snapshot = router.metrics_snapshot()
    assert snapshot["sessions_restarted_total"] == len(on_target)
    # SLO ledger: each failover landed in the `restarted` bucket — an
    # answered request that burned error budget, not an outage — and the
    # burn is now visibly nonzero while availability stays high.
    gauges = router.slo.gauges()
    assert gauges["slo_requests_restarted"] == float(len(on_target))
    assert gauges["slo_requests_failed"] == 0.0
    assert gauges["slo_error_budget_burn"] > 0.0
    assert gauges["slo_availability"] < 1.0

    # The supervisor respawns the replica (fresh process, warm-up gated)
    # and the fleet heals back to 2-ready.
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and router.ready_count() < 2:
        time.sleep(0.1)
    assert router.ready_count() == 2
    assert target.restarts == restarts_before + 1
    assert target.state == READY and target.state != DEAD


@pytest.mark.slow
def test_fleet_chaos_loadgen_real_replicas(tmp_path):
    """The acceptance run, end to end with REAL jax replicas: loadgen
    spawns `python -m rt1_tpu.serve.fleet` on the tiny config, injects
    replica_kill + serve_reload from the deterministic fault plan, and
    the run must finish with zero failed requests and one AOT compile per
    replica lifetime. (Slow: two jax subprocess boots + AOT compiles.)"""
    output = tmp_path / "bench_fleet.json"
    cmd = [
        sys.executable,
        os.path.join(REPO, "scripts", "serve_loadgen.py"),
        "--fleet", "2",
        "--config", os.path.join(REPO, "rt1_tpu/train/configs/tiny.py"),
        "--random_init",
        "--sessions", "4",
        "--duration", "16",
        "--think_time", "0.02",
        "--chaos_interval_s", "4.0",
        "--replica_timeout_s", "10.0",
        "--faults", "replica_kill@1,serve_reload@2",
        "--log_dir", str(tmp_path / "logs"),
        "--output", str(output),
    ]
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        cmd, capture_output=True, text=True, timeout=900, cwd=REPO, env=env
    )
    assert proc.returncode == 0, (
        f"stdout: {proc.stdout}\nstderr: {proc.stderr[-3000:]}"
    )
    result = json.loads(output.read_text())
    assert result["requests_failed"] == 0
    assert result["requests_ok"] > 0
    assert result["chaos"]["kills_injected"] == 1
    assert result["chaos"]["reloads_injected"] == 1
    assert result["replica_restarts_total"] == 1
    # The pinned-compile invariant, kill + respawn included: every
    # replica compiled exactly once per AOT batch-size bucket (the
    # default --buckets auto ladder), never more.
    assert result["replica_compile_counts"], result
    assert all(
        c == b and b >= 1
        for c, b in zip(
            result["replica_compile_counts"],
            result["replica_bucket_counts"],
        )
    ), result
    # SLO ledger rides the BENCH record: the kill+reload scenario burns
    # nonzero error budget (the restarted requests) while availability
    # stays above the objective — degraded, within contract.
    slo = result["slo"]
    assert slo["by_class"]["restarted"]["count"] >= 1
    assert slo["by_class"]["failed"]["count"] == 0
    assert slo["error_budget_burn"] > 0.0
    assert slo["availability"] >= slo["objectives"]["availability"]
    assert slo["availability_within_objective"] is True
    # The router kept its own (server-side) ledger; it saw the same
    # restarted requests.
    assert result["server_slo"]["by_class"]["restarted"]["count"] >= 1
    # slo_summary.json artifact written next to --output for run_report.
    summary_path = output.parent / "slo_summary.json"
    assert summary_path.exists()
    assert json.loads(summary_path.read_text()) == slo


def test_replica_that_cannot_boot_is_named_by_the_fleet():
    """A replica that gives up before serving (on a TPU host: the chip is
    held by its sibling — one process per chip) prints a
    `{"status": "failed"}` line; the supervisor's warm-up error carries
    that reason instead of a bare exit code."""

    def _failing_argv(replica_id):
        return [
            sys.executable, "-c",
            "import json; print(json.dumps({'status': 'failed', 'error': "
            "'cannot initialize the accelerator backend: chip held'}), "
            "flush=True); raise SystemExit(1)",
        ]

    supervisor = FleetSupervisor(
        Router(replica_timeout_s=10.0), _failing_argv, 1,
        poll_interval_s=0.1, chaos_interval_s=3600.0, warmup_timeout_s=30.0,
    )
    with pytest.raises(RuntimeError, match="exited rc=1 .*chip held"):
        supervisor.start(wait_ready=True)

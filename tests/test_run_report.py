"""scripts/run_report.py: post-mortem rendering pinned on canned artifacts.

A golden-ish contract: given a known goodput summary and flight-recorder
dump, the report's load-bearing lines (bucket rows, badput narrative,
flight tail, health gauges) must come out exactly — an operator reads
this under pressure, so format drift is a regression, not cosmetics.
"""

import json
import os
import sys

import pytest

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.dirname(__file__)), "scripts")
)
import run_report  # noqa: E402

from rt1_tpu.obs import startup  # noqa: E402
from rt1_tpu.obs.goodput import GoodputLedger  # noqa: E402
from rt1_tpu.obs.recorder import FlightRecorder  # noqa: E402


def _canned_workdir(tmp_path):
    """A workdir as a preempted, once-rolled-back run would leave it."""
    wd = tmp_path / "run"
    wd.mkdir()

    clock = {"t": 0.0}

    def fake_clock():
        return clock["t"]

    # The start-up log of that run: the first step traced and compiled
    # for all of its 20 s.
    log = startup.StartupLog(clock=fake_clock)
    led = GoodputLedger(clock=fake_clock, compile_seconds=log.compile_seconds)
    with led.phase("init"):
        with log.phase("build_model"):
            clock["t"] += 8.0
        led.note_io("ckpt_restore", 2.0)
    clock["t"] += 20.0
    log.mark_role("train_step", "train_step_guarded")
    log._on_start(startup.TRACE, 0.0, fun_name="train_step_guarded")
    log._on_span(startup.TRACE, 0.0, 5.0, fun_name="train_step_guarded")
    log._on_start(startup.BACKEND, 5.0, fun_name="jit(train_step_guarded)")
    log._on_span(startup.BACKEND, 5.0, 20.0, fun_name="jit(train_step_guarded)")
    led.note_step({"total_ms": 20_000.0, "compile_ms": 20_000.0})
    for _ in range(10):
        clock["t"] += 1.0
        led.note_step(
            {"total_ms": 1000.0, "wait_data_ms": 200.0, "h2d_ms": 50.0}
        )
    led.mark_rollback()
    for _ in range(4):
        clock["t"] += 1.0
        led.note_step({"total_ms": 1000.0}, replay=True)
    led.note_io("ckpt_save", 3.0)
    clock["t"] += 3.0
    led.mark_preempted()
    with led.phase("preempt_drain"):
        clock["t"] += 2.0
    led.set_flops_per_step(1.5e9, peak_flops=197e12, n_chips=1)
    led.write_summary(str(wd / "goodput_summary.json"), startup=log.snapshot())

    rec = FlightRecorder(capacity=8, path=str(wd / "flight_record.jsonl"))
    for step in range(30, 42):
        rec.record(
            step,
            total_ms=31.25,
            stall_pct=12.5,
            **({"loss": 2.5 - step * 0.01} if step % 2 == 0 else {}),
        )
    rec.record(
        42,
        total_ms=31.25,
        stall_pct=12.5,
        loss=2.08,
        health={"health/logit_entropy": 2.4587, "health/token_acc/dim0": 0.25},
        guard={"guard/device_skips_total": 1.0, "guard/rollbacks_total": 1.0},
    )
    rec.dump(reason="preempt")
    return str(wd)


def test_report_golden_sections(tmp_path):
    wd = _canned_workdir(tmp_path)
    goodput = run_report.load_goodput(wd)
    flight = run_report.load_flight(wd)
    report = run_report.render_report(wd, goodput, flight, tb=None, tail=4)
    lines = report.splitlines()

    # Goodput table rows: fixed-width bucket lines with shares.
    # Wall: 8 init + 20 compile + 10 productive + 4 replay + 3 between-
    # steps save + 2 drain = 47 s, every second attributed.
    assert "Wall time: 47.0 s" in report
    row = next(ln for ln in lines if ln.startswith("init"))
    assert row.startswith("init                  6.00   12.8%")
    assert "model/dataset/state setup" in row
    row = next(ln for ln in lines if ln.startswith("rollback_replay"))
    assert "4.00" in row and "steps re-run after guard rollback" in row
    assert any(
        ln.startswith("step") and "GOODPUT" in ln for ln in lines
    )
    # Narrative: goodput%, MFU, events.
    assert "Goodput 16.0% / badput 84.0% of wall time." in report
    assert "MFU" in report and "1.5e+09 FLOPs/step" in report
    assert "1 rollback(s), 4 step(s) replayed" in report
    assert "PREEMPTED" in report
    # The start-up log's block rides in the summary and is rendered with it.
    row = next(ln for ln in lines if ln.startswith("compile"))
    assert row.startswith("compile              20.00   42.6%")
    assert "  setup/build_model: 8.000 s, self 8.000 s" in lines
    assert any(ln.startswith("  train_step (train_step_guarded): trace 5.000 s")
               and "compile 15.000 s" in ln for ln in lines)

    # Flight tail: capacity 8 with 13 records -> 8 retained, tail of 4.
    assert "Dump reason: preempt — 8 of 13 recorded steps retained." in report
    assert "      42      31.2    12.5        2.08" in report
    assert "      39      31.2    12.5           -" in report
    # Health gauges embedded in the final record surface in the report.
    assert "health/logit_entropy" in report and "2.4587" in report
    assert "Guard at the end: 1 device skips, 1 rollbacks." in report

    # TB-less degradation is a note, not a crash.
    assert "No TensorBoard events readable" in report


def test_report_all_sources_missing(tmp_path):
    wd = str(tmp_path / "empty")
    os.makedirs(wd)
    report = run_report.render_report(
        wd,
        run_report.load_goodput(wd),
        run_report.load_flight(wd),
        run_report.load_tb_scalars(wd),
    )
    assert "goodput_summary.json not found" in report
    assert "flight_record.jsonl not found" in report


def test_main_writes_out_file(tmp_path, capsys):
    wd = _canned_workdir(tmp_path)
    out = str(tmp_path / "report.md")
    run_report.main(["--workdir", wd, "--out", out])
    with open(out) as f:
        text = f.read()
    assert text.startswith(f"# RT-1 run report — {wd}")
    # stdout stays clean when --out is given (stderr gets the note).
    assert "Where the hours went" not in capsys.readouterr().out


def test_goodput_fractions_always_renderable(tmp_path):
    """A summary whose fractions were hand-edited out of range must not
    crash the bar renderer (clamped, not asserted)."""
    wd = tmp_path / "run"
    wd.mkdir()
    summary = {
        "wall_s": 10.0,
        "buckets_s": {b: 0.0 for b in run_report._BUCKET_NOTES},
        "fractions": {b: 0.0 for b in run_report._BUCKET_NOTES},
        "goodput_pct": 0.0,
        "badput_pct": 100.0,
        "steps_productive": 0,
        "steps_replayed": 0,
        "rollbacks": 0,
        "preempted": False,
    }
    summary["fractions"]["step"] = 1.7  # corrupt
    with open(wd / "goodput_summary.json", "w") as f:
        json.dump(summary, f)
    report = run_report.render_report(
        str(wd), run_report.load_goodput(str(wd)), None, None
    )
    assert "170.0%" in report  # reported honestly, bar clamped


def test_bar_rendering_bounds():
    assert run_report._bar(0.0) == "." * 30
    assert run_report._bar(100.0) == "#" * 30
    assert run_report._bar(250.0) == "#" * 30
    assert len(run_report._bar(33.3)) == 30


def _canned_serve_workdir(tmp_path):
    """A workdir as a fleet-3 chaos loadgen run leaves it: SLO summary,
    BENCH record, and a slow-request exemplar dump."""
    from rt1_tpu.obs.recorder import ExemplarRing
    from rt1_tpu.obs.slo import SLOLedger, SLOObjectives

    wd = tmp_path / "serve-run"
    wd.mkdir()
    ledger = SLOLedger(SLOObjectives(availability=0.99))
    for _ in range(996):
        ledger.observe("ok", 0.012)
    ledger.observe("restarted", 0.150)
    ledger.observe("restarted", 0.200)
    ledger.observe("rejected", 0.001)
    ledger.observe("failed", 0.0)
    ledger.write_summary(str(wd / "slo_summary.json"))

    bench = {
        "metric": "serve_requests_per_sec",
        "value": 93.5,
        "unit": "req/s",
        "requests_ok": 996,
        "requests_restarted": 2,
        "requests_rejected": 1,
        "requests_failed": 1,
        "fleet_replicas": 3,
        "faults": "replica_kill@1,serve_reload@2",
        "replica_restarts_total": 1,
        "replica_compile_counts": [1, 1, 1],
        "replicas_ready_at_end": 3,
    }
    with open(wd / "BENCH_serve_fleet.json", "w") as f:
        json.dump(bench, f)

    ring = ExemplarRing(capacity=8, threshold_ms=50.0)
    ring.offer(
        151.2,
        request_id="slowest-one",
        session="s3",
        outcome="restarted",
        phases={"queue_wait_ms": 80.0, "device_ms": 60.0},
    )
    ring.offer(
        72.0,
        request_id="also-slow",
        session="s1",
        outcome="ok",
        phases={"queue_wait_ms": 40.0, "device_ms": 30.0},
    )
    ring.dump(str(wd / "slow_requests.jsonl"), reason="supervisor_scrape")
    return str(wd)


def test_serve_postmortem_section(tmp_path):
    """The serve post-mortem: SLO verdict + outcome table + fleet/chaos
    evidence + slowest exemplars, merged from the serving artifacts."""
    wd = _canned_serve_workdir(tmp_path)
    serve = run_report.load_serve(wd)
    assert serve is not None
    report = run_report.render_report(wd, None, None, None, serve=serve)

    assert "## Serve post-mortem (SLO ledger)" in report
    # Verdict numbers: 996/1000 ok -> 99.6% availability vs 99% objective
    # -> 40% of the error budget burned; SLO met.
    assert "Availability 99.600%" in report
    assert "error budget burned 40.0%" in report
    assert "Objectives: availability >= 0.99" in report
    assert "SLO met." in report
    # Outcome table rows with per-class budget burn.
    lines = report.splitlines()
    ok_row = next(ln for ln in lines if ln.startswith("ok "))
    assert "996" in ok_row
    restarted_row = next(ln for ln in lines if ln.startswith("restarted"))
    assert "2" in restarted_row and "20.0%" in restarted_row
    # Fleet/chaos evidence from the BENCH record.
    assert "Loadgen: 93.5 req/s — 996 ok, 2 restarted, 1 rejected," in report
    assert "Fleet: 3 replicas" in report
    assert "replica_kill@1,serve_reload@2" in report
    assert "compile counts [1, 1, 1]" in report
    # Exemplars: slowest first, with phase columns.
    assert "Slow-request exemplars: 2 retained" in report
    assert "(threshold 50.0 ms" in report
    slowest = next(ln for ln in lines if ln.startswith("slowest-one"))
    also = next(ln for ln in lines if ln.startswith("also-slow"))
    assert lines.index(slowest) < lines.index(also)
    assert "151.20" in slowest and "80.00" in slowest and "60.00" in slowest
    assert slowest.rstrip().endswith("restarted")


def test_serve_quant_bench_renders_dtype_table(tmp_path):
    """ISSUE 9 satellite: BENCH_serve_quant.json folds into the serve
    post-mortem as a per-dtype latency/parity/bytes table next to the SLO
    verdict, honesty note included."""
    wd = _canned_serve_workdir(tmp_path)
    quant = {
        "metric": "serve_param_bytes_reduction_int8",
        "value": 3.71,
        "unit": "x",
        "per_dtype": {
            "f32": {
                "req_per_sec": 100.2, "latency_p50_ms": 66.1,
                "latency_p99_ms": 219.9, "requests_failed": 0,
                "param_bytes_device": 50528,
                "parity": {"agreement": 1.0},
            },
            "int8": {
                "req_per_sec": 150.6, "latency_p50_ms": 48.1,
                "latency_p99_ms": 92.3, "requests_failed": 0,
                "param_bytes_device": 29208,
                "parity": {"agreement": 0.997},
            },
        },
        "honesty_note": "XLA:CPU lacks native int8 matmul",
    }
    with open(os.path.join(wd, "BENCH_serve_quant.json"), "w") as f:
        json.dump(quant, f)
    serve = run_report.load_serve(wd)
    assert serve["quant_bench"]["value"] == 3.71
    report = run_report.render_report(wd, None, None, None, serve=serve)
    assert "int8 param-byte reduction 3.71x" in report
    lines = report.splitlines()
    f32_row = next(ln for ln in lines if ln.startswith("f32 "))
    int8_row = next(ln for ln in lines if ln.startswith("int8 "))
    assert "66.10" in f32_row and "100.0%" in f32_row
    assert "48.10" in int8_row and "99.7%" in int8_row
    assert "0.029" in int8_row  # device MB column
    assert "Note: XLA:CPU lacks native int8 matmul" in report
    # The SLO verdict still leads the section — the dtype table rides it.
    assert report.index("SLO met.") < report.index("int8 param-byte")


def test_serve_elastic_bench_renders_timeline_and_cost(tmp_path):
    """ISSUE 15: a BENCH_serve_elastic.json in the workdir renders as the
    per-phase A/B table, the scale-event timeline, and the cost-per-
    request comparison (with the p99-envelope verdict); a workdir without
    one keeps its report elastic-free."""
    wd = _canned_serve_workdir(tmp_path)
    elastic = {
        "metric": "serve_elastic_cost_ratio_fixed_over_elastic",
        "value": 2.004,
        "unit": "x",
        "headline_schedule": "diurnal",
        "schedules": ["diurnal"],
        "min_replicas": 1,
        "max_replicas": 3,
        "surge_dtype": "int8",
        "requests_failed": 0,
        "p99_peak_phase": {
            "diurnal": {
                "elastic_ms": 43.2,
                "fixed_max_ms": 46.0,
                "envelope_factor": 1.5,
                "within_envelope": True,
            }
        },
        "cost_per_request": {
            "diurnal": {"elastic": 0.010016, "fixed_max": 0.02007}
        },
        "sides": {
            "elastic": {
                "diurnal": {
                    "phases": [
                        {
                            "phase": "night", "clients": 2,
                            "req_per_sec": 67.5, "latency_p50_ms": 15.8,
                            "latency_p99_ms": 28.4, "requests_rejected": 0,
                            "requests_failed": 0, "replicas_after": 1,
                        },
                        {
                            "phase": "midday", "clients": 10,
                            "req_per_sec": 255.7, "latency_p50_ms": 26.4,
                            "latency_p99_ms": 43.2, "requests_rejected": 3,
                            "requests_failed": 0, "replicas_after": 3,
                        },
                    ],
                    "scale_events": [
                        {
                            "t_s": 4.6, "direction": "up",
                            "replica_id": 1, "dtype": "int8",
                            "reason": "occupancy 1.75 >= 0.75",
                        },
                        {
                            "t_s": 18.4, "direction": "down",
                            "replica_id": 1, "dtype": "int8",
                            "reason": "occupancy 0.17 <= 0.30 for 4 ticks",
                        },
                    ],
                    "replica_seconds_by_dtype": {
                        "f32": 18.9, "int8": 27.3
                    },
                }
            },
            "fixed_max": {
                "diurnal": {
                    "phases": [
                        {
                            "phase": "night", "clients": 2,
                            "req_per_sec": 74.7, "latency_p50_ms": 14.6,
                            "latency_p99_ms": 20.5, "requests_rejected": 0,
                            "requests_failed": 0, "replicas_after": 3,
                        },
                    ],
                    "replica_seconds_by_dtype": {"f32": 57.1},
                }
            },
        },
    }
    with open(os.path.join(wd, "BENCH_serve_elastic.json"), "w") as f:
        json.dump(elastic, f)
    serve = run_report.load_serve(wd)
    assert serve["elastic_bench"]["value"] == 2.004
    report = run_report.render_report(wd, None, None, None, serve=serve)
    assert (
        "cost-per-request ratio fixed-max/elastic 2.004x on the diurnal "
        "schedule" in report
    )
    assert "1..3 replicas, surge dtype int8, 0 failed requests" in report
    lines = report.splitlines()
    # Per-phase rows for both sides, replicas column included.
    midday = next(
        ln for ln in lines if "elastic" in ln and "midday" in ln
    )
    assert "255.7" in midday and midday.rstrip().endswith("3")
    night_fixed = next(
        ln for ln in lines if "fixed_max" in ln and "night" in ln
    )
    assert night_fixed.rstrip().endswith("3")
    # The scale-event timeline, up and down, with dtype + reason.
    assert (
        "t=    4.6s up    replica 1 (int8): occupancy 1.75 >= 0.75"
        in report
    )
    assert "t=   18.4s down  replica 1 (int8)" in report
    # Cost + envelope verdicts.
    assert (
        "Cost/request (byte-weighted replica-seconds): elastic 0.010016 "
        "vs fixed-max 0.02007" in report
    )
    assert (
        "Peak-phase p99: elastic 43.2 ms vs fixed-max 46.0 ms — within "
        "the 1.5x envelope." in report
    )
    # A workdir without the record keeps its report elastic-free.
    bare = run_report.render_report(
        wd, None, None, None,
        serve={"slo": serve["slo"]},
    )
    assert "Elastic fleet" not in bare


def test_serve_migration_bench_renders_event_table(tmp_path):
    """ISSUE 19: a BENCH_serve_migration.json in the workdir renders as
    the per-event durable-vs-legacy outcome table with the window-reset
    verdict and migration counters; a workdir without one keeps its
    report migration-free."""
    wd = _canned_serve_workdir(tmp_path)
    migration = {
        "metric": "serve_migration_window_resets",
        "value": 0,
        "unit": "resets",
        "fleet_replicas": 3,
        "events": ["kill", "drain", "rolling_reload", "rebalance"],
        "zero_window_resets": True,
        "legacy_window_resets": 3,
        "token_identical_continuations": True,
        "requests_failed": 0,
        "compile_pinned_at_bucket_count": True,
        "sides": {
            "durable": {
                "durable": True,
                "events": [
                    {"event": "warmup", "ok": 8, "migrated": 0,
                     "restarted": 0, "rejected": 0, "failed": 0,
                     "window_resets": 0, "continuity_ok": 8},
                    {"event": "kill", "ok": 5, "migrated": 3,
                     "restarted": 0, "rejected": 0, "failed": 0,
                     "window_resets": 0, "continuity_ok": 8},
                    {"event": "drain", "ok": 4, "migrated": 4,
                     "restarted": 0, "rejected": 0, "failed": 0,
                     "window_resets": 0, "continuity_ok": 8},
                    {"event": "rolling_reload", "ok": 4, "migrated": 4,
                     "restarted": 0, "rejected": 0, "failed": 0,
                     "window_resets": 0, "continuity_ok": 8},
                    {"event": "rebalance", "ok": 6, "migrated": 2,
                     "restarted": 0, "rejected": 0, "failed": 0,
                     "window_resets": 0, "continuity_ok": 8},
                ],
                "migration_counters": {
                    "migration_exports_total": 6,
                    "migration_imports_total": 10,
                    "migration_import_failures_total": 0,
                    "migration_restores_total": 1,
                    "migration_restore_failures_total": 0,
                },
            },
            "legacy": {
                "durable": False,
                "events": [
                    {"event": "kill", "ok": 5, "migrated": 0,
                     "restarted": 3, "rejected": 0, "failed": 0,
                     "window_resets": 3, "continuity_ok": 5},
                ],
                "migration_counters": {
                    "migration_exports_total": 6,
                    "migration_imports_total": 10,
                    "migration_import_failures_total": 0,
                    "migration_restores_total": 0,
                    "migration_restore_failures_total": 0,
                },
            },
        },
    }
    with open(os.path.join(wd, "BENCH_serve_migration.json"), "w") as f:
        json.dump(migration, f)
    serve = run_report.load_serve(wd)
    assert serve["migration_bench"]["value"] == 0
    report = run_report.render_report(wd, None, None, None, serve=serve)
    assert (
        "0 window reset(s) on the durable side vs 3 legacy" in report
    )
    assert "kill/drain/rolling_reload/rebalance gauntlet" in report
    assert "Continuations token-identical: yes" in report
    assert "compile pinned at bucket count: yes" in report
    lines = report.splitlines()
    # Per-event rows for both sides — the warmup row stays out of the
    # table (it is load, not a disruption).
    durable_kill = next(
        ln for ln in lines if "[durable]" in ln or (
            ln.strip().startswith("kill") and "3" in ln
        )
    )
    assert durable_kill is not None
    kill_rows = [ln for ln in lines if ln.strip().startswith("kill ")]
    assert len(kill_rows) == 2  # one per side
    assert not any("warmup" in ln for ln in lines)
    assert "ring restores 1 (0 failed)." in report
    assert "ring restores 0 (0 failed)." in report
    # A workdir without the record keeps its report migration-free.
    bare = run_report.render_report(
        wd, None, None, None, serve={"slo": serve["slo"]}
    )
    assert "Durable sessions" not in bare


def test_eval_matrix_section_renders_table(tmp_path):
    """ISSUE 13: a BENCH_eval_matrix.json in the workdir renders as a
    task × checkpoint success table (plus the oracle-fill note); a
    workdir without one keeps its report matrix-free."""
    wd = tmp_path / "run"
    wd.mkdir()
    record = {
        "bench": "eval_matrix",
        "unit": "mean_cell_success_rate",
        "value": 0.45,
        "tasks": ["block2block", "block1_to_corner"],
        "checkpoints": ["1950", "3900"],
        "episodes_per_cell": 5,
        "max_episode_steps": 80,
        "backend": "kinematic",
        "matrix": {
            "block2block": {
                "1950": {"successes": 2, "episodes": 5,
                         "success_rate": 0.4, "mean_episode_length": 61.0},
                "3900": {"successes": 4, "episodes": 5,
                         "success_rate": 0.8, "mean_episode_length": 48.0},
            },
            "block1_to_corner": {
                "1950": {"successes": 0, "episodes": 5,
                         "success_rate": 0.0, "mean_episode_length": 80.0},
                # 3900 cell absent: renders as '-', not a crash.
            },
        },
        "oracle_fill": {
            "episodes_appended": 8,
            "episodes_per_task": {"block1_to_corner": 8},
            "shards_after": 2,
            "freshness_epoch": 1,
        },
    }
    with open(wd / "BENCH_eval_matrix.json", "w") as f:
        json.dump(record, f)

    loaded = run_report.load_eval_matrix(str(wd))
    assert loaded is not None
    report = run_report.render_report(
        str(wd), None, None, None, eval_matrix=loaded
    )
    assert "Eval matrix (task × checkpoint success)" in report
    assert "2 task(s) × 2 checkpoint(s)" in report
    assert "mean cell success 0.450" in report
    assert "ckpt 1950" in report and "ckpt 3900" in report
    assert "4/5 (0.80)" in report
    assert "0/5 (0.00)" in report
    # The missing cell renders as '-'.
    corner_row = next(
        line for line in report.splitlines()
        if line.startswith("block1_to_corner")
    )
    assert corner_row.rstrip().endswith("-")
    assert "Oracle corpus fill: 8 episodes appended" in report
    # Absent record -> no matrix section at all.
    plain = run_report.render_report(str(wd), None, None, None)
    assert "Eval matrix" not in plain
    # A half-written record degrades to None, not a crash.
    with open(wd / "BENCH_eval_matrix.json", "w") as f:
        f.write('{"bench": "eval_ma')
    assert run_report.load_eval_matrix(str(wd)) is None


def _canned_multichip(wd):
    record = {
        "bench": "multihost_scaling",
        "groups": {
            "1proc": {
                "processes": 1, "devices_global": 2, "global_batch": 4,
                "mesh": {"data": 2, "fsdp": 1, "model": 1},
                "steps_per_sec": 240.8, "examples_per_sec": 963.2,
                "mfu_pct": 0.000127, "per_host_data_stall_pct": [1.7],
            },
            "2proc": {
                "processes": 2, "devices_global": 4, "global_batch": 8,
                "mesh": {"data": 2, "fsdp": 2, "model": 1},
                "steps_per_sec": 4.6, "examples_per_sec": 36.8,
                "mfu_pct": 2.4e-06,
                "per_host_data_stall_pct": [0.1, 0.2],
            },
        },
        "scaling": {
            "steps_per_sec_ratio_2p_over_1p": 0.019,
            "examples_per_sec_ratio_2p_over_1p": 0.038,
        },
        "methodology": {"caveats": "XLA:CPU gloo-over-loopback lower bound"},
    }
    with open(os.path.join(wd, "MULTICHIP_r06.json"), "w") as f:
        json.dump(record, f)
    return record


def test_multichip_section_renders_beside_goodput(tmp_path):
    """ISSUE 14 satellite: the MULTICHIP scale-out record renders right
    after the goodput section — per-topology steps/s + MFU + per-host
    data-stall, the weak-scaling ratio, and the record's own caveats."""
    wd = _canned_workdir(tmp_path)
    _canned_multichip(wd)
    record = run_report.load_multichip(wd)
    assert record is not None
    report = run_report.render_report(
        wd,
        run_report.load_goodput(wd),
        run_report.load_flight(wd),
        None,
        multichip=record,
    )
    assert "## Multi-host scaling (MULTICHIP record)" in report
    # Beside the goodput section: goodput first, scaling right after.
    assert report.index("Where the hours went") < report.index(
        "Multi-host scaling"
    ) < report.index("Flight recorder")
    assert "1proc" in report and "2proc" in report
    lines = report.splitlines()
    row = next(l for l in lines if l.startswith("2proc"))
    assert "4.60" in row  # steps/s
    assert "[0.1, 0.2]" in row  # per-host data-stall
    assert "examples/s x0.038" in report
    assert "gloo-over-loopback lower bound" in report


def test_multichip_loader_ignores_foreign_records(tmp_path):
    """Pre-ISSUE-14 MULTICHIP rounds (dryrun leg matrices) have no
    throughput table — the loader returns None instead of rendering a
    broken section; so do torn/invalid files."""
    wd = tmp_path / "run"
    wd.mkdir()
    with open(wd / "MULTICHIP_r05.json", "w") as f:
        json.dump({"dryrun_multichip": 8, "legs": {"pp": "ok"}}, f)
    assert run_report.load_multichip(str(wd)) is None
    with open(wd / "MULTICHIP_r07.json", "w") as f:
        f.write('{"bench": "multihost_sc')
    assert run_report.load_multichip(str(wd)) is None
    # An EXPLICITLY named path fails loudly instead of degrading to the
    # "no record found" note — the operator typed it.
    with pytest.raises(ValueError, match="unreadable"):
        run_report.load_multichip(str(wd), str(wd / "nope.json"))
    with pytest.raises(ValueError, match="not a multihost_scaling"):
        run_report.load_multichip(str(wd), str(wd / "MULTICHIP_r05.json"))


def test_serve_section_absent_for_training_only_run(tmp_path):
    """A pure training workdir renders NO serve section — the golden
    training report stays byte-stable."""
    wd = _canned_workdir(tmp_path)
    assert run_report.load_serve(wd) is None
    report = run_report.render_report(
        wd, run_report.load_goodput(wd), run_report.load_flight(wd), None
    )
    assert "Serve post-mortem" not in report


def test_slo_violation_renders_loudly(tmp_path):
    """An out-of-objective run must say so, naming the violated axis."""
    from rt1_tpu.obs.slo import SLOLedger, SLOObjectives

    wd = tmp_path / "bad-run"
    wd.mkdir()
    ledger = SLOLedger(SLOObjectives(availability=0.99))
    for _ in range(90):
        ledger.observe("ok", 0.010)
    for _ in range(10):
        ledger.observe("failed", 0.0)
    ledger.write_summary(str(wd / "slo_summary.json"))
    report = run_report.render_report(
        str(wd), None, None, None, serve=run_report.load_serve(str(wd))
    )
    assert "SLO VIOLATED — availability outside objective." in report


def test_main_renders_serve_section(tmp_path, capsys):
    wd = _canned_serve_workdir(tmp_path)
    run_report.main(["--workdir", wd])
    out = capsys.readouterr().out
    assert "Serve post-mortem" in out
    assert "Availability 99.600%" in out


def _canned_deploy_workdir(tmp_path):
    """A workdir as scripts/deploy_loop.py leaves it: one promoted and
    one rolled-back fleet episode in BENCH_deploy.json."""
    wd = tmp_path / "deploy-run"
    wd.mkdir()
    record = {
        "bench": "deploy_e2e",
        "verdict": "deploy_cycle_proven",
        "total_seconds": 812.4,
        "config": {"gate_tasks": "block2block"},
        "promote": {
            "episode": "promote",
            "faults": None,
            "final_deploy": {
                "incumbent_step": 4,
                "promotions_total": 1,
                "rollbacks_total": 0,
            },
            "timeline": [
                {"tick": 3, "event": "candidate", "step": 4, "incumbent": 2},
                {"tick": 3, "event": "gate_passed", "step": 4},
                {"tick": 3, "event": "canary_started", "step": 4,
                 "replica": 1, "weight": 0.5},
                {"tick": 9, "event": "promoted", "step": 4,
                 "previous_incumbent": 2, "replicas": 2},
            ],
            "traffic": {
                "requests_ok": 1480, "failures": [], "restarts": [],
                "sessions_created": 31,
            },
            "post_sweep_restarted": [],
            "verdicts": [
                {"path": "deploy/verdict_4.json", "candidate_step": 4,
                 "incumbent_step": 2, "passed": True, "signature_ok": True},
            ],
        },
        "rollback": {
            "episode": "rollback",
            "faults": "canary_slo_breach@4",
            "final_deploy": {
                "incumbent_step": 4,
                "promotions_total": 0,
                "rollbacks_total": 1,
            },
            "timeline": [
                {"tick": 2, "event": "candidate", "step": 6, "incumbent": 4},
                {"tick": 2, "event": "gate_passed", "step": 6},
                {"tick": 2, "event": "canary_started", "step": 6,
                 "replica": 1, "weight": 0.5},
                {"tick": 8, "event": "rolled_back", "step": 6, "replica": 1,
                 "reason": "slo_breach_injected", "incumbent": 4},
            ],
            "traffic": {
                "requests_ok": 960,
                "failures": [],
                "restarts": [{"session": "probe-9", "unix_time": 1.0}],
                "sessions_created": 22,
            },
            "post_sweep_restarted": ["probe-11"],
            "verdicts": [
                {"path": "deploy/verdict_6.json", "candidate_step": 6,
                 "incumbent_step": 4, "passed": True, "signature_ok": True},
            ],
        },
    }
    with open(wd / "BENCH_deploy.json", "w") as f:
        json.dump(record, f)
    return str(wd)


def test_deploy_section_renders_timeline_and_verdicts(tmp_path):
    """ISSUE 16 satellite: BENCH_deploy.json renders as the promotion
    timeline + signed-verdict table, ahead of the serve post-mortem."""
    wd = _canned_deploy_workdir(tmp_path)
    deploy = run_report.load_deploy(wd)
    assert deploy is not None
    report = run_report.render_report(wd, None, None, None, deploy=deploy)

    assert "## Deployment (promotion controller)" in report
    assert (
        "Verdict 'deploy_cycle_proven' in 812.4 s (2 fleet episode(s), "
        "gate tasks 'block2block')." in report
    )
    lines = report.splitlines()
    # Both episodes, each with its headline and timeline rows.
    promote_hdr = next(ln for ln in lines if ln.startswith("[promote]"))
    assert "faults=none" in promote_hdr
    assert "incumbent 4, 1 promotion(s), 0 rollback(s)." in promote_hdr
    rollback_hdr = next(ln for ln in lines if ln.startswith("[rollback]"))
    assert "faults=canary_slo_breach@4" in rollback_hdr
    assert "0 promotion(s), 1 rollback(s)." in rollback_hdr
    assert (
        "  tick    3  canary_started    step=4 replica=1 weight=0.5"
        in lines
    )
    assert (
        "  tick    9  promoted          step=4 previous_incumbent=2 "
        "replicas=2" in lines
    )
    rolled = next(
        ln for ln in lines if "rolled_back" in ln and "tick" in ln
    )
    assert "reason=slo_breach_injected" in rolled
    # Traffic honesty: re-homed count folds live restarts + post sweep.
    promote_traffic = next(
        ln for ln in lines if "1480 ok" in ln
    )
    assert "0 failed, 0 re-homed" in promote_traffic
    rollback_traffic = next(ln for ln in lines if "960 ok" in ln)
    assert "2 re-homed (restarted: true)" in rollback_traffic
    # The signed-verdict table.
    v4 = next(ln for ln in lines if ln.startswith("deploy/verdict_4.json"))
    assert "ok" in v4 and "True" in v4
    assert any(ln.startswith("deploy/verdict_6.json") for ln in lines)


def test_deploy_section_absent_without_record(tmp_path):
    """A workdir with no BENCH_deploy.json renders no deployment section
    — the golden training report stays byte-stable."""
    wd = _canned_workdir(tmp_path)
    assert run_report.load_deploy(wd) is None
    report = run_report.render_report(
        wd, run_report.load_goodput(wd), run_report.load_flight(wd), None
    )
    assert "Deployment (promotion controller)" not in report


def test_deploy_loader_tolerates_torn_record(tmp_path):
    wd = tmp_path / "torn"
    wd.mkdir()
    (wd / "BENCH_deploy.json").write_text('{"bench": "deploy_e2e", ')
    assert run_report.load_deploy(str(wd)) is None


# -------------------------------------------------- alerts & history


def _canned_obs_workdir(tmp_path):
    """A workdir as an armed `fleet --collector` run leaves it: a TSDB
    snapshot holding serve/deploy history plus the scraped-back
    rt1_alert_* families from a ReplicaDown incident."""
    from rt1_tpu.obs.tsdb import SNAPSHOT_BASENAME, TSDB

    wd = tmp_path / "obsrun"
    wd.mkdir()
    clock = {"t": 1000.0}
    db = TSDB(clock=lambda: clock["t"])
    for cycle in range(10):
        down = 3 <= cycle < 7  # replica 1 dead for scrape cycles 3..6
        db.append_many(
            [
                ("rt1_serve_replica_up", {"replica_id": "0"}, 1.0),
                (
                    "rt1_serve_replica_up",
                    {"replica_id": "1"},
                    0.0 if down else 1.0,
                ),
                ("rt1_serve_slo_requests_total", None, 10.0 * (cycle + 1)),
                (
                    "rt1_serve_slo_error_budget_burn_rolling",
                    None,
                    25.0 if down else 0.0,
                ),
                ("rt1_alert_fired_total", None, 1.0 if cycle >= 3 else 0.0),
                (
                    "rt1_alert_resolved_total",
                    None,
                    1.0 if cycle >= 7 else 0.0,
                ),
                ("rt1_obs_collector_cycles_total", None, float(cycle + 1)),
            ],
            t=clock["t"],
        )
        if down:
            db.append(
                "rt1_alert_firing",
                1.0,
                labels={
                    "alert": "ReplicaDown",
                    "severity": "page",
                    "replica_id": "1",
                },
                t=clock["t"],
            )
        clock["t"] += 2.0
    db.write_snapshot(str(wd / SNAPSHOT_BASENAME))
    return str(wd)


def test_obs_section_golden(tmp_path):
    wd = _canned_obs_workdir(tmp_path)
    obs = run_report.load_obs(wd)
    assert obs is not None
    report = run_report.render_report(wd, None, None, None, obs=obs)
    lines = report.splitlines()
    assert "## Alerts & history (metrics plane)" in lines

    # The snapshot header line names the file and its bounds.
    snap_line = next(ln for ln in lines if ln.startswith("Snapshot "))
    assert "8 series" in snap_line and "74 points" in snap_line

    # The alert timeline reconstructs the incident span from the series:
    # firing at cycles 3..6 = 6 seconds of scrape coverage, with the
    # instance labels and lifecycle counters intact.
    assert any(
        "fired_total=1" in ln and "resolved_total=1" in ln for ln in lines
    )
    incident = next(ln for ln in lines if "ReplicaDown" in ln)
    assert "[page]" in incident
    assert "firing" in incident
    assert "seen    6.0s" in incident
    assert "replica_id=1" in incident

    # Key signals render as sparklines with the last value, labeled
    # instances fanned out.
    assert any(
        "rt1_serve_replica_up{replica_id=1}" in ln and ln.endswith(" 1")
        for ln in lines
    )
    burn = next(
        ln
        for ln in lines
        if "rt1_serve_slo_error_budget_burn_rolling" in ln
        and "Key signals" not in ln
    )
    assert burn.endswith(" 0")  # decayed back by the last scrape
    # The non-spark families are counted, not silently dropped.
    assert any("more stored series" in ln for ln in lines)


def test_obs_section_absent_without_snapshot(tmp_path):
    """A training-only workdir renders no metrics-plane section at all:
    the golden training report stays byte-stable."""
    wd = _canned_workdir(tmp_path)
    assert run_report.load_obs(wd) is None
    report = run_report.render_report(
        wd, run_report.load_goodput(wd), run_report.load_flight(wd), None
    )
    assert "Alerts & history" not in report


def test_obs_loader_tolerates_torn_snapshot(tmp_path):
    """A SIGKILLed collector's half-written snapshot still loads (torn
    tail dropped) — the post-mortem exists exactly for that run."""
    wd = _canned_obs_workdir(tmp_path)
    from rt1_tpu.obs.tsdb import SNAPSHOT_BASENAME

    path = os.path.join(wd, SNAPSHOT_BASENAME)
    body = open(path).read().rstrip("\n")
    with open(path, "w") as f:
        f.write(body[:-20])
    obs = run_report.load_obs(wd)
    assert obs is not None
    report = run_report.render_report(wd, None, None, None, obs=obs)
    assert "## Alerts & history (metrics plane)" in report

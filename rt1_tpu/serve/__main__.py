"""Serving entry point: `python -m rt1_tpu.serve`.

Run (tiny smoke config, random weights, CPU):

  JAX_PLATFORMS=cpu python -m rt1_tpu.serve \
      --config rt1_tpu/train/configs/tiny.py --random_init --port 8321

Run (trained checkpoint):

  python -m rt1_tpu.serve --config rt1_tpu/train/configs/language_table.py \
      --workdir /tmp/vt --port 8321 --embedder ngram

Prints one JSON ready-line (`{"status": "serving", "port": ...}`) once the
batched step is AOT-compiled and the socket is bound, then serves until
SIGTERM/SIGINT, which drains accepted requests before exiting.
"""

from __future__ import annotations

import json
import sys
import threading
import time


def _start_checkpoint_watcher(
    app, workdir: str, interval_s: float, served_step
) -> None:
    """Poll the checkpoint dir; hot-swap when a newer step appears.

    The push-free alternative to `POST /reload`: a training job saving into
    `workdir` rolls onto the fleet automatically. `served_step` is the step
    the server actually restored at boot — seeding from a fresh
    latest_step() here would silently skip a checkpoint saved during the
    (long) jax boot + AOT warmup. Daemon thread, restore errors
    logged-and-skipped (the old params keep serving; the next poll
    retries).
    """
    import os

    from rt1_tpu.trainer.checkpoints import latest_step

    directory = os.path.join(os.path.abspath(workdir), "checkpoints")

    def _watch():
        served = served_step if served_step is not None and served_step >= 0 \
            else None
        while True:
            time.sleep(interval_s)
            try:
                newest = latest_step(directory)
                if newest is not None and (served is None or newest > served):
                    result = app.reload(newest)
                    served = result["checkpoint_step"]
                    print(
                        json.dumps({"status": "reloaded", **result}),
                        flush=True,
                    )
            except Exception as exc:  # noqa: BLE001 - keep watching
                print(
                    json.dumps(
                        {"status": "reload_failed", "error": str(exc)}
                    ),
                    flush=True,
                )

    threading.Thread(
        target=_watch, name="rt1-serve-ckpt-watcher", daemon=True
    ).start()


def main(argv):
    del argv
    from absl import flags

    # Persistent XLA cache BEFORE any jax compile: the serving process's
    # single batched-step compile is served from disk on restarts.
    from rt1_tpu import compilation_cache

    compilation_cache.enable_persistent_cache()

    FLAGS = flags.FLAGS
    # First touch of the accelerator, before the heavy imports: a replica
    # that cannot have it says so at once — on stdout too, where a fleet
    # supervisor reads its replicas' status lines.
    from rt1_tpu.parallel.distributed import describe_devices

    try:
        device = describe_devices()
    except RuntimeError as exc:
        print(
            json.dumps({
                "status": "failed",
                "replica_id": FLAGS.replica_id,
                "error": str(exc),
            }),
            flush=True,
        )
        raise

    from rt1_tpu.eval.embedding import get_embedder
    from rt1_tpu.eval.restore import build_serve_engine
    from rt1_tpu.serve.server import (
        ServeApp,
        install_signal_handlers,
        make_server,
    )

    config = FLAGS.config
    if not FLAGS.random_init and not FLAGS.allow_embedder_mismatch:
        # Same guard as eval/main.py: serving a checkpoint with a different
        # instruction embedder than it was trained on would hand the policy
        # foreign-domain embeddings and score ~random with 200 OK.
        from rt1_tpu.data.collect import check_embedder_compatibility

        check_embedder_compatibility(
            FLAGS.workdir,
            FLAGS.embedder,
            context="checkpoint data_manifest; pass "
            "--allow_embedder_mismatch to override",
            manifest_name="data_manifest.json",
        )
    from rt1_tpu.serve.engine import pow2_buckets

    if FLAGS.buckets.strip() == "auto":
        buckets = pow2_buckets(FLAGS.max_sessions)
    else:
        buckets = [
            int(b) for b in FLAGS.buckets.split(",") if b.strip()
        ] or None
    embedder = get_embedder(FLAGS.embedder)
    engine, step = build_serve_engine(
        config,
        workdir=None if FLAGS.random_init else FLAGS.workdir,
        inference_dtype=FLAGS.inference_dtype,
        max_sessions=FLAGS.max_sessions,
        buckets=buckets,
        embedder=embedder,
        cached_inference=FLAGS.cached_inference,
    )

    # Standby restore source for zero-downtime hot-swap (POST /reload and
    # the optional watcher). Random-init replicas rebuild the same
    # deterministic init — the chaos harness hot-swaps bit-identical
    # params to prove the mechanism without a trained checkpoint.
    from rt1_tpu.eval.restore import load_standby_variables

    reload_workdir = None if FLAGS.random_init else FLAGS.workdir

    def reload_fn(reload_step):
        return load_standby_variables(
            config, workdir=reload_workdir, step=reload_step
        )

    # Data-flywheel episode capture (rt1_tpu/flywheel/): opt-in via
    # --capture_dir. The sink shares the engine's embedder instance so
    # text-only clients still yield embeddable episodes without loading
    # the embedding model a second time.
    capture = None
    if FLAGS.capture_dir:
        from rt1_tpu.flywheel import EpisodeCaptureSink

        capture = EpisodeCaptureSink(
            FLAGS.capture_dir,
            max_episodes=FLAGS.capture_max_episodes,
            max_steps=FLAGS.capture_max_steps,
            embed_fn=embedder,
        )

    # Arm chaos sites from the environment (RT1_FAULTS): the fleet
    # supervisor exports its combined fault spec before spawning so
    # replica-side sites (session_restore) fire inside this process.
    from rt1_tpu.resilience import faults

    faults.install_from("")

    app = ServeApp(
        engine,
        image_shape=(config.data.height, config.data.width, 3),
        max_batch=FLAGS.max_batch or None,
        max_delay_s=FLAGS.max_delay_ms / 1e3,
        max_queue=FLAGS.max_queue,
        scheduler=FLAGS.scheduler,
        pipeline_depth=FLAGS.pipeline_depth,
        request_timeout_s=FLAGS.request_timeout_s,
        replica_id=FLAGS.replica_id,
        reload_fn=reload_fn,
        slow_threshold_ms=FLAGS.slow_threshold_ms,
        exemplar_path=FLAGS.exemplar_path or None,
        capture=capture,
        checkpoint_step=step if step is not None else -1,
        session_snapshot_dir=FLAGS.session_snapshot_dir or None,
        snapshot_max_age_s=FLAGS.snapshot_max_age_s,
        snapshot_every=FLAGS.session_snapshot_every,
    )
    app.start(warmup=True)
    if FLAGS.watch_checkpoints_s > 0 and not FLAGS.random_init:
        _start_checkpoint_watcher(app, FLAGS.workdir,
                                  FLAGS.watch_checkpoints_s,
                                  served_step=step)
    httpd = make_server(app, host=FLAGS.host, port=FLAGS.port,
                        quiet=not FLAGS.verbose)
    install_signal_handlers(app, httpd)
    print(
        json.dumps(
            {
                "status": "serving",
                "host": httpd.server_address[0],
                "port": httpd.server_address[1],
                **device,
                "replica_id": FLAGS.replica_id,
                "checkpoint_step": step,
                "max_sessions": engine.max_sessions,
                "compile_count": engine.compile_count,
                "buckets": [int(b) for b in engine.buckets],
                "scheduler": FLAGS.scheduler,
                "inference_dtype": engine.inference_dtype,
                "cached_inference": engine.cached_inference,
                "param_bytes_device": engine.serving_param_bytes,
            }
        ),
        flush=True,
    )
    try:
        httpd.serve_forever()
    finally:
        httpd.server_close()
        if not app.draining:
            app.drain()
    print(json.dumps({"status": "drained", **app.metrics_snapshot()}),
          flush=True)
    return 0


if __name__ == "__main__":
    from absl import app as absl_app
    from absl import flags
    from ml_collections import config_flags

    config_flags.DEFINE_config_file("config", None, "Model/data config.")
    flags.DEFINE_string("workdir", "/tmp/rt1_tpu", "Checkpoint directory.")
    flags.DEFINE_bool(
        "random_init", False,
        "Serve randomly initialized weights (smoke tests / load generation; "
        "no checkpoint needed).")
    flags.DEFINE_string("host", "127.0.0.1", "Bind address.")
    flags.DEFINE_integer("port", 8321, "Bind port (0 = ephemeral).")
    flags.DEFINE_integer(
        "max_sessions", 8,
        "Concurrent session slots = fixed device batch size.")
    flags.DEFINE_integer(
        "max_batch", 0,
        "Micro-batch flush size (0 = max_sessions).")
    flags.DEFINE_float(
        "max_delay_ms", 10.0,
        "[cycle scheduler] Micro-batching deadline: longest a request "
        "waits for batchmates. The continuous scheduler never waits — "
        "batching emerges from device busy time.")
    flags.DEFINE_integer(
        "max_queue", 64,
        "Bounded admission queue; beyond this /act returns 503 busy.")
    flags.DEFINE_enum(
        "scheduler", "continuous", ["continuous", "cycle"],
        "Batch scheduler: 'continuous' rolls requests into the next "
        "device step the moment they land (double-buffered pipeline); "
        "'cycle' is the legacy wait-for-deadline-or-full loop (A/B "
        "baseline).")
    flags.DEFINE_integer(
        "pipeline_depth", 2,
        "[continuous] Max batches in flight: 2 = prepare/upload batch "
        "N+1 while N executes (double buffering).")
    flags.DEFINE_string(
        "buckets", "auto",
        "AOT batch-size buckets, comma-separated (e.g. '1,2,4,8'); "
        "'auto' = powers of two up to max_sessions. Every bucket is "
        "compiled at warm-up; compile_count is pinned at the bucket "
        "count for the process lifetime.")
    flags.DEFINE_float(
        "request_timeout_s", 60.0, "Server-side per-request timeout.")
    flags.DEFINE_integer(
        "replica_id", 0,
        "This replica's id within a fleet (rt1_tpu.serve.fleet sets it); "
        "surfaced in /healthz and the replica_id metrics gauge.")
    flags.DEFINE_float(
        "watch_checkpoints_s", 0.0,
        "Poll the workdir checkpoint dir this often and hot-swap newer "
        "steps automatically (0 = off; ignored with --random_init).")
    flags.DEFINE_enum(
        "inference_dtype", "f32", ["f32", "bf16", "int8"],
        "Low-precision serving mode (rt1_tpu/models/quant.py): bf16 casts "
        "weights+compute once at restore; int8 quantizes the FiLM-"
        "EfficientNet and transformer matmul weights per-output-channel "
        "(norms/embeddings/action head stay f32). /reload requantizes "
        "standby checkpoints — compile_count stays 1.")
    flags.DEFINE_bool(
        "cached_inference", False,
        "Incremental decode: keep per-session transformer K/V caches on "
        "device so a step attends one frame against cached keys instead "
        "of re-running the full window (rt1_tpu/serve/engine.py). Exact "
        "while a session's window fills; after roll-over, cache entries "
        "keep their insertion-time positions (staleness bounded at "
        "window-1 rolls; parity gated by serve/parity.py). Hot-swap "
        "rebuilds all caches from retained context. OFF by default — "
        "the default path is byte-identical to the windowed engine.")
    flags.DEFINE_string(
        "embedder", "hash",
        "Instruction embedder spec (hash | ngram | use | table.npz).")
    flags.DEFINE_bool(
        "allow_embedder_mismatch", False,
        "Serve even if the checkpoint's data manifest records a different "
        "instruction embedder.")
    flags.DEFINE_float(
        "slow_threshold_ms", 0.0,
        "Keep requests at least this slow in the exemplar ring "
        "(GET /slow_requests); 0 keeps the most recent window of all.")
    flags.DEFINE_string(
        "exemplar_path", "",
        "Dump the slow-request exemplar ring here (JSONL) on drain.")
    flags.DEFINE_string(
        "session_snapshot_dir", "",
        "Durable sessions: write a bounded on-disk snapshot ring of live "
        "session windows here (rt1_tpu/serve/migrate.py) so a SIGKILL'd "
        "replica's sessions restore mid-episode at re-home time instead "
        "of resetting. OFF by default — no disk writes unless an "
        "operator opts in.")
    flags.DEFINE_float(
        "snapshot_max_age_s", 600.0,
        "Staleness bound for crash restores: a ring snapshot older than "
        "this starts a fresh window instead (age surfaced as "
        "snapshot_age_s in the restoring /act response).")
    flags.DEFINE_integer(
        "session_snapshot_every", 1,
        "Write a session's ring snapshot every N served steps (1 = every "
        "step; higher trades restore staleness for snapshot I/O).")
    flags.DEFINE_string(
        "capture_dir", "",
        "Data flywheel: capture completed sessions as episode .npz files "
        "into this directory (rt1_tpu/flywheel/capture.py). OFF by "
        "default — serving records nothing unless an operator opts in.")
    flags.DEFINE_integer(
        "capture_max_episodes", 512,
        "Capture disk ring: keep at most this many episode files "
        "(oldest pruned).")
    flags.DEFINE_integer(
        "capture_max_steps", 512,
        "Capture per-session step bound; steps beyond it are dropped.")
    flags.DEFINE_bool("verbose", False, "Log per-request lines.")
    flags.mark_flags_as_required(["config"])
    sys.exit(absl_app.run(main))

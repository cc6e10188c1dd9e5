"""Benchmark: flagship RT-1 train-step throughput on the attached TPU chip.

Prints ONE JSON line: {"metric", "value", "unit"}.

Config matches the reference's implied throughput baseline (SURVEY.md §6,
`distribute_train.py:269-295`): batch 8 per chip, time_sequence_length 6,
256×456 images, FiLM-EfficientNet-B3 + TokenLearner (8 tokens), 8-layer decoder,
vocab 256 — i.e. one DDP rank's workload on one TPU chip.

The device modes (train/infer/e2e/mfu) need an accelerator: where jax
finds none they exit non-zero and print no metric. `--mode env` and
`--mode multihost` are host-only and run anywhere.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    # Host dispatch hiccups of tens of ms are not averaged out by a single
    # 20-step window; the train headline times several windows and
    # publishes the best sustained one.
    p.add_argument("--windows", type=int, default=5)
    p.add_argument("--dtype", default="bfloat16", choices=["bfloat16", "float32"])
    p.add_argument("--height", type=int, default=256)
    p.add_argument("--width", type=int, default=456)
    # "train": train-step throughput (the driver's metric). "infer": closed-
    # loop control-step latency of the jitted single-pass infer_step at
    # batch 1 (the reference's 10 Hz budget, SURVEY.md §7 hard part 3).
    # "e2e": the REAL training path — windowed episode pipeline feeding
    # uint8 batches through the double-buffered device prefetch (VERDICT r1
    # weak #1: the compute-only bench hid the input pipeline). Also prints a
    # stderr detail line with compute-only vs end-to-end and the stall %.
    # "mfu": model-flops-utilization estimate from XLA cost analysis.
    # "env": host-side simulator throughput (control steps/s incl. obs
    # render) — the denominator of closed-loop eval wall-clock. The
    # reference pays IK + 24x pybullet stepSimulation + TINY_RENDERER per
    # control step (language_table.py:599-646); ours is the kinematic
    # backend + PIL renderer. Needs no accelerator.
    # "multihost": 1-process vs 2-process scale-out (scripts/
    # bench_multihost.py — real jax.distributed groups on forced CPU host
    # devices) -> the MULTICHIP record; subprocess-based, needs no
    # accelerator either.
    p.add_argument(
        "--mode", default="train",
        choices=["train", "infer", "e2e", "mfu", "env", "multihost"]
    )
    p.add_argument(
        "--data_dir", default="/tmp/rt1_bench_episodes",
        help="e2e mode: episode cache dir (synthesized on first run).")
    p.add_argument(
        "--episodes", type=int, default=24,
        help="e2e mode: corpus size. 24 (default, the historical TPU-metric "
             "corpus) fits inside the windowed dataset's 64-episode RAM "
             "cache, hiding per-window episode reloads; sizes above it "
             "exercise the decode-per-window regime a real corpus (7800 "
             "episodes) lives in.")
    p.add_argument("--src_height", type=int, default=180)
    p.add_argument(
        "--src_width", type=int, default=320,
        help="e2e mode: synthetic corpus SOURCE frame size. Default 180x320 "
             "(the simulator-native size of the historical bench corpus); "
             "the reference's converted corpus stores 256x456 frames, so "
             "--src_height 256 --src_width 456 reproduces its per-window "
             "decode bill. Non-default sizes get their own corpus dir.")
    p.add_argument(
        "--packed", action="store_true",
        help="e2e mode: feed from the packed mmap frame cache via the "
             "sample-ahead feeder (rt1_tpu/data/pack.py + feeder.py) "
             "instead of the tf.data decode+crop path. The cache is packed "
             "on first run and reused. Metric gains a '_packed' suffix.")
    p.add_argument(
        "--model", default="flagship", choices=["flagship", "tiny"],
        help="Model under the step: 'flagship' is the reference-parity B3 "
             "config (the TPU headline); 'tiny' is the CPU-runnable "
             "tiny-tokenizer config (configs/tiny.py scale) for input-"
             "pipeline A/Bs on hosts without a chip. Metrics gain a "
             "'_tiny' suffix so flagship baselines stay clean.")
    p.add_argument(
        "--attention_impl", default="dense", choices=["dense", "pallas"],
        help="infer mode: attention implementation under test.")
    p.add_argument(
        "--inference_dtype", default="",
        help="infer mode: comma dtypes to A/B (e.g. 'f32,bf16,int8') "
             "through the low-precision serving path (rt1_tpu/models/"
             "quant.py — bf16 cast-at-restore, int8 per-channel weights). "
             "Measured with the interleaved-window methodology "
             "(alternating dtype order per round, best-of floors per "
             "side); adds an infer_quant_ab JSON line with a per-dtype "
             "latency column + param bytes. Honesty: XLA:CPU has no "
             "native int8 matmul — there the byte column is the measured "
             "win and TPU latency is the projection.")
    p.add_argument(
        "--window_sweep", default="",
        help="infer mode: comma window lengths (e.g. '3,6,15') to A/B the "
             "full-window infer_step against the KV-cached "
             "infer_step_cached at each length (interleaved windows, "
             "alternating side order per round, floor medians — the "
             "quant-A/B methodology). Writes BENCH_serve_kvcache.json "
             "next to this script. Headline: cached per-step latency "
             "stays near-flat across window lengths (O(frame) work) "
             "while the windowed path grows O(window).")
    p.add_argument(
        "--guard", action="store_true",
        help="e2e mode: after the headline measurement, re-run the same "
             "loop through the guard-enabled train step (rt1_tpu/resilience "
             "— device-side non-finite update skip + cumulative skip "
             "counter) and report guard_overhead_pct in the e2e_detail "
             "line. The acceptance budget is <= 2%% (the guard is one "
             "select per parameter and one replicated int add; host-side "
             "checks only reuse scalars the loop already fetches at log "
             "steps). The headline metric stays the UNGUARDED number.")
    p.add_argument(
        "--health", action="store_true",
        help="e2e mode: A/B the model-health-pack train step (rt1_tpu/obs/"
             "health.py — per-layer grad/update norms, logit entropy, "
             "token accuracy packed on device). health_overhead_pct is "
             "the pack's program delta measured on per-step-synced "
             "resident-batch floors, alternating sides (budget <= 2%%; "
             "exceeding it flags health_over_budget); e2e_health_* "
             "report the pipeline-fed rate too, which on a core-starved "
             "host additionally includes feeder contention. The headline "
             "metric stays the pack-free number. Composable with --guard.")
    p.add_argument(
        "--mixed_precision", action="store_true",
        help="mfu/e2e modes: A/B the true-mixed-precision train step "
             "(f32 master params + one in-step bf16 cast for fwd/bwd, "
             "trainer/train.py mixed_precision=True) against the step as "
             "configured, using the PR 5 interleaved-window methodology "
             "(alternating order per round, best-of-N floors on both "
             "sides). Pass --dtype float32 for a clean f32-vs-mixed "
             "comparison; the headline metric stays the configured-step "
             "number, the A/B lands in the *_detail stderr line "
             "(mfu_mixed_precision / e2e_mp_steps_per_sec_per_chip + "
             "mp_speedup_pct).")
    p.add_argument(
        "--trace_dir", default="",
        help="Capture a jax.profiler trace of the measured loop into this "
             "directory (TensorBoard/XProf format; works on TPU and CPU) "
             "for train/mfu/e2e/infer modes (env mode is host-only and "
             "ignores it with a warning). Where the headline number comes "
             "from is visible op-by-op there.")
    p.add_argument(
        "--trace", default="",
        help="Write a host-side Chrome-trace JSON (rt1_tpu/obs/trace.py — "
             "the same format the train loop emits with config.obs.trace) "
             "to this path: bench-loop spans plus, with --packed, the "
             "sample-ahead feeder workers' assembly spans on one Perfetto "
             "timeline. Near-zero overhead (<2% steps/s budget).")
    args = p.parse_args()

    import sys

    if args.mode == "env":
        if args.trace_dir:
            print("bench: --trace_dir is ignored in --mode env (host-only "
                  "loop, no XLA programs to trace)", file=sys.stderr)
        return env_bench(args)

    if args.mode == "multihost":
        # Subprocess groups on forced CPU host devices — this process
        # never touches an accelerator. All knobs live on the dedicated
        # CLI (scripts/bench_multihost.py); bench.py is the discoverable
        # front door for the MULTICHIP record.
        from scripts.bench_multihost import main as multihost_main

        record = multihost_main(["--steps", str(args.steps)])
        print(
            json.dumps(
                {
                    "metric": "multihost_examples_per_sec_ratio_2p_over_1p",
                    "value": record["scaling"][
                        "examples_per_sec_ratio_2p_over_1p"
                    ],
                    "unit": "x",
                }
            )
        )
        return

    variant = ("_tiny" if args.model == "tiny" else "") + (
        "_packed" if args.packed and args.mode == "e2e" else ""
    )

    import jax

    # A missing or unusable chip raises here, by name, where jax is not
    # pinned to the CPU; where it is, refuse: a CPU run is not a
    # measurement of the device.
    from rt1_tpu.parallel.distributed import describe_devices

    device = describe_devices()
    if device["platform"] == "cpu":
        sys.exit(
            f"bench: --mode {args.mode} needs an accelerator, but jax found "
            f"{device}"
        )

    # Persistent compilation cache: repeated bench runs skip the
    # multi-minute first compile of the full B3 graph.
    from rt1_tpu.compilation_cache import enable_persistent_cache

    enable_persistent_cache()
    if args.trace:
        # Before any feeder threads exist, so --packed assembly spans land
        # in the same timeline as the bench loop's.
        from rt1_tpu.obs import trace as obs_trace

        obs_trace.enable(args.trace)
    import jax.numpy as jnp

    from rt1_tpu.models.rt1 import RT1Policy
    from rt1_tpu.parallel import MeshConfig, make_mesh
    from rt1_tpu.specs import language_table_action_space, sample_space
    from rt1_tpu.trainer import create_train_state, make_optimizer, make_train_step_fns

    def build_bench_model(dtype):
        if args.model == "tiny":
            # The REAL tiny config, not a copy: retuning configs/tiny.py
            # retunes the '_tiny' bench metrics with it. Only the bench-axis
            # knobs (seq len to match the e2e window, attention impl, dtype)
            # are overridden.
            from rt1_tpu.train.configs import tiny as tiny_config
            from rt1_tpu.train.train import build_model

            mc = tiny_config.get_config().model
            mc.time_sequence_length = 6
            mc.attention_impl = args.attention_impl
            mc.dtype = dtype
            return build_model(mc)
        return RT1Policy(
            action_space=language_table_action_space(),
            time_sequence_length=6,
            dtype=jnp.bfloat16 if dtype == "bfloat16" else jnp.float32,
            attention_impl=args.attention_impl,
        )

    model = build_bench_model(args.dtype)
    rng = jax.random.PRNGKey(0)
    b, t = args.batch, 6
    obs = {
        "image": jax.random.uniform(rng, (b, t, args.height, args.width, 3)),
        "natural_language_embedding": jax.random.normal(
            jax.random.fold_in(rng, 1), (b, t, 512)
        ),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 2), (b, t)
    )

    if args.mode == "infer":
        return infer_bench(
            args, model, rng, obs, actions, build_model_fn=build_bench_model
        )

    n_chips = len(jax.devices())
    mesh = make_mesh(MeshConfig())
    tx = make_optimizer(steps_per_epoch=975)  # 7800 episodes / batch 8 (reference)
    state = create_train_state(model, rng, (obs, actions), tx)
    fns = make_train_step_fns(model, mesh, state)
    state = fns.shard_state(state)
    batch = fns.shard_batch((obs, actions))

    # --mixed_precision A side = the configured step above; B side = the
    # true-mixed-precision program (bf16 compute model + one in-step cast
    # of the f32 masters). Same state/shardings, so the two programs
    # interleave over one donated state.
    mp_step = None
    if args.mixed_precision and args.mode in ("mfu", "e2e"):
        mp_fns = make_train_step_fns(
            build_bench_model("bfloat16"), mesh, state, mixed_precision=True
        )
        mp_step = mp_fns.train_step
    elif args.mixed_precision:
        print("bench: --mixed_precision only applies to --mode mfu/e2e; "
              "ignored", file=sys.stderr)

    def timed_resident_loop(state, steps, warmup, resident=None, trace=False,
                            step_fn=None):
        step_fn = fns.train_step if step_fn is None else step_fn
        resident = batch if resident is None else resident
        for i in range(warmup):
            state, metrics = step_fn(state, resident, jax.random.fold_in(rng, i))
            jax.block_until_ready(metrics["loss"])
        from rt1_tpu.obs import trace as obs_trace

        with _maybe_trace(args.trace_dir if trace else ""):
            t0 = time.perf_counter()
            for i in range(steps):
                with obs_trace.span("bench_step", step=i):
                    state, metrics = step_fn(state, resident, jax.random.fold_in(rng, 100 + i))
            jax.block_until_ready(metrics["loss"])
            # dt read INSIDE the trace context: trace stop/serialization
            # can take seconds and must not deflate the published number.
            dt = time.perf_counter() - t0
        return state, dt

    if args.mode == "mfu":
        return mfu_bench(
            args, fns, state, batch, rng, n_chips, timed_resident_loop,
            variant, mp_step=mp_step,
        )

    for flag in ("guard", "health"):
        if getattr(args, flag) and args.mode != "e2e":
            print(f"bench: --{flag} only applies to --mode e2e; ignored",
                  file=sys.stderr)
    if args.mode == "e2e":
        guarded_step = None
        if args.guard:
            # Same model/mesh/shardings, guarded step program. The adapter
            # hides the cumulative-skip-counter carry so the bench loop
            # calls it with the ordinary (state, batch, rng) signature.
            gfns = make_train_step_fns(model, mesh, state, guard_nonfinite=True)
            _skips = {"v": gfns.init_guard_skips()}

            def guarded_step(g_state, g_batch, g_rng):
                g_state, _skips["v"], metrics = gfns.train_step(
                    g_state, _skips["v"], g_batch, g_rng
                )
                return g_state, metrics

        health_step = None
        if args.health:
            # Same model/mesh/shardings, health-pack step program; the
            # signature is already (state, batch, rng).
            hfns = make_train_step_fns(model, mesh, state, model_health=True)
            health_step = hfns.train_step

        return e2e_bench(
            args, fns, state, rng, n_chips, timed_resident_loop, variant,
            guarded_step=guarded_step, health_step=health_step,
            mp_step=mp_step,
        )

    # Best-of-N windows: min time ~= noise-free sustained throughput; a
    # mean would charge the chip for host dispatch stragglers.
    best_dt = None
    for w in range(max(1, args.windows)):
        state, dt = timed_resident_loop(
            state, args.steps, args.warmup if w == 0 else 0,
            trace=(w == 0),
        )
        best_dt = dt if best_dt is None else min(best_dt, dt)
    steps_per_sec_per_chip = args.steps / best_dt / n_chips
    metric = f"train_steps_per_sec_per_chip{variant}"
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(steps_per_sec_per_chip, 4),
                "unit": "steps/s/chip",
            }
        )
    )
    _dump_host_trace()


def _dump_host_trace():
    """Write the --trace Chrome-trace JSON, if one is recording; prints a
    stderr detail line with the path (same convention as *_detail lines)."""
    from rt1_tpu.obs import trace as obs_trace

    if obs_trace.enabled():
        import sys

        path = obs_trace.dump()
        print(
            json.dumps({"mode": "host_trace", "path": path}), file=sys.stderr
        )


def _maybe_trace(trace_dir):
    """jax.profiler trace context when `trace_dir` is non-empty — the
    op-by-op evidence behind whichever headline loop it wraps."""
    import contextlib

    if not trace_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(trace_dir)


def _ensure_bench_episodes(
    data_dir, n_episodes=24, steps_per_episode=40, height=180, width=320
):
    """Synthesize a cached corpus of `height`x`width`-source episodes."""
    import glob

    import numpy as np

    from rt1_tpu.data.episodes import generate_synthetic_episode, save_episode

    if (height, width) != (180, 320):
        # Non-default source sizes live in their own corpus dir so the
        # historical 180x320 corpus (and its TPU-metric provenance) stays
        # untouched.
        data_dir = data_dir.rstrip("/") + f"_src{height}x{width}"
    paths = sorted(glob.glob(os.path.join(data_dir, "episode_*.npz")))
    if len(paths) >= n_episodes:
        return paths[:n_episodes]
    os.makedirs(data_dir, exist_ok=True)
    rng = np.random.default_rng(0)
    for i in range(n_episodes):
        save_episode(
            os.path.join(data_dir, f"episode_{i}.npz"),
            generate_synthetic_episode(
                rng, num_steps=steps_per_episode, height=height, width=width
            ),
        )
    return sorted(glob.glob(os.path.join(data_dir, "episode_*.npz")))


def _e2e_feed(args, fns):
    """The host->device batch iterator under test: tf.data or packed."""
    from rt1_tpu.data.pipeline import WindowedEpisodeDataset, device_feeder

    paths = _ensure_bench_episodes(
        args.data_dir,
        n_episodes=args.episodes,
        height=args.src_height,
        width=args.src_width,
    )
    if args.packed:
        import sys

        from rt1_tpu.data import pack as pack_lib
        from rt1_tpu.data.feeder import SampleAheadFeeder

        corpus_dir = os.path.dirname(paths[0])
        pack_dir = (
            corpus_dir.rstrip("/")
            + f"_packed_{args.height}x{args.width}_n{len(paths)}"
        )
        t0 = time.perf_counter()
        pack_lib.pack_episodes(
            paths, pack_dir, args.height, args.width, 0.95
        )
        print(
            json.dumps(
                {
                    "mode": "pack_detail",
                    "pack_dir": pack_dir,
                    "pack_seconds": round(time.perf_counter() - t0, 3),
                }
            ),
            file=sys.stderr,
        )
        cache = pack_lib.PackedEpisodeCache(pack_dir, window=6)
        feeder = SampleAheadFeeder(
            cache, args.batch, seed=0, num_threads=2, depth=2
        )
        return device_feeder(feeder, fns.batch_sharding, depth=2)
    ds = WindowedEpisodeDataset(
        paths, window=6, crop_factor=0.95, height=args.height, width=args.width
    )
    tfds = ds.as_tf_dataset(batch_size=args.batch, seed=0)
    return device_feeder(tfds.as_numpy_iterator(), fns.batch_sharding, depth=2)


def e2e_bench(args, fns, state, rng, n_chips, timed_resident_loop, variant="",
              guarded_step=None, health_step=None, mp_step=None):
    """Pipeline-fed steps: host windowing/augment -> uint8 H2D (double-
    buffered) -> device step. The number BASELINE.md's wall-clock north star
    actually cares about; `stall_pct` on stderr is the input-bound fraction.
    `--packed` swaps the tf.data assembly for the packed mmap cache +
    sample-ahead feeder. Like train mode, the headline is best-of-N
    `--windows` (dispatch-noise filtering, round-5 advisor finding).
    `--guard` / `--health` A/B the same loop through the guarded /
    health-pack step program and report the overhead percentages.
    """
    import sys

    import jax

    feed = _e2e_feed(args, fns)

    # Warmup compiles the uint8-input step and fills the prefetch queue.
    for i in range(args.warmup):
        state, metrics = fns.train_step(state, next(feed), jax.random.fold_in(rng, i))
        jax.block_until_ready(metrics["loss"])
    # One pipeline batch pinned on device: the stall baseline below must time
    # the SAME compiled program (uint8 inputs) as the e2e loop, or the
    # dtype-variant compute delta would masquerade as input stall.
    resident = next(feed)

    # Best-of-N windows (the same noise filter the train headline uses —
    # min over windows estimates the sustained rate with host-dispatch
    # stragglers removed). The trace wraps only the first window, and the
    # compute-only baseline runs untraced, so trace overhead can't inflate
    # either side of the stall computation.
    from rt1_tpu.obs import trace as obs_trace

    # A/B step programs (--guard / --health): warmed up once, then timed
    # in windows INTERLEAVED with the headline's. Sequential A-then-B
    # measurement puts slow host drift (thermal, page cache, a background
    # process grabbing a core) wholly on whichever loop ran last — a
    # round-5-style ordering artifact measured at tens of percent on this
    # 2-core host; interleaving lands drift on both sides of every
    # comparison, and best-of-N still filters the stragglers.
    alternates = {}
    if guarded_step is not None:
        alternates["guard"] = guarded_step
    if health_step is not None:
        alternates["health"] = health_step
    if mp_step is not None:
        alternates["mp"] = mp_step
    for k, stepfn in enumerate(alternates.values()):
        for i in range(args.warmup):
            state, metrics = stepfn(
                state, next(feed), jax.random.fold_in(rng, 200 + 100 * k + i)
            )
            jax.block_until_ready(metrics["loss"])

    sbox = [state]

    def timed_window(stepfn, rng_offset):
        # Same per-step span wrappers for every program under test: the
        # A/B must differ only in the step program, or the spans' host
        # cost lands on one side and biases the overhead.
        t0 = time.perf_counter()
        for i in range(args.steps):
            with obs_trace.span("wait_batch"):
                dev_batch = next(feed)
            with obs_trace.span("device_dispatch", step=i):
                sbox[0], metrics = stepfn(
                    sbox[0], dev_batch, jax.random.fold_in(rng, rng_offset + i)
                )
        jax.block_until_ready(metrics["loss"])
        return time.perf_counter() - t0

    # Round order ALTERNATES: a window drains the sample-ahead queue, so
    # whichever program runs second in a round starts starved and pays
    # extra stall — a systematic bias against it. Flipping the order each
    # round gives every program equal fresh-queue exposure, and the
    # best-of-N min on each side then converges to that program's true
    # window floor (the same estimator the guard A/B has always used).
    windows = {"headline": [], **{n: [] for n in alternates}}
    programs = [("headline", fns.train_step)] + list(alternates.items())
    for w in range(max(1, args.windows)):
        round_order = programs if w % 2 == 0 else programs[::-1]
        for j, (name, stepfn) in enumerate(round_order):
            trace_now = args.trace_dir if (w == 0 and name == "headline") else ""
            with _maybe_trace(trace_now):
                windows[name].append(
                    timed_window(stepfn, 1000 * (1 + j) + 50 * w)
                )
    state = sbox[0]
    best_dt = min(windows["headline"])

    def overhead_pct(name):
        return max(0.0, (min(windows[name]) / best_dt - 1.0) * 100.0)

    # Compute baseline gets the same best-of-N noise filter as the e2e
    # loop: a dispatch straggler landing in a single compute window would
    # inflate dt_compute while best_dt filtered it, understating stall_pct.
    dt_compute = None
    for w in range(max(1, args.windows)):
        state, dt_w = timed_resident_loop(
            state, args.steps, 1 if w == 0 else 0, resident=resident
        )
        dt_compute = dt_w if dt_compute is None else min(dt_compute, dt_w)

    # Health overhead is judged on the RESIDENT-batch floor, not the e2e
    # rate: on a 2-core host the e2e loop runs at the feeder's knife edge
    # (XLA compute and assembly threads share the cores), so any extra
    # device work is amplified nonlinearly into stall — that measures the
    # host's core budget, not the pack. The resident A/B pins one batch,
    # interleaves base/health windows with alternating order, and compares
    # window floors: the pack's actual program delta. The e2e health rate
    # stays in the detail line for the contention-inclusive picture.
    health_overhead = None
    if health_step is not None:
        state, metrics = health_step(
            state, resident, jax.random.fold_in(rng, 700)
        )
        jax.block_until_ready(metrics["loss"])
        # PER-STEP floor sampling, synced on every step: a shared-core
        # container steals CPU in bursts long enough to poison whole
        # 20-step windows, but a ~15 ms single step lands inside quiet
        # slots constantly — the min over hundreds of per-step samples on
        # each side converges to the quiet-host step latency no matter
        # the weather. The per-step sync cost is identical on both sides
        # of the A/B, so it cancels out of the ratio.
        floors = {"base": [], "health": []}
        for r in range(8):
            pair = [("base", fns.train_step), ("health", health_step)]
            if r % 2:
                pair = pair[::-1]
            for name, stepfn in pair:
                for i in range(max(args.steps, 25)):
                    t0 = time.perf_counter()
                    state, metrics = stepfn(
                        state, resident,
                        jax.random.fold_in(rng, 800 + 100 * r + i),
                    )
                    jax.block_until_ready(metrics["loss"])
                    floors[name].append(time.perf_counter() - t0)
        health_overhead = max(
            0.0, (min(floors["health"]) / min(floors["base"]) - 1.0) * 100.0
        )

    # Input-only drain: pull batches with no device step in the loop. This
    # is the pipeline's own sustained rate — the number the e2e ratio
    # converges to as the device step shrinks (a TPU step is ~8 ms; on a
    # CPU device the step dominates and hides most of the input delta).
    n_drain = args.steps * 2
    t0 = time.perf_counter()
    for _ in range(n_drain):
        next(feed)
    dt_drain = time.perf_counter() - t0

    e2e = args.steps / best_dt / n_chips
    compute_only = args.steps / dt_compute / n_chips
    stall_pct = max(0.0, 1.0 - dt_compute / best_dt) * 100
    detail = {
        "mode": "e2e_detail",
        "compute_only_steps_per_sec_per_chip": round(compute_only, 4),
        "e2e_steps_per_sec_per_chip": round(e2e, 4),
        "input_stall_pct": round(stall_pct, 2),
        "input_only_batches_per_sec": round(n_drain / dt_drain, 4),
        "packed": bool(args.packed),
        "model": args.model,
        "windows": max(1, args.windows),
    }
    if "guard" in alternates:
        e2e_guard = args.steps / min(windows["guard"]) / n_chips
        detail["e2e_guarded_steps_per_sec_per_chip"] = round(e2e_guard, 4)
        detail["guard_overhead_pct"] = round(overhead_pct("guard"), 2)
    if "mp" in alternates:
        # Mixed precision is a SPEEDUP candidate, not an overhead budget:
        # report the signed delta of the window floors (negative = mp
        # slower — expected on XLA:CPU hosts, which emulate bf16 via f32).
        e2e_mp = args.steps / min(windows["mp"]) / n_chips
        detail["e2e_mp_steps_per_sec_per_chip"] = round(e2e_mp, 4)
        detail["mp_speedup_pct"] = round(
            (best_dt / min(windows["mp"]) - 1.0) * 100.0, 2
        )
    if "health" in alternates:
        e2e_health = args.steps / min(windows["health"]) / n_chips
        detail["e2e_health_steps_per_sec_per_chip"] = round(e2e_health, 4)
        detail["e2e_health_overhead_pct"] = round(overhead_pct("health"), 2)
        overhead = round(health_overhead, 2)
        detail["health_overhead_pct"] = overhead
        detail["health_budget_pct"] = 2.0
        if overhead > 2.0:
            detail["health_over_budget"] = True
            print(
                f"bench: health-pack overhead {overhead}% exceeds the 2% "
                f"budget — the packed statistics grew, or the model is too "
                f"small for its param reductions to hide",
                file=sys.stderr,
            )
    print(json.dumps(detail), file=sys.stderr)
    metric = f"train_steps_per_sec_per_chip_e2e{variant}"
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(e2e, 4),
                "unit": "steps/s/chip",
            }
        )
    )
    _dump_host_trace()


def mfu_bench(args, fns, state, batch, rng, n_chips, timed_resident_loop,
              variant="", mp_step=None):
    """MFU = measured FLOP/s / peak FLOP/s, with FLOPs from XLA's own cost
    analysis of the compiled train step (fwd+bwd+update, the whole program).
    Peak comes from the device_kind table in rt1_tpu/obs/flops.py; a device
    that is not in it is an error, not a default.

    The estimator itself lives in rt1_tpu/obs/flops.py (shared with the
    train loop's live goodput/mfu gauge); this mode keeps the COMPILED
    (post-fusion) analysis path so published baselines stay comparable.

    With ``mp_step`` (--mixed_precision) the mixed-precision program is
    timed in windows INTERLEAVED with the configured step's, order
    alternating per round (the PR 5 drift-cancelling methodology), each
    side scored against its own compiled program's FLOPs; the comparison
    lands in the mfu_detail stderr line, the headline metric stays the
    configured step's.
    """
    import sys

    import jax

    from rt1_tpu.obs import flops as flops_lib

    peak = flops_lib.peak_flops(jax.devices()[0].device_kind)
    if peak is None:
        sys.exit(
            "bench: no peak FLOP/s known for device_kind "
            f"{jax.devices()[0].device_kind!r} — refusing to publish an MFU"
        )
    flops = flops_lib.train_step_flops(
        fns.train_step, state, batch, jax.random.fold_in(rng, 0), compile=True
    )
    if flops is None:
        # The estimator reports "no FLOPs" as None (right for the train
        # loop's live gauge, which just disarms); bench is a measurement
        # tool and must fail loudly rather than publish a silently-zero
        # MFU baseline.
        print(
            "bench: XLA cost analysis returned no FLOPs for the compiled "
            "train step — refusing to publish a zero MFU measurement",
            file=sys.stderr,
        )
        sys.exit(1)
    flops_mp = None
    if mp_step is not None:
        flops_mp = flops_lib.train_step_flops(
            mp_step, state, batch, jax.random.fold_in(rng, 0), compile=True
        )

    dt = None
    dt_mp = None
    for w in range(max(1, args.windows)):
        sides = [("base", None)]
        if mp_step is not None:
            sides.append(("mp", mp_step))
        if w % 2:
            sides = sides[::-1]
        for name, stepfn in sides:
            state, dt_w = timed_resident_loop(
                state, args.steps, args.warmup if w == 0 else 0,
                step_fn=stepfn,
            )
            if name == "base":
                dt = dt_w if dt is None else min(dt, dt_w)
            else:
                dt_mp = dt_w if dt_mp is None else min(dt_mp, dt_w)
    dt_per_step = dt / args.steps

    mfu = flops_lib.mfu_pct(flops, dt_per_step, n_chips, peak)
    detail = {
        "mode": "mfu_detail",
        **flops_lib.mfu_detail(flops, dt_per_step, n_chips, peak),
    }
    if dt_mp is not None:
        mp_per_step = dt_mp / args.steps
        detail["mp_step_ms"] = round(mp_per_step * 1e3, 3)
        detail["mp_speedup_pct"] = round((dt / dt_mp - 1.0) * 100.0, 2)
        detail["windows"] = max(1, args.windows)
        if flops_mp is not None:
            detail["mfu_mixed_precision"] = round(
                flops_lib.mfu_pct(flops_mp, mp_per_step, n_chips, peak), 3
            )
            detail["mp_flops_per_step"] = flops_mp
        else:
            # The timing A/B is already paid for and valid — publish it,
            # but say loudly why the mp MFU column is absent rather than
            # looking as if --mixed_precision was never passed.
            print(
                "bench: XLA cost analysis returned no FLOPs for the "
                "mixed-precision step — mp_step_ms/mp_speedup_pct are "
                "valid, mfu_mixed_precision omitted",
                file=sys.stderr,
            )
    print(json.dumps(detail), file=sys.stderr)
    print(
        json.dumps(
            {
                "metric": f"train_step_mfu{variant}",
                "value": round(mfu, 3),
                "unit": "%",
            }
        )
    )
    _dump_host_trace()


def env_bench(args):
    """Simulator control-step throughput on the host (no accelerator).

    Random actions, episode auto-reset on termination, observation render
    included — the per-step work the eval loop pays besides policy
    inference. Comparison point: the reference's step does IK + 24x
    `stepSimulation` in PyBullet plus a TINY_RENDERER render at the same
    10 Hz control rate.
    """
    import numpy as np

    from rt1_tpu.envs import LanguageTable, blocks
    from rt1_tpu.envs.rewards import BlockToBlockReward

    env = LanguageTable(
        block_mode=blocks.BlockMode.BLOCK_4,
        reward_factory=BlockToBlockReward,
        seed=0,
    )
    rng = np.random.default_rng(0)
    env.reset()
    for _ in range(20):  # warmup / first-episode setup out of the timing
        _, _, done, _ = env.step(rng.uniform(-0.03, 0.03, 2))
        if done:
            env.reset()
    # --steps means control steps here; the train modes' default (20) is
    # far too short for a stable host-sim number, so scale it 20x, keeping
    # the historical 400 at the default (ADVICE r3: --steps was ignored).
    n_steps = args.steps * 20
    t0 = time.perf_counter()
    for _ in range(n_steps):
        _, _, done, _ = env.step(rng.uniform(-0.03, 0.03, 2))
        if done:
            env.reset()
    dt = time.perf_counter() - t0
    sps = n_steps / dt
    print(
        json.dumps(
            {
                "metric": "env_control_steps_per_sec",
                "value": round(sps, 2),
                "unit": "steps/s",
            }
        )
    )


def infer_bench(args, model, rng, obs, actions, build_model_fn=None):
    """Control-step latency: one jitted infer_step per tick at batch 1.

    The reference's inference loop runs `tokens_per_action` (=3) full
    transformer passes per 10 Hz control step on GPU
    (`transformer_network.py:246-268`); ours is a single fused pass with a
    donated rolling state. Prints median latency in ms.
    """
    import statistics
    import jax

    # Parameter shapes are batch-independent: init at batch 1 / one frame of
    # context so startup does 1/48th of the full-batch tokenization work.
    obs1 = jax.tree.map(lambda x: x[:1, :1], obs)
    actions1 = jax.tree.map(lambda x: x[:1, :1], actions)
    model1 = model.clone(time_sequence_length=1)
    variables = model1.init({"params": rng, "crop": rng}, obs1, actions1, train=False)

    import functools

    @functools.partial(jax.jit, donate_argnums=(2,))
    def step(variables, observation, state):
        return model.apply(variables, observation, state, method=model.infer_step)

    frame = {
        "image": obs["image"][:1, 0],
        "natural_language_embedding": obs["natural_language_embedding"][:1, 0],
    }
    state = model.initial_state(batch_size=1)
    for _ in range(max(args.warmup, 1)):
        out, state = step(variables, frame, state)
    jax.block_until_ready(out["action_tokens"])

    times = []
    with _maybe_trace(args.trace_dir):
        for _ in range(args.steps):
            t0 = time.perf_counter()
            out, state = step(variables, frame, state)
            jax.block_until_ready(out["action_tokens"])
            times.append((time.perf_counter() - t0) * 1000.0)
    p50 = statistics.median(times)
    print(
        json.dumps(
            {
                "metric": f"infer_step_latency_p50_{args.attention_impl}",
                "value": round(p50, 3),
                "unit": "ms",
            }
        )
    )
    if args.inference_dtype:
        _infer_quant_ab(args, model, variables, frame, build_model_fn)
    if args.window_sweep:
        _infer_kvcache_sweep(args, build_model_fn)
    _dump_host_trace()


def _infer_quant_ab(args, model, variables, frame, build_model_fn=None):
    """Per-dtype control-step latency A/B through the low-precision
    serving path, interleaved-window methodology (PR 5/PR 8): rounds
    alternate the dtype order, each side reports its best (floor) window
    median — single uninterleaved windows are ±10% garbage under this
    host's bursty co-tenant CPU theft."""
    import statistics
    import sys

    import jax
    import numpy as np

    from rt1_tpu.models.quant import serving_preparer, tree_bytes

    dtypes = [d.strip() for d in args.inference_dtype.split(",") if d.strip()]
    host_masters = jax.tree.map(lambda x: np.asarray(x), variables)
    sides = {}
    for dtype in dtypes:
        prepare = serving_preparer(dtype)
        serving = prepare(host_masters) if prepare else host_masters
        # Each side gets a model at ITS serving compute dtype (f32 for the
        # f32 and int8 rows, bf16 for bf16) — independent of --dtype, so
        # the per-dtype columns can't silently measure the bench-wide
        # compute mode. A rebuild is needed because a constructed
        # tokenizer_def's dtype would survive model.clone().
        side_model = model
        if build_model_fn is not None:
            side_model = build_model_fn(
                "bfloat16" if dtype == "bf16" else "float32"
            )
        elif dtype == "bf16":
            side_model = model.clone(dtype=jax.numpy.bfloat16)

        def make_step(m):
            import functools

            @functools.partial(jax.jit, donate_argnums=(2,))
            def step(v, observation, state):
                return m.apply(
                    v, observation, state, method=m.infer_step
                )

            return step

        sides[dtype] = {
            "step": make_step(side_model),
            "variables": jax.device_put(serving),
            "state": side_model.initial_state(batch_size=1),
            "param_bytes": tree_bytes(serving),
            "window_medians": [],
        }
    # Warmup (the one compile per side), then interleaved windows.
    for side in sides.values():
        out, side["state"] = side["step"](
            side["variables"], frame, side["state"]
        )
        jax.block_until_ready(out["action_tokens"])
    rounds = 4
    window = max(args.steps // rounds, 8)
    order = list(sides)
    for round_i in range(rounds):
        for dtype in order if round_i % 2 == 0 else order[::-1]:
            side = sides[dtype]
            times = []
            for _ in range(window):
                t0 = time.perf_counter()
                out, side["state"] = side["step"](
                    side["variables"], frame, side["state"]
                )
                jax.block_until_ready(out["action_tokens"])
                times.append((time.perf_counter() - t0) * 1000.0)
            side["window_medians"].append(statistics.median(times))
    f32_bytes = (
        sides["f32"]["param_bytes"]
        if "f32" in sides
        else tree_bytes(host_masters)
    )
    per_dtype = {
        dtype: {
            "latency_p50_ms_floor": round(min(side["window_medians"]), 3),
            "window_medians_ms": [
                round(m, 3) for m in side["window_medians"]
            ],
            "param_bytes": side["param_bytes"],
            "byte_reduction_vs_f32": round(
                f32_bytes / side["param_bytes"], 3
            ),
        }
        for dtype, side in sides.items()
    }
    print(
        json.dumps(
            {
                "metric": "infer_quant_ab",
                "dtypes": dtypes,
                "per_dtype": per_dtype,
                "rounds": rounds,
                "window_steps": window,
                "timing_methodology": (
                    "interleaved windows, alternating dtype order per "
                    "round, best-of (floor) window median per side"
                ),
                "honesty_note": (
                    "XLA:CPU lacks native int8 matmul — the int8 side "
                    "pays an explicit dequant here, so its CPU latency "
                    "is an upper bound; param bytes is the measured win "
                    "and TPU (int8-fused dequant, native bf16 MXU) is "
                    "the latency projection"
                ),
            }
        ),
        file=sys.stderr,
    )


def _infer_kvcache_sweep(args, build_model_fn):
    """Cached-vs-windowed control-step latency across window lengths
    (ISSUE 17): at each `--window_sweep` length T, A/B the full-window
    `infer_step` against the KV-cached `infer_step_cached` with the
    interleaved-window methodology (alternating side order per round,
    best-of floor medians per side). The cached side is warmed past
    roll-over so it measures the steady shift-and-decode regime, not the
    (cheaper-looking) fill phase. Writes `BENCH_serve_kvcache.json` next
    to this script; the acceptance shape is a near-flat cached column
    while the windowed column grows with T."""
    import functools
    import statistics
    import sys

    import jax

    from rt1_tpu.specs import language_table_action_space, sample_space

    windows = sorted(
        {int(w) for w in args.window_sweep.split(",") if w.strip()}
    )
    rng = jax.random.PRNGKey(0)
    frame = {
        "image": jax.random.uniform(rng, (1, args.height, args.width, 3)),
        "natural_language_embedding": jax.random.normal(
            jax.random.fold_in(rng, 1), (1, 512)
        ),
    }
    rounds = 4
    window_steps = max(args.steps // rounds, 8)
    per_window = {}
    for seq_len in windows:
        m = build_model_fn(args.dtype).clone(time_sequence_length=seq_len)
        # Param shapes are window-independent (the position table is a
        # fixed max_seq_len=256 rows), so init at one frame of context —
        # the same startup trick as infer_bench. Both sides share one
        # variable tree: the decode branch reuses the training path's
        # submodule names, so the param trees are identical.
        m1 = m.clone(time_sequence_length=1)
        obs1 = {
            "image": frame["image"][:, None],
            "natural_language_embedding": (
                frame["natural_language_embedding"][:, None]
            ),
        }
        actions1 = sample_space(
            language_table_action_space(), jax.random.fold_in(rng, 2), (1, 1)
        )
        variables = m1.init(
            {"params": rng, "crop": rng}, obs1, actions1, train=False
        )

        def make_step(method, model=m):
            @functools.partial(jax.jit, donate_argnums=(2,))
            def step(v, observation, state):
                return model.apply(v, observation, state, method=method)

            return step

        sides = {
            "windowed": {
                "step": make_step(m.infer_step),
                "state": m.initial_state(batch_size=1),
                "window_medians": [],
            },
            "cached": {
                "step": make_step(m.infer_step_cached),
                "state": m.initial_state(batch_size=1, cached=True),
                "window_medians": [],
            },
        }
        # Warmup: the one compile per side, then step PAST roll-over so
        # the cached side's timings are the steady post-fill regime.
        for side in sides.values():
            for _ in range(seq_len + 2):
                out, side["state"] = side["step"](
                    variables, frame, side["state"]
                )
            jax.block_until_ready(out["action_tokens"])
        order = list(sides)
        for round_i in range(rounds):
            for name in order if round_i % 2 == 0 else order[::-1]:
                side = sides[name]
                times = []
                for _ in range(window_steps):
                    t0 = time.perf_counter()
                    out, side["state"] = side["step"](
                        variables, frame, side["state"]
                    )
                    jax.block_until_ready(out["action_tokens"])
                    times.append((time.perf_counter() - t0) * 1000.0)
                side["window_medians"].append(statistics.median(times))
        row = {
            name: {
                "latency_p50_ms_floor": round(
                    min(side["window_medians"]), 3
                ),
                "window_medians_ms": [
                    round(x, 3) for x in side["window_medians"]
                ],
            }
            for name, side in sides.items()
        }
        row["speedup_windowed_over_cached"] = round(
            row["windowed"]["latency_p50_ms_floor"]
            / row["cached"]["latency_p50_ms_floor"],
            3,
        )
        per_window[str(seq_len)] = row

    lo, hi = str(windows[0]), str(windows[-1])

    def growth(side):
        return round(
            per_window[hi][side]["latency_p50_ms_floor"]
            / per_window[lo][side]["latency_p50_ms_floor"],
            3,
        )

    record = {
        "metric": "serve_kvcache_cached_latency_growth",
        "value": growth("cached"),
        "unit": "x",
        "windows": windows,
        "per_window": per_window,
        "cached_latency_growth": growth("cached"),
        "windowed_latency_growth": growth("windowed"),
        "model": args.model,
        "attention_impl": args.attention_impl,
        "dtype": args.dtype,
        "image_hw": [args.height, args.width],
        "rounds": rounds,
        "window_steps": window_steps,
        "headline": (
            f"window {windows[0]}->{windows[-1]}: cached per-step latency "
            f"grows {growth('cached')}x vs {growth('windowed')}x windowed "
            "(near-flat cached = per-step device work is O(frame), not "
            "O(window))"
        ),
        "timing_methodology": (
            "interleaved windows, alternating side order per round, "
            "best-of (floor) window median per side; cached side warmed "
            "past window roll-over (steady shift-and-decode regime)"
        ),
    }
    print(json.dumps(record), file=sys.stderr)
    out_path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_serve_kvcache.json",
    )
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"bench: wrote {out_path}", file=sys.stderr)


if __name__ == "__main__":
    main()

"""Capture one profiler trace of the train step: the device's ops under
their scopes and the program's host spans, in one file on one clock.

The reference has no profiling story beyond Lightning's progress bar
(SURVEY.md §5 "Tracing/profiling"); Stack B wraps steps in
`jax.profiler.StepTraceAnnotation` (`language_table/train/train.py:182`).
This script is the deep-dive companion: it traces N real train steps with
`jax.profiler.start_trace` (an `.xplane.pb` viewable in TensorBoard's
profile plugin or Perfetto). The device's ops carry the scope that made
them (Flax names every module; `preprocess`, `loss`, `optimizer`, `health`,
`cast_bf16` name the rest), and the program's own spans (`rt1/feeder/*`,
`rt1/h2d/put`: `rt1_tpu/obs/trace.py`) lie in the same file on the lines of
the threads that opened them — with `--packed`, the sample-ahead feeder's
workers. `benchmarks/trace/program.py` reduces such a file to device time a
step per scope group and a table of the spans; docs/observability.md,
"One profile", says how to read it.

Model/state construction reuses `train.build_model` + the trainer helpers
— the profiled step is the REAL config's step (`--model tiny` profiles
`configs/tiny.py` at bench geometry, `flagship` the reference-parity B3),
not a hand-rolled copy that can drift.

Run (claims the TPU):
  python scripts/profile_train.py --logdir /tmp/rt1_trace --steps 5
CPU tiny config over the PR 2 packed data path:
  JAX_PLATFORMS=cpu python scripts/profile_train.py --model tiny --packed \
      --logdir /tmp/rt1_trace --steps 5
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--logdir", default="/tmp/rt1_trace")
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--batch", type=int, default=8)
    p.add_argument(
        "--model", default="flagship", choices=["flagship", "tiny"],
        help="Config under the profiler: 'flagship' = configs/language_table"
             ".py (reference-parity B3), 'tiny' = configs/tiny.py (CPU-"
             "runnable).")
    p.add_argument(
        "--height", type=int, default=0,
        help="Image height (0 = the chosen config's data.height).")
    p.add_argument(
        "--width", type=int, default=0,
        help="Image width (0 = the chosen config's data.width).")
    p.add_argument(
        "--packed", action="store_true",
        help="Feed the profiled steps from the packed mmap cache via the "
             "sample-ahead feeder (bench.py --mode e2e --packed data path) "
             "instead of a resident synthetic batch, so the trace covers "
             "wait/H2D and the feeder threads.")
    p.add_argument(
        "--data_dir", default="/tmp/rt1_bench_episodes",
        help="--packed: episode corpus dir (synthesized on first run, "
             "shared with bench.py).")
    p.add_argument(
        "--episodes", type=int, default=24, help="--packed: corpus size.")
    p.add_argument("--src_height", type=int, default=180)
    p.add_argument(
        "--src_width", type=int, default=320,
        help="--packed: synthetic corpus SOURCE frame size (see bench.py).")
    args = p.parse_args()

    import jax

    from rt1_tpu.compilation_cache import enable_persistent_cache

    enable_persistent_cache()

    from rt1_tpu.parallel import MeshConfig, make_mesh
    from rt1_tpu.specs import language_table_action_space, sample_space
    from rt1_tpu.trainer import (
        create_train_state,
        make_optimizer,
        make_train_step_fns,
    )
    from rt1_tpu.trainer.metrics import step_trace
    from rt1_tpu.train.train import build_model

    if args.model == "tiny":
        from rt1_tpu.train.configs import tiny as config_module
    else:
        from rt1_tpu.train.configs import language_table as config_module
    config = config_module.get_config()
    mc = config.model
    # Bench-geometry sequence length (matches the packed caches bench.py
    # builds, so --packed reuses its corpus instead of re-packing).
    mc.time_sequence_length = 6
    height = args.height or config.data.height
    width = args.width or config.data.width

    model = build_model(mc)
    rng = jax.random.PRNGKey(0)
    b, t = args.batch, mc.time_sequence_length
    obs = {
        "image": jax.random.uniform(rng, (b, t, height, width, 3)),
        "natural_language_embedding": jax.random.normal(
            jax.random.fold_in(rng, 1), (b, t, 512)
        ),
    }
    actions = sample_space(
        language_table_action_space(), jax.random.fold_in(rng, 2), (b, t)
    )
    mesh = make_mesh(MeshConfig())
    state = create_train_state(model, rng, (obs, actions), make_optimizer())
    fns = make_train_step_fns(model, mesh, state)
    state = fns.shard_state(state)

    if args.packed:
        # The exact bench feed (packed cache + sample-ahead feeder +
        # double-buffered H2D), built by bench.py's own helper.
        import bench as bench_module

        feed_args = argparse.Namespace(
            data_dir=args.data_dir,
            episodes=args.episodes,
            src_height=args.src_height,
            src_width=args.src_width,
            packed=True,
            height=height,
            width=width,
            batch=b,
        )
        feed = bench_module._e2e_feed(feed_args, fns)

        def next_batch():
            return next(feed)

    else:
        resident = fns.shard_batch((obs, actions))

        def next_batch():
            return resident

    for i in range(args.warmup):
        state, metrics = fns.train_step(
            state, next_batch(), jax.random.fold_in(rng, i)
        )
        jax.block_until_ready(metrics["loss"])

    jax.profiler.start_trace(args.logdir)
    times = []
    for i in range(args.steps):
        with step_trace("train", i):
            t0 = time.perf_counter()
            dev_batch = next_batch()
            state, metrics = fns.train_step(
                state, dev_batch, jax.random.fold_in(rng, 100 + i)
            )
            jax.block_until_ready(metrics["loss"])
            times.append(time.perf_counter() - t0)
    jax.profiler.stop_trace()

    for i, dt in enumerate(times):
        print(f"step {i}: {dt * 1e3:.2f} ms")
    # The one file, reduced: device time a step by scope group (on a chip;
    # the CPU's profile has no device plane) and the program's spans.
    from benchmarks.trace import program, xplane

    for line in program.describe(program.reduce_xplane(xplane.find_xplane(args.logdir))):
        print(line)
    print(
        f"trace written to {args.logdir} — device ops under their scopes and "
        "the program's rt1/* host spans in one xplane.pb (reduced above by "
        "benchmarks/trace/program.py): view it with TensorBoard's profile "
        "plugin (docs/observability.md, 'One profile')."
    )


if __name__ == "__main__":
    main()

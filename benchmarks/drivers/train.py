"""The window loop of ``kind: train`` mixes.

Set-up builds ONE object, the compiled step with its state
(benchmarks/program.py), drives it from the seed through its first steps
through the window's own call and feed, and hands the same object to the
window.  The window dispatches step i, then blocks on step i-1's loss (one
step in flight, as a loop that logs does) and stamps the completion.
"""

from __future__ import annotations

import contextlib
import gc
import time
from typing import Any, Callable, Dict, List, Optional

CHECK_STEPS = 3      # the reference follows these
WARM_STEPS = 2       # further steps before the window, after the host reads


def _annotator(on: bool) -> Optional[Callable[[str], Any]]:
    if not on:
        return None
    import jax

    return lambda name: jax.profiler.TraceAnnotation(name)


def first_steps(prog, one_step: Callable[[int], Any]):
    """Drive the program through the steps the reference follows.  Returns
    each step's loss, Adam's first moment after the first step and the
    parameters after the last, read to the host before the next step's
    donation takes them."""
    import jax

    from benchmarks import check

    losses: List[float] = []
    mu1 = None
    for i in range(CHECK_STEPS):
        metrics = one_step(i)
        losses.append(float(jax.device_get(metrics["loss"])))
        if i == 0:
            mu1 = jax.device_get(check.adam_mu(prog.state.opt_state))
    return losses, mu1, jax.device_get(prog.state.params)


def run(ctx) -> Dict[str, Any]:
    import jax
    import numpy as np

    from rt1_tpu.data.pipeline import device_feeder

    from benchmarks import check, devices, program, stats, traffic, weights

    log = ctx.log
    counter = devices.CompileCounter()
    config_file, mix = ctx.config_file, ctx.traffic
    config = program.program_config(config_file)
    annotate = _annotator(ctx.trace)
    span = annotate if annotate is not None else (lambda _n: contextlib.nullcontext())

    t0 = time.perf_counter()
    feed = traffic.build_train_feed(
        ctx.root, mix, config, ctx.seed, program.wants_task_ids(config)
    )
    t_feed = time.perf_counter()
    prog = program.build(config_file, ctx.seed, feed.health_task_names)
    t_built = time.perf_counter()
    host = traffic.TimedIterator(feed.host_iter, annotate)
    host.keep = CHECK_STEPS
    dev_iter = device_feeder(
        host, prog.fns.batch_sharding, depth=int(mix.get("device_feeder_depth", 2))
    )
    base_key = weights.seed_key(ctx.seed)
    batch_size = int(config.per_host_batch_size)
    h2d_s = [0.0]

    def one_step(i: int):
        t = time.perf_counter()
        with span("bench/h2d"):
            batch = next(dev_iter)
        h2d_s[0] += time.perf_counter() - t
        with span("bench/dispatch"):
            return prog.step(batch, jax.random.fold_in(base_key, i))

    # -- first steps: what the reference follows, through the window's call
    losses, mu1, params3 = first_steps(prog, one_step)
    skips = int(jax.device_get(prog.skips)) if prog.skips is not None else 0
    for i in range(CHECK_STEPS, CHECK_STEPS + WARM_STEPS):
        one_step(i)["loss"].block_until_ready()
    first_batches = list(host.taps)
    host.taps = []
    t_warm = time.perf_counter()
    log(f"set-up: feed {t_feed - t0:.1f}s, build {t_built - t_feed:.1f}s, "
        f"first {CHECK_STEPS + WARM_STEPS} steps {t_warm - t_built:.1f}s; "
        f"compile events so far {counter.snapshot()}")

    # -- the window.  Tracing the step leaves some 600,000 objects behind,
    # and the first full collection over them stops the loop for 0.7-2 s
    # (PERF.md section 5).  A training job pays that once, soon after its
    # start; a window that opens right after set-up would pay it in one run
    # of three.  So the one full collection is made here, as part of warming
    # up.  Nothing is frozen: from here on the collector runs as it does in
    # the trainer's own loop, and what it costs is in the window.
    gc.collect()
    pauses: List[float] = []
    full_collections: List[float] = []
    clock = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            clock[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - clock[0])
            if info.get("generation") == 2:
                full_collections.append(pauses[-1])

    gc.callbacks.append(on_gc)
    before = counter.snapshot()
    wait0, h2d0, calls0 = host.wait_s, h2d_s[0], host.calls
    tracer = ctx.make_tracer() if ctx.trace else None
    completions: List[float] = []
    pending = None
    i = CHECK_STEPS + WARM_STEPS
    first_dispatch = time.perf_counter()
    setup_s = first_dispatch - ctx.t_process_start + ctx.clock_offset
    while True:
        now = time.perf_counter()
        if now - first_dispatch >= ctx.seconds:
            break
        if tracer is not None:
            tracer.tick(now - first_dispatch, completions, host, h2d_s[0])
        metrics = one_step(i)
        i += 1
        if pending is not None:
            with span("bench/sync"):
                pending["loss"].block_until_ready()
            completions.append(time.perf_counter())
        pending = metrics
    with span("bench/sync"):
        pending["loss"].block_until_ready()
    completions.append(time.perf_counter())
    if tracer is not None:
        tracer.finish(completions, host, h2d_s[0])
    after = counter.snapshot()
    gc.callbacks.remove(on_gc)
    log(f"python gc in the window: {len(pauses)} collections, longest "
        f"{max(pauses, default=0.0) * 1e3:.1f} ms, {sum(pauses) * 1e3:.1f} ms in all; "
        f"full collections {len(full_collections)}"
        f"{' of ' + ', '.join(f'{p * 1e3:.0f} ms' for p in full_collections) if full_collections else ''}")
    last_loss = float(jax.device_get(pending["loss"]))
    skips_end = int(jax.device_get(prog.skips)) if prog.skips is not None else 0

    in_window = {k: after[k] - before[k] for k in ("traces", "compiles")}
    window = stats.train_window_metrics(first_dispatch, completions, batch_size)
    steps = window["steps"]
    log(f"window: {steps} steps of batch {batch_size} in {window['span_s']:.3f}s; "
        f"step interval p50 {window['train_step_ms_p50']:.3f} ms, p95 "
        f"{window['train_step_ms_p95']:.3f} ms, max {window['train_step_ms_max']:.3f} ms "
        f"over {window['intervals']} intervals; compile events in window {in_window}")
    log(f"host, mean per step over the window: feeder wait "
        f"{(host.wait_s - wait0) / max(1, host.calls - calls0) * 1e3:.3f} ms, "
        f"next(dev_iter) {(h2d_s[0] - h2d0) / steps * 1e3:.3f} ms")

    mem = devices.memory(jax.local_devices())
    log(f"memory: peak_bytes_in_use {mem['peak_bytes_in_use']}, peak_bytes_reserved "
        f"{mem['peak_bytes_reserved']}, bytes_limit {mem['bytes_limit']}")

    # -- free the program, then let the reference follow the first steps
    feed.close()
    abstract = (prog.abstract_params, prog.abstract_batch_stats)
    del prog, dev_iter, pending, metrics
    checks = check.compare_training(
        config_file=config_file, abstract=abstract, seed=ctx.seed,
        batches=first_batches, losses=losses, mu1=mu1, params3=params3, log=log,
    )
    harness_faults = []
    if in_window["traces"] or in_window["compiles"]:
        harness_faults.append(f"compiled inside the window: {in_window}")
    if skips or skips_end:
        harness_faults.append(f"the guard skipped {skips_end} update(s)")
    if not np.isfinite(last_loss):
        harness_faults.append("the last loss of the window is not finite")

    return {
        "attempted": steps,
        "failed": skips_end,
        "checks": checks,
        "harness_faults": harness_faults,
        "end_to_end": {
            "setup_s": setup_s,
            "train_samples_per_s": window["train_samples_per_s"],
            "train_step_ms_p95": window["train_step_ms_p95"],
        },
        "memory": mem,
        "trace": tracer.summary() if tracer is not None else None,
        "batch": batch_size,
    }

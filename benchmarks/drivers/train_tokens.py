"""The window loop of ``kind: train_tokens`` mixes: a language-model family
trained on packed token sequences.

The same window as drivers/train.py (dispatch step i, block on step i-1's
loss, stamp; one full collection before the window; a compile inside the
window is a fault; guard skips count as ``failed``) and the same ``correct``
(the window's own compiled step through its first three steps, then the plain
reference over the same batches).  What differs is what a batch is: the
program is built here from a token batch's shapes, the feed is the program's
packed-token feed, and the reference is handed (tokens, targets).  A traced
run also reduces the profile by scope (trace/scopes_lm.json) and reads the
step's counters over the traced steps, for the per-layer readers.

A configuration's file may bring scopes and counters of its own, and readers
under benchmarks/metrics/ for them, with no edit here:

* ``scope_tallies``: rules as in scopes_lm.json (``group``, ``pattern``,
  ``why``), counted beside the family's: the family's rules still partition
  the step (``trace["program"]["scope_s"]``), and an op's self time is added
  to every tally whose pattern its scope holds
  (``trace["program"]["tally_s"][group]``);
* ``counters``: names of further step metrics, logged every step of the
  window; ``trace["counters"][name]`` is the mean over the traced steps.  A
  name the step does not return is a fault of the run.
"""

from __future__ import annotations

import contextlib
import gc
import os
import time
from typing import Any, Dict, List

from benchmarks.drivers.train import CHECK_STEPS, WARM_STEPS, _annotator, first_steps

SCOPE_RULES = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "trace", "scopes_lm.json")
# what every routed decoder's step returns; a step without them logs none
COUNTERS = ("moe/assignments_held", "moe/load_max_over_mean")
HELD, LOAD = COUNTERS


def batch_spec(config, seq_len: int):
    """Abstract (observations, actions) of one batch, as the token feed emits it."""
    import jax
    import jax.numpy as jnp

    shape = (int(config.per_host_batch_size), int(seq_len))
    return ({"tokens": jax.ShapeDtypeStruct(shape, jnp.int32)},
            {"targets": jax.ShapeDtypeStruct(shape, jnp.int32)})


def build_program(config_file: Dict[str, Any], seed: int, seq_len: int):
    """benchmarks/program.py::build for a family whose batch is token ids."""
    import jax
    import jax.numpy as jnp

    from rt1_tpu import obs, resilience
    from rt1_tpu.parallel import mixed_precision_from_config
    from rt1_tpu.trainer import create_train_state, make_train_step_fns

    from benchmarks import program

    config = program.program_config(config_file)
    plan, model, init_fn, loss_fn, tx = program.build_model(config)
    observations, actions = batch_spec(config, seq_len)
    shapes = jax.eval_shape(
        lambda r, o, a: create_train_state(model, r, (o, a), tx, init_fn=init_fn),
        jax.ShapeDtypeStruct((2,), jnp.uint32), observations, actions)
    gains = program.weight_gains(config_file)
    state = program.initial_state(shapes.params, shapes.batch_stats, tx, seed, gains)
    res_opts = resilience.ResilienceOptions.from_config(config)
    obs_opts = obs.ObsOptions.from_config(config, "")
    fns = make_train_step_fns(
        model, plan.mesh, state, accum_steps=config.accum_steps, loss_fn=loss_fn,
        guard_nonfinite=res_opts.guard, guard_grad_norm_max=res_opts.guard_grad_norm_max,
        model_health=obs_opts.model_health, health_group_depth=obs_opts.health_group_depth,
        plan=plan, mixed_precision=mixed_precision_from_config(config), check_coverage=True)
    return program.Program(
        config=config, model=model, fns=fns, state=fns.shard_state(state),
        skips=fns.init_guard_skips() if fns.guarded else None,
        abstract_params=shapes.params, abstract_batch_stats=shapes.batch_stats, gains=gains)


def build_feed(mix: Dict[str, Any], config, seed: int):
    from rt1_tpu.data.tokens import PackedTokenFeed

    corpus = mix["corpus"]
    return PackedTokenFeed(
        batch_size=int(config.per_host_batch_size), seq_len=int(mix["seq_len"]),
        vocab=int(config.model.lm.vocab_held), seed=int(seed),
        corpus_seed=corpus["corpus_seed"], documents=corpus["documents"],
        doc_len_median=corpus["doc_len_median"], doc_len_sigma=corpus["doc_len_sigma"],
        doc_len_min=corpus["doc_len_min"], depth=int(mix.get("feed_depth", 2)))


def token_batch(host_batch):
    """(observations, actions) as the reference takes them."""
    return host_batch["observations"], host_batch["actions"]


def reference_readings(config_file, abstract, seed, batches, prec: str, log) -> Dict[str, Any]:
    """check.reference_readings for (tokens, targets) batches."""
    from benchmarks import check

    return check.reference_readings(
        config_file, abstract, seed, batches, prec, log, as_model=token_batch)


def counter_names(config_file: Dict[str, Any], metrics):
    """(the step metrics logged every step, the configuration's ``counters``
    that the step does not return).  Logged: the routed layers' two where the
    step returns them, then the configuration's own."""
    own = list(config_file.get("counters", []))
    missing = [k for k in own if k not in metrics]
    names = [k for k in COUNTERS if k in metrics and k not in own]
    return names + [k for k in own if k in metrics], missing


def _traced_counters(names, counter_log, first: int, count: int, config, mix) -> Dict[str, float]:
    """Each logged counter by its name, mean over the steps of the traced
    slice, beside the shapes the readers divide by."""
    import jax
    import numpy as np

    lm = config.model.lm
    layer_types = list(lm.layer_types)
    routed_layers = len(layer_types) - int(lm.num_dense_layers)
    tokens = int(config.per_host_batch_size) * int(mix["seq_len"])
    out = {"routed_layers": routed_layers, "seq_len": int(mix["seq_len"]),
           "attention_layers": layer_types.count("full_attention"),
           "assignments_total": float(tokens * int(lm.num_experts_per_tok) * routed_layers)}
    steps = counter_log[first:first + count] or counter_log[-1:]
    if names and steps:
        values = np.asarray(jax.device_get(steps), np.float64)
        out.update(zip(names, values.mean(axis=0).tolist()))
    return out


def reduce_profile(events, scopes, config_file) -> Dict[str, Any]:
    """The device's time by the family's scope groups, and by the
    configuration's own tallies beside them."""
    from benchmarks.trace import program as trace_program

    return trace_program.reduce_events(
        events, scopes, trace_program.load_rules(SCOPE_RULES),
        trace_program.compile_rules(config_file.get("scope_tallies", [])))


def run(ctx) -> Dict[str, Any]:
    import jax
    import numpy as np

    from rt1_tpu.data.pipeline import device_feeder

    from benchmarks import check, devices, program, stats, traffic, weights
    from benchmarks.trace import program as trace_program
    from benchmarks.trace import xplane

    log = ctx.log
    counter = devices.CompileCounter()
    config_file, mix = ctx.config_file, ctx.traffic
    config = program.program_config(config_file)
    annotate = _annotator(ctx.trace)
    span = annotate if annotate is not None else (lambda _n: contextlib.nullcontext())

    t0 = time.perf_counter()
    feed = build_feed(mix, config, ctx.seed)
    t_feed = time.perf_counter()
    prog = build_program(config_file, ctx.seed, int(mix["seq_len"]))
    t_built = time.perf_counter()
    host = traffic.TimedIterator(feed, annotate)
    host.keep = CHECK_STEPS
    dev_iter = device_feeder(
        host, prog.fns.batch_sharding, depth=int(mix.get("device_feeder_depth", 2)))
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
        metrics = one_step(i)
        metrics["loss"].block_until_ready()
    names, no_such_counter = counter_names(config_file, metrics)
    first_batches = list(host.taps)
    host.taps = []
    t_warm = time.perf_counter()
    log(f"set-up: feed {t_feed - t0:.1f}s, build {t_built - t_feed:.1f}s, "
        f"first {CHECK_STEPS + WARM_STEPS} steps {t_warm - t_built:.1f}s; "
        f"compile events so far {counter.snapshot()}")

    # -- the window (drivers/train.py says why the one full collection is here)
    gc.collect()
    pauses: List[float] = []
    clock = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            clock[0] = time.perf_counter()
        else:
            pauses.append(time.perf_counter() - clock[0])

    gc.callbacks.append(on_gc)
    before = counter.snapshot()
    wait0, h2d0, calls0 = host.wait_s, h2d_s[0], host.calls
    tracer = ctx.make_tracer() if ctx.trace else None
    completions: List[float] = []
    counter_log: List[Any] = []
    traced_from = 0
    pending = None
    i = CHECK_STEPS + WARM_STEPS
    first_dispatch = time.perf_counter()
    setup_s = first_dispatch - ctx.t_process_start + ctx.clock_offset
    while True:
        now = time.perf_counter()
        if now - first_dispatch >= ctx.seconds:
            break
        if tracer is not None:
            was = tracer.state
            tracer.tick(now - first_dispatch, completions, host, h2d_s[0])
            if was == "waiting" and tracer.state == "tracing":
                traced_from = len(completions)
        metrics = one_step(i)
        counter_log.append([metrics[k] for k in names])
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
        f"{max(pauses, default=0.0) * 1e3:.1f} ms, {sum(pauses) * 1e3:.1f} ms in all")
    last_loss = float(jax.device_get(pending["loss"]))
    skips_end = int(jax.device_get(prog.skips)) if prog.skips is not None else 0

    in_window = {k: after[k] - before[k] for k in ("traces", "compiles")}
    window = stats.train_window_metrics(first_dispatch, completions, batch_size)
    steps = window["steps"]
    log(f"window: {steps} steps of {batch_size} sequences x {mix['seq_len']} tokens in "
        f"{window['span_s']:.3f}s; step interval p50 {window['train_step_ms_p50']:.3f} ms, p95 "
        f"{window['train_step_ms_p95']:.3f} ms, max {window['train_step_ms_max']:.3f} ms "
        f"over {window['intervals']} intervals; compile events in window {in_window}")
    log(f"host, mean per step over the window: feeder wait "
        f"{(host.wait_s - wait0) / max(1, host.calls - calls0) * 1e3:.3f} ms, "
        f"next(dev_iter) {(h2d_s[0] - h2d0) / steps * 1e3:.3f} ms; padding share of the "
        f"sequences packed so far {feed.padding_share * 100:.2f} %")
    if HELD in names and LOAD in names:
        # what a step's time follows: interval j ends with step j + 1's completion
        values = np.asarray(jax.device_get(counter_log), np.float64)
        intervals = np.diff(np.asarray(completions)) * 1e3
        rows = values[1:len(intervals) + 1, names.index(HELD)]
        load = values[:, names.index(LOAD)]
        keep = intervals < 2 * np.median(intervals)      # not the profiler's stop
        slope = np.polyfit(rows[keep], intervals[keep], 1)[0] if keep.sum() > 2 else float("nan")
        q = np.percentile(intervals[keep], [0, 25, 50, 75, 100])
        log(f"intervals, ms: min {q[0]:.3f}, quartiles {q[1]:.3f} / {q[2]:.3f} / {q[3]:.3f}, max "
            f"{q[4]:.3f}; moe/assignments_held a step {rows.min():.0f}-{rows.max():.0f} (mean "
            f"{rows.mean():.0f}), an interval grows {slope * 1e3:.3f} us a row held (correlation "
            f"{np.corrcoef(rows[keep], intervals[keep])[0, 1]:.2f}); moe/load_max_over_mean "
            f"{load.min():.3f}-{load.max():.3f}")

    mem = devices.memory(jax.local_devices())
    log(f"memory: peak_bytes_in_use {mem['peak_bytes_in_use']}, peak_bytes_reserved "
        f"{mem['peak_bytes_reserved']}, bytes_limit {mem['bytes_limit']}")

    trace_summary = None
    if tracer is not None:
        # the program's own scopes, before summary() removes the profile
        events, scopes = trace_program.events_from_xplane(xplane.find_xplane(tracer.dir))
        by_scope = reduce_profile(events, scopes, config_file)
        del events, scopes
        for line in trace_program.describe(by_scope):
            log(line)
        trace_summary = tracer.summary()
        trace_summary["program"] = {
            k: by_scope.get(k) for k in ("step_program", "runs", "scope_s", "tally_s",
                                         "op_self_s", "step_s", "spans")}
        trace_summary["counters"] = _traced_counters(
            names, counter_log, traced_from, int(tracer.slice["steps"]), config, mix)
        log("counters, mean over the traced steps: " + ", ".join(
            f"{k} {trace_summary['counters'][k]:.6g}" for k in names))

    # -- free the program, then let the reference follow the first steps
    feed.close()
    abstract = (prog.abstract_params, prog.abstract_batch_stats)
    del prog, dev_iter, pending, metrics, counter_log
    checks = check.compare_training(
        config_file=config_file, abstract=abstract, seed=ctx.seed, batches=first_batches,
        losses=losses, mu1=mu1, params3=params3, log=log, as_model=token_batch)
    harness_faults = []
    if no_such_counter:
        harness_faults.append(f"the configuration's counters {no_such_counter} are not among "
                              f"the step's metrics")
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
        "trace": trace_summary,
        "batch": batch_size,
    }

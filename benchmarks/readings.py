"""Readings that the limits of ``correct`` are set from, many seeds in one
process (one compile of the step, one of the reference, one of the control):

    python benchmarks/readings.py <workload> <seed> [<seed> ...] [--control] [--fault half_batch]

For each seed: the cell's own program at the cell's own size, driven through
its first three steps by its own feed, against the plain reference; with
``--control`` also the reference one precision down in the program's place;
with ``--fault`` the program with that fault planted under it.  Each is judged
by ``check.judge`` with the cell's own limits, as a run judges the program.
One JSON line per seed.  This is a tool, not part of a run: BENCHMARK.json's
command never calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("half_batch",))
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from rt1_tpu.data.pipeline import device_feeder

    from benchmarks import check, devices, program, run, traffic, weights
    from benchmarks.drivers import train

    manifest = run.load_manifest(ROOT)
    cell = run.find_cell(manifest, args.workload)
    config_file = program.load_config_file(os.path.join(ROOT, run.config_path(manifest, cell["config"])))
    mix = traffic.load_traffic_file(os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json"))
    device = devices.describe(int(cell["chips"]))
    devices.enable_compile_cache(ROOT)
    config = program.program_config(config_file)
    prog = None

    def first_half_twice(x):
        h = x.shape[0] // 2
        return jax.device_put(jnp.concatenate([x[:h], x[:h]]), x.sharding)

    for seed in args.seeds:
        feed = traffic.build_train_feed(ROOT, mix, config, seed, program.wants_task_ids(config))
        if prog is None:
            prog = program.build(config_file, seed, feed.health_task_names)
        else:
            prog.reset(seed)
        host = traffic.TimedIterator(feed.host_iter)
        host.keep = train.CHECK_STEPS
        dev_iter = device_feeder(host, prog.fns.batch_sharding, depth=2)
        base = weights.seed_key(seed)

        def one_step(i):
            batch = next(dev_iter)
            if args.fault == "half_batch":
                batch = jax.tree.map(first_half_twice, batch)
            return prog.step(batch, jax.random.fold_in(base, i))

        losses, mu1, params3 = train.first_steps(prog, one_step)
        skips = int(jax.device_get(prog.skips)) if prog.skips is not None else 0
        batches = list(host.taps)
        feed.close()
        del dev_iter
        abstract = (prog.abstract_params, prog.abstract_batch_stats)
        log = lambda m: print(f"[readings] {m}", file=sys.stderr, flush=True)  # noqa: E731
        reading = check.program_readings(abstract, seed, config_file, losses, mu1, params3)
        ref = check.reference_readings(config_file, abstract, seed, batches, "highest", log)
        limits = config_file["limits"]

        def judged(side):
            nums = check.numbers(side, ref)
            verdict = check.judge(nums, limits)
            return {"correct": all(c["ok"] for c in verdict),
                    "over": [c["name"] for c in verdict if not c["ok"]],
                    "numbers": {k: list(v) for k, v in nums.items()}}

        out = {"seed": seed, "device": device["kind"], "fault": args.fault, "skips": skips,
               "limits": limits, "program": judged(reading),
               "losses": losses, "reference_losses": ref["losses"]}
        if args.control:
            precision = config_file["control_precision"]
            out["control_precision"] = precision
            out["control"] = judged(check.reference_readings(
                config_file, abstract, seed, batches, precision, log))
        log(f"seed {seed}: program{' with ' + args.fault if args.fault else ''} correct "
            f"{out['program']['correct']} {out['program']['over']}"
            + (f"; control {out['control_precision']} correct {out['control']['correct']} "
               f"{out['control']['over']}" if args.control else ""))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

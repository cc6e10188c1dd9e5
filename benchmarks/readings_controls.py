"""benchmarks/readings_tokens.py for a configuration that has controls of its
own: the readings that the limits of ``correct`` are set from, many seeds in
one process (one compile of the step, one of the reference, one of each
control):

    python benchmarks/readings_controls.py <workload> <seed> [<seed> ...]
        [--control <name> ...] [--fault half_targets|one_leaf] [--leaf <path>]
        [--assignments]

For each seed: the cell's own program at the cell's own size, driven through
its first three steps by its own feed, against the plain reference; for each
``--control`` the reference computed as that name says, in the program's place
(the configuration's ``control_precision``, or one of its
``mechanism_controls``: the reference takes the name as its precision
argument); with ``--fault`` the program with that fault planted under it:
``half_targets`` hides every second target from the program (a cell whose
batch is one sequence has no half batch to leave out), ``one_leaf`` puts the
leaf whose path holds ``--leaf`` back after every step.  Each is judged by
``check.judge`` with the cell's own limits, as a run judges the program.  One
JSON line per seed.  A tool, not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ONE_LEAF = "layer_2']['mixer']['o_proj']['kernel"
IGNORE = -1


def main(argv=None, root: str = ROOT) -> int:
    """``root`` is for tests; the command line has none."""
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--control", action="append", default=[])
    ap.add_argument("--fault", choices=("half_targets", "one_leaf"))
    ap.add_argument("--leaf", default=ONE_LEAF)
    ap.add_argument("--assignments", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from rt1_tpu.data.pipeline import device_feeder

    from benchmarks import check, devices, program, readings_tokens, run, traffic, weights
    from benchmarks.drivers import train, train_tokens

    manifest = run.load_manifest(root)
    cell = run.find_cell(manifest, args.workload)
    config_file = program.load_config_file(
        os.path.join(root, run.config_path(manifest, cell["config"])))
    mix = traffic.load_traffic_file(
        os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json"))
    known = [config_file["control_precision"], *config_file.get("mechanism_controls", [])]
    for name in args.control:
        if name not in known:
            raise SystemExit(f"{cell['config']} has no control {name!r}: {known}")
    device = devices.describe(int(cell["chips"]))
    devices.enable_compile_cache(root)
    config = program.program_config(config_file)
    prog = None
    log = lambda m: print(f"[readings] {m}", file=sys.stderr, flush=True)  # noqa: E731

    def every_second_hidden(path, x):
        if "targets" not in jax.tree_util.keystr(path):
            return x
        hidden = jnp.arange(x.shape[-1]) % 2 == 1
        return jax.device_put(jnp.where(hidden, IGNORE, x), x.sharding)

    for seed in args.seeds:
        feed = train_tokens.build_feed(mix, config, seed)
        if prog is None:
            prog = train_tokens.build_program(config_file, seed, int(mix["seq_len"]))
            tx = prog.state.tx
        else:
            prog.state = prog.fns.shard_state(program.initial_state(
                prog.abstract_params, prog.abstract_batch_stats, tx, seed, prog.gains))
            prog.skips = prog.fns.init_guard_skips() if prog.fns.guarded else None
        host = traffic.TimedIterator(feed)
        host.keep = train.CHECK_STEPS
        dev_iter = device_feeder(host, prog.fns.batch_sharding, depth=2)
        base = weights.seed_key(seed)
        counters = []

        def one_step(i):
            batch = next(dev_iter)
            if args.fault == "half_targets":
                batch = jax.tree_util.tree_map_with_path(every_second_hidden, batch)
            before = prog.state.params if args.fault == "one_leaf" else None
            if before is not None:      # the leaf is put back: it must outlive the donation
                before = jax.tree.map(jnp.copy, before)
            metrics = prog.step(batch, jax.random.fold_in(base, i))
            counters.append({k: float(v) for k, v in metrics.items()
                             if k.startswith(("moe/", "attention/"))})
            if before is not None:
                prog.state = prog.state.replace(params=jax.tree_util.tree_map_with_path(
                    lambda path, new, old: old if args.leaf in jax.tree_util.keystr(path) else new,
                    prog.state.params, before))
            return metrics

        losses, mu1, params3 = train.first_steps(prog, one_step)
        skips = int(jax.device_get(prog.skips)) if prog.skips is not None else 0
        batches = list(host.taps)
        feed.close()
        del dev_iter
        prog.state = None       # the reference needs the room; the compiled step stays
        abstract = (prog.abstract_params, prog.abstract_batch_stats)
        reading = check.program_readings(abstract, seed, config_file, losses, mu1, params3)
        del mu1, params3
        ref = train_tokens.reference_readings(config_file, abstract, seed, batches, "highest", log)
        limits = config_file["limits"]

        def judged(side):
            nums = check.numbers(side, ref)
            verdict = check.judge(nums, limits)
            return {"correct": all(c["ok"] for c in verdict),
                    "over": [c["name"] for c in verdict if not c["ok"]],
                    "numbers": {k: list(v) for k, v in nums.items()}}

        out = {"seed": seed, "device": device["kind"], "fault": args.fault, "skips": skips,
               "limits": limits, "program": judged(reading), "counters": counters,
               "losses": losses, "reference_losses": ref["losses"], "controls": {}}
        for name in args.control:
            out["controls"][name] = judged(train_tokens.reference_readings(
                config_file, abstract, seed, batches, name, log))
        if args.assignments:
            differing, total = readings_tokens.assignments_differing(
                prog, config_file, seed, batches[0])
            out["assignments_differing"] = {"by_routed_layer": differing, "of": total}
        log(f"seed {seed}: program{' with ' + args.fault if args.fault else ''} correct "
            f"{out['program']['correct']} {out['program']['over']}" + "".join(
                f"; control {name} correct {c['correct']} {c['over']}"
                for name, c in out["controls"].items()))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

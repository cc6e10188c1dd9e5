"""benchmarks/readings.py for ``kind: train_tokens`` cells: the readings that
the limits of ``correct`` are set from, many seeds in one process (one compile
of the step, one of the reference, one of the control):

    python benchmarks/readings_tokens.py <workload> <seed> [<seed> ...] [--control]
        [--fault half_batch|one_leaf] [--assignments]

For each seed: the cell's own program at the cell's own size, driven through
its first three steps by its own feed, against the plain reference; with
``--control`` also the reference one precision down in the program's place;
with ``--fault`` the program with that fault planted under it; with
``--assignments`` also how many of the first batch's tokens x top-k
assignments differ between the program's routed layers and the reference's.
Each is judged by ``check.judge`` with the cell's own limits, as a run judges
the program.  One JSON line per seed.  A tool, not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ONE_LEAF = "layer_2']['mixer']['out_proj']['kernel"


def assignments_differing(prog, config_file, seed, batch):
    """Per routed layer, the live tokens x top-k assignments of the first
    batch that the program (its own arithmetic) and the reference (float32)
    select differently, from the seed's weights.  Live: up to a sequence's last
    counted target; the program gives the positions after it no rows, so what
    it routes there is read by nothing."""
    import jax
    import numpy as np

    from benchmarks import check, program, weights

    params, _ = weights.make_weights(
        prog.abstract_params, prog.abstract_batch_stats, seed, program.weight_gains(config_file))
    observations, actions = batch["observations"], batch["actions"]
    _, state = jax.jit(lambda p: prog.model.apply(
        {"params": p}, observations, actions, mutable=["intermediates"]))(params)
    mine = [np.sort(np.asarray(v["ffn"]["selected"][0]), axis=-1)
            for _, v in sorted(state["intermediates"].items())]
    ref = check.load_reference(config_file["reference"])
    sz = ref.sizes(config_file["overrides"])
    theirs = jax.jit(lambda p: ref.selected_experts(p, observations["tokens"], sz))(params)
    counted = np.asarray(actions["targets"]) != ref.IGNORE
    live = (np.flip(np.cumsum(np.flip(counted, 1), 1), 1) > 0).reshape(-1)
    return [int((a != np.sort(np.asarray(b), axis=-1))[live].sum())
            for a, b in zip(mine, theirs)], int(live.sum()) * mine[0].shape[-1]


def main(argv=None, root: str = ROOT) -> int:
    """``root`` is for tests; the command line has none."""
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seeds", type=int, nargs="+")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", choices=("half_batch", "one_leaf"))
    ap.add_argument("--assignments", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from rt1_tpu.data.pipeline import device_feeder

    from benchmarks import check, devices, program, run, traffic, weights
    from benchmarks.drivers import train, train_tokens

    manifest = run.load_manifest(root)
    cell = run.find_cell(manifest, args.workload)
    config_file = program.load_config_file(
        os.path.join(root, run.config_path(manifest, cell["config"])))
    mix = traffic.load_traffic_file(
        os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json"))
    device = devices.describe(int(cell["chips"]))
    devices.enable_compile_cache(root)
    config = program.program_config(config_file)
    prog = None
    log = lambda m: print(f"[readings] {m}", file=sys.stderr, flush=True)  # noqa: E731

    def first_half_twice(x):
        h = x.shape[0] // 2
        return jax.device_put(jnp.concatenate([x[:h], x[:h]]), x.sharding)

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

        def one_step(i):
            batch = next(dev_iter)
            if args.fault == "half_batch":
                batch = jax.tree.map(first_half_twice, batch)
            before = prog.state.params if args.fault == "one_leaf" else None
            if before is not None:      # the leaf is put back: it must outlive the donation
                before = jax.tree.map(jnp.copy, before)
            metrics = prog.step(batch, jax.random.fold_in(base, i))
            if before is not None:
                prog.state = prog.state.replace(params=jax.tree_util.tree_map_with_path(
                    lambda path, new, old: old if ONE_LEAF in jax.tree_util.keystr(path) else new,
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
               "limits": limits, "program": judged(reading),
               "losses": losses, "reference_losses": ref["losses"]}
        if args.control:
            precision = config_file["control_precision"]
            out["control_precision"] = precision
            out["control"] = judged(train_tokens.reference_readings(
                config_file, abstract, seed, batches, precision, log))
        if args.assignments:
            differing, total = assignments_differing(prog, config_file, seed, batches[0])
            out["assignments_differing"] = {"by_routed_layer": differing, "of": total}
        log(f"seed {seed}: program{' with ' + args.fault if args.fault else ''} correct "
            f"{out['program']['correct']} {out['program']['over']}"
            + (f"; control {out['control_precision']} correct {out['control']['correct']} "
               f"{out['control']['over']}" if args.control else ""))
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

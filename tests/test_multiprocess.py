"""FAST 2-process scale-out smoke (ISSUE 14, tier-1 — NOT slow-marked).

Two real `jax.distributed` processes with 2 forced host devices each (a
2-host x 2-device slice), bounded by subprocess timeouts, so scale-out
regressions fail in the default suite instead of only on hardware. The
heavyweight 2x4 topology with the full multihost Orbax matrix stays in
the slow tests/test_distributed.py.

Asserted here (cross-process; each worker's local assertions gate its
`ok_<pid>` marker — see tests/multiprocess_worker.py):

* per-host feeder slices are disjoint and exhaustive over the batched
  prefix of the global stream;
* both processes observe IDENTICAL losses (the gradient reduction is a
  real cross-host collective), and the 2-process loss trajectory equals a
  single-process run of the same global batch within float tolerance —
  the ISSUE 14 acceptance criterion;
* multi-process checkpoint save/restore ran, `latest_step` tolerated a
  foreign in-progress Orbax tmp dir, and the plan-migrating restore
  round-tripped on-mesh (worker-side assertions).
"""

import os
import subprocess
import sys

import numpy as np

from rt1_tpu.parallel.distributed import free_local_port as _free_port


def test_two_process_smoke_fast(tmp_path):
    port = _free_port()
    worker = os.path.join(os.path.dirname(__file__), "multiprocess_worker.py")
    env = {
        k: v
        for k, v in os.environ.items()
        # Strip this (single-process) test session's device-count override
        # from the children; the worker pins its own 2-device platform.
        # The RT1_* rendezvous env is set by the worker itself (the
        # env-fallback path under test).
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(i), str(port), str(tmp_path)],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for i in range(2)
    ]
    outputs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=240)
            outputs.append(out)
    finally:
        for p in procs:  # no leaked workers holding the coordinator port
            if p.poll() is None:
                p.kill()
                p.wait()
    for i, (p, out) in enumerate(zip(procs, outputs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        assert os.path.exists(tmp_path / f"ok_{i}")

    # Host slices: disjoint and jointly exhaustive over the batched prefix
    # (24 windows, global batch 4 — no tail here).
    stripes = []
    for i in range(2):
        with open(tmp_path / f"windows_{i}.txt") as f:
            stripes.append([int(x) for x in f.read().split(",") if x])
    s0, s1 = set(stripes[0]), set(stripes[1])
    assert len(s0) == len(stripes[0]) and len(s1) == len(stripes[1])
    assert s0.isdisjoint(s1)
    assert len(s0 | s1) == 24  # 4 episodes x 6 steps

    # Both processes computed the SAME global losses.
    losses = []
    for i in range(2):
        with open(tmp_path / f"losses_{i}.txt") as f:
            losses.append([float(x) for x in f.read().split(",")])
    assert losses[0] == losses[1] and losses[0]

    # Acceptance: the 2-process trajectory equals a single-process run of
    # the same (seed, corpus, global batch) within float tolerance. The
    # reference runs IN this (single-process, 8-virtual-device) session on
    # a 4-device dp x fsdp carve — same logical mesh shape, same global
    # batch, different process topology.
    sys.path.insert(0, os.path.dirname(__file__))
    import multiprocess_worker as mw

    import jax

    from rt1_tpu.parallel import ShardingPlan

    plan = ShardingPlan.from_config(
        {"parallel": {"dp": 2, "fsdp": 2}}, devices=jax.devices()[:4]
    )
    ref_losses, _, _, ref_feeder = mw.train_losses(
        str(tmp_path / "data" / "packed"), plan,
        process_index=0, process_count=1, local_batch=2 * mw.LOCAL_BATCH,
    )
    np.testing.assert_allclose(losses[0], ref_losses, rtol=1e-5, atol=1e-5)
    # The single-process stream is the concatenation of the worker stripes.
    ref_order = ref_feeder.host_order(0).tolist()
    merged = (
        np.stack(
            [np.asarray(s).reshape(-1, mw.LOCAL_BATCH) for s in stripes],
            axis=1,
        ).reshape(-1).tolist()
    )
    assert merged == ref_order[: len(merged)]

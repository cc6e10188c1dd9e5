"""Orbax checkpointing: save / restore-or-initialize / best-keep policy.

Replaces (SURVEY.md §5 checkpoint/resume):
* Stack A Lightning `ModelCheckpoint(save_top_k=-1, save_last=True,
  every_n_epochs)` (`distribute_train.py:214-220`),
* Stack B `clu.checkpoint.MultihostCheckpoint` + flax `save_checkpoint`
  with `keep_every_n_steps` (`language_table/train/train.py:122-138,201-217`).

Orbax is multihost-aware out of the box (each host writes its shards of a
sharded TrainState; restore lays arrays back out on the mesh), which is the
TPU-native replacement for clu's multihost rendezvous.

Plan migration (rt1_tpu/parallel/reshard.py, docs/parallelism.md
"Multi-host"): ``restore(plan=...)`` / ``restore_or_initialize(plan=...)``
restore a checkpoint saved under one sharding plan onto a different
mesh/plan — the template becomes abstract arrays carrying the TARGET
plan's shardings, so Orbax lays every global array out on the new mesh
(dense→fsdp, 4→8 devices, train-mesh→serve-replica) with a single-process
gather→slice fallback for Orbax versions that reject abstract templates.

Multi-process discipline: every process participates in save/restore
(Orbax coordinates the shard writes and the commit internally), but the
side-band artifacts OUR layer adds — the ``saved_under.json`` provenance
marker — are written by process 0 only, and the module-level
`latest_step` scan tolerates another host's in-progress Orbax tmp dirs
(proven under two real processes in tests/test_multiprocess.py).

Resilience (rt1_tpu/resilience/, docs/resilience.md): `CheckpointConfig.
retry` wraps save/restore in exponential-backoff retry so a transient
filesystem error degrades to a logged warning instead of killing the run;
`restore_or_initialize` survives a corrupt/partial latest step by falling
back to older retained steps (loudly); and the `ckpt_save`/`ckpt_restore`
fault-injection sites make both paths provable in tests and chaos runs.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional

from rt1_tpu.resilience import faults
from rt1_tpu.resilience.retry import RetryOptions, retry_call


@dataclasses.dataclass
class CheckpointConfig:
    directory: str
    max_to_keep: Optional[int] = None  # None = keep everything (save_top_k=-1)
    save_interval_steps: int = 1000
    keep_period: Optional[int] = None  # also keep every Nth (keep_every_n_steps)
    # Backoff schedule for transient I/O on save/restore; None = no retry
    # (one attempt, errors propagate — the pre-resilience behavior).
    retry: Optional[RetryOptions] = None
    # Observer for checkpoint I/O wall time: called as on_io(name, seconds)
    # with name "ckpt_save"/"ckpt_restore" after every logical operation
    # (retries included in the measured span, failures too — badput is
    # badput). The train loop hands the goodput ledger's note_io here
    # (rt1_tpu/obs/goodput.py); exceptions are swallowed — accounting must
    # never take down checkpointing.
    on_io: Optional[Callable[[str, float], None]] = None


class CheckpointManager:
    """Thin wrapper over ocp.CheckpointManager for TrainState pytrees."""

    def __init__(self, config: CheckpointConfig):
        # Imported where a manager is made, not with the module: orbax takes
        # seconds to import (its cloud logging client), and whoever imports
        # the trainer without saving (a benchmark run, a shape check) does
        # not pay them.
        import orbax.checkpoint as ocp

        self._ocp = ocp
        self._config = config
        options = ocp.CheckpointManagerOptions(
            max_to_keep=config.max_to_keep,
            save_interval_steps=config.save_interval_steps,
            keep_period=config.keep_period,
            create=True,
        )
        self._mgr = ocp.CheckpointManager(
            config.directory,
            options=options,
        )
        # Logical-operation ordinals for fault injection: bumped once per
        # save/restore (NOT per retry attempt), so "ckpt_save@2" means the
        # 2nd save even when an earlier injected failure triggered retries.
        self._save_ops = 0
        self._restore_ops = 0

    def _io(self, fn, name: str):
        """Run an I/O closure, retried per the config (or once when off);
        reports the whole operation's wall time (all attempts) to `on_io`."""
        t0 = time.perf_counter()
        try:
            if self._config.retry is None:
                return fn()
            return retry_call(fn, options=self._config.retry, name=name)
        finally:
            if self._config.on_io is not None:
                try:
                    self._config.on_io(name, time.perf_counter() - t0)
                except Exception:  # noqa: BLE001 - accounting only
                    pass

    def save(self, step: int, state: Any, force: bool = False) -> bool:
        self._save_ops += 1
        op = self._save_ops

        def _save():
            # Injection precedes the real write so a "transient" spec fires
            # once and the retry's next attempt genuinely succeeds. Indexed
            # by the logical save ordinal, not the attempt, so a spec's
            # extra fires (`x<K>`) land on consecutive RETRIES of the same
            # save rather than silently consuming later saves' occurrences.
            faults.maybe_fail("ckpt_save", index=op, what=f"save at step {step}")
            return self._mgr.save(
                step, args=self._ocp.args.StandardSave(state), force=force
            )

        saved = bool(self._io(_save, "ckpt_save"))
        if saved:
            self._write_provenance(step)
        return saved

    def _write_provenance(self, step: int) -> None:
        """`saved_under.json`: the topology this checkpoint was written
        from (process/device counts + newest step) — what `reshard` names
        in its diagnostics when a migrated restore fails, and the
        restore-on-a-different-slice post-mortem's first question. Process
        0 ONLY (the one multi-process rule for side-band files: N hosts
        racing one marker is how markers get torn), atomic tmp+rename,
        best-effort — provenance must never take down checkpointing."""
        import json
        import os

        import jax

        from rt1_tpu.parallel.distributed import is_primary

        if not is_primary():
            return
        try:
            path = os.path.join(self._config.directory, "saved_under.json")
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(
                    {
                        "step": int(step),
                        "process_count": int(jax.process_count()),
                        "device_count": int(jax.device_count()),
                        "local_device_count": int(jax.local_device_count()),
                        "written_at_unix": time.time(),
                    },
                    f,
                    indent=2,
                    sort_keys=True,
                )
            os.replace(tmp, path)
        except Exception:  # noqa: BLE001 - marker only
            pass

    def restore(
        self, state_like: Any, step: Optional[int] = None, plan: Any = None
    ) -> Any:
        """Restore into the structure/shardings of `state_like`.

        With ``plan`` (a `parallel.ShardingPlan`) the restore is a PLAN
        MIGRATION (parallel/reshard.py): `state_like` contributes only the
        tree structure and shapes/dtypes; placement comes from the target
        plan's rules, so a checkpoint saved under a different mesh/plan
        (dense→fsdp, 4→8 devices, pod→serve-replica) lands directly in the
        layout this process computes with. If this Orbax version rejects
        the abstract sharded template, a single-process gather→slice
        fallback restores into `state_like` and re-places through the plan
        (loudly — on a multi-host mesh the fallback raises instead).
        """
        if step is None:
            step = self._mgr.latest_step()
        if step is None:
            raise FileNotFoundError(
                f"No checkpoint found in {self._config.directory}"
            )

        self._restore_ops += 1
        op = self._restore_ops

        def _restore():
            faults.maybe_fail(
                "ckpt_restore", index=op, what=f"restore step {step}"
            )
            if plan is None:
                return self._mgr.restore(
                    step, args=self._ocp.args.StandardRestore(state_like)
                )
            from rt1_tpu.parallel import reshard

            template = reshard.abstract_target(state_like, plan)
            try:
                return self._mgr.restore(
                    step, args=self._ocp.args.StandardRestore(template)
                )
            except (TypeError, ValueError, NotImplementedError) as exc:
                # Only template-shape rejections (an Orbax that cannot
                # take abstract sharded templates) — I/O and corruption
                # errors must propagate to restore_or_initialize's
                # older-step fallback WITHOUT a pointless second full
                # restore of the same broken step.
                import jax
                from absl import logging

                if jax.process_count() > 1:
                    raise  # a host cannot materialize other hosts' shards
                logging.warning(
                    "checkpoint: sharded (plan-target) restore of step %d "
                    "rejected (%s: %s) — falling back to host gather→slice",
                    step, type(exc).__name__, exc,
                )
                restored = self._mgr.restore(
                    step, args=self._ocp.args.StandardRestore(state_like)
                )
                return reshard.place_on_plan(restored, plan)

        return self._io(_restore, "ckpt_restore")

    def restore_or_initialize(self, state_like: Any, plan: Any = None):
        """(state, step): restored latest, or the passed-in init at step 0.

        Mirrors `clu.checkpoint.restore_or_initialize` semantics
        (`language_table/train/train.py:125-127`): training resumes from
        `step + 1` after preemption.

        Robust to a corrupt/partial newest step (half-written before a hard
        kill, truncated by a full disk): a failed restore logs loudly and
        falls back to the next-older retained step instead of wedging the
        relaunch; only when EVERY retained step fails does the original
        error propagate. ``plan`` passes through to :meth:`restore` — the
        resume path is plan-migrating too, so a run relaunched on a
        different slice shape restores the old slice's checkpoint directly
        into the new layout.
        """
        steps = sorted(self.all_steps(), reverse=True)
        if not steps:
            return state_like, 0
        last_exc: Optional[Exception] = None
        for step in steps:
            try:
                return self.restore(state_like, step, plan=plan), int(step)
            except Exception as exc:  # noqa: BLE001 - fall back per step
                from absl import logging

                last_exc = exc
                logging.error(
                    "checkpoint: restore of step %d in %s FAILED (%s: %s)%s",
                    step,
                    self._config.directory,
                    type(exc).__name__,
                    exc,
                    " — falling back to the previous retained step"
                    if step != steps[-1]
                    else " — no older step to fall back to",
                )
        raise last_exc

    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def all_steps(self) -> List[int]:
        """Retained step numbers (finalized only — Orbax skips tmp dirs)."""
        return [int(s) for s in self._mgr.all_steps()]

    def wait_until_finished(self):
        self._mgr.wait_until_finished()

    def close(self):
        self._mgr.close()


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Newest checkpoint step under `ckpt_dir`, or None — without building a
    CheckpointManager (cheap enough for CLI glue, watchdogs, and provenance
    stamping; Orbax step dirs are plain integer-named directories).

    Defensive against in-flight/aborted writes: Orbax tmp dirs
    (`<step>.orbax-checkpoint-tmp-<ts>`) fail the digit check, and a bare
    EMPTY integer-named directory (mkdir happened, contents never landed)
    is not a checkpoint either.
    """
    import os

    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if not d.isdigit():
            continue  # Orbax tmp dirs and sidecar files
        full = os.path.join(ckpt_dir, d)
        try:
            if not os.path.isdir(full) or not os.listdir(full):
                continue
        except OSError:
            continue
        steps.append(int(d))
    return max(steps) if steps else None

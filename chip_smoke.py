#!/usr/bin/env python3
"""Quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
flagship's full width (`rt1_tpu/train/configs/language_table.py`:
FiLM-EfficientNet-B3 + TokenLearner + 8-layer decoder, 256x456 frames,
window 6, bf16, batch 8; random seeded weights, synthetic batches):

* ``train``        `python -m rt1_tpu.train.train`, a few optimizer steps,
                   a finite loss for each, one checkpoint;
* ``train_resume`` the same command on the same workdir: restores the
                   checkpoint, takes no duplicate step, and compiles from
                   the persistent cache;
* ``serve``        `python -m rt1_tpu.serve --random_init --port 0`: two
                   sessions over HTTP `/act` past window roll-over, actions
                   finite and in bounds, `compile_count` == bucket count,
                   SIGTERM -> drained line, exit 0;
* ``pallas``       `infer_step` with ``attention_impl="pallas"`` against
                   the dense path on the same seeded weights, with the
                   kernel (`tpu_custom_call`) present in the compiled
                   program.

``--chips 4`` runs ONLY the four-chip path and what it is compared with:
the flagship trainer under ``config.parallel.auto`` (dp 2 x fsdp 2) and
the same seed and global batch on one device.

One process for each chip: this parent never imports jax; every phase is a
child process that exits before the next starts. Each phase prints one
JSON object on its own stdout line; the LAST line is
``{"ok": ..., "device": {"platform", "kind", "count"}}``. Any failed
phase, any phase on a platform other than ``tpu`` and any non-finite value
exit non-zero with ``"ok": false``.
"""

from __future__ import annotations

import argparse
import base64
import importlib.util
import json
import math
import os
import queue
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import traceback
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple

REPO = os.path.dirname(os.path.abspath(__file__))
FLAGSHIP = os.path.join("rt1_tpu", "train", "configs", "language_table.py")

#: The whole run must end inside the chip check's 1200 s; phases get what
#: is left of this, capped by their own limit, so one that sits is reported
#: as a hang instead of being waited out.
TOTAL_BUDGET_S = 1140.0
PHASE_LIMIT_S = {
    "probe": 180.0,
    "probe_one_device": 180.0,
    "train": 600.0,
    "train_resume": 360.0,
    "serve": 480.0,
    "pallas": 360.0,
    "train_sharded": 700.0,
    "train_reference": 600.0,
}
TRAIN_STEPS = 4  # first launch: steps 1..4, checkpoint at 4
RESUME_STEPS = 2  # second launch: steps 5..6 only
#: Language-Table action space is Box(-0.1, 0.1) (rt1_tpu/specs.py).
ACTION_BOUND = 0.1
#: max |logit_pallas - logit_dense| / max(1, max |logit_dense|). The kernel
#: keeps softmax probabilities in f32 where the dense path rounds them to
#: the compute dtype, so bf16 differs in the last bits of every layer.
PALLAS_TOL = {"bfloat16": 0.1, "float32": 2e-3}
#: Sharded vs one-device loss, |a - b| / |b| per step; same seed, data and
#: global batch. Steps 1-2 (through the first update) differ only by the
#: order of bf16 reductions: tight. From then on Adam's early updates are
#: about +-lr per weight whatever a gradient's size, so rounding noise in
#: near-zero gradients flips update signs and the runs drift apart
#: chaotically — two ONE-chip runs whose init differed in the last bits
#: were 4 % apart at step 4 (PERF.md, PR 21). The later bound only catches
#: a run that went somewhere else entirely.
SHARDED_LOSS_RTOL_THROUGH_FIRST_UPDATE = 5e-3
SHARDED_LOSS_RTOL_LATER = 0.15

# The server child writes no goodput summary: its cache traffic is still
# counted off its log. The trainer's comes from the start-up log's snapshot
# in its goodput_summary.json (rt1_tpu/obs/startup.py).
_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")
_MISS = re.compile(r"PERSISTENT COMPILATION CACHE MISS for '([^']+)'")
_DEVICES = re.compile(r"devices: platform=(\S+) device_kind=(.+?) count=(\d+)")
_PLACEMENT = re.compile(
    r"state placement: params\+opt_state total_bytes=(\d+) "
    r"per_device_bytes=(\{.*?\})"
)
_LOSS = re.compile(r"\] \[(\d+)\] (?:.*?, )?loss=([^,\s]+)")
_PEAK = re.compile(r"device memory: peak_bytes_in_use=(\d+) stats=(\{.*?\})")


class PhaseFailed(RuntimeError):
    """A phase did not meet its contract; the message says which part."""


def _load_config(path: str):
    """Import a config file (ml_collections only — no jax in the parent)."""
    spec = importlib.util.spec_from_file_location("_smoke_config", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.get_config()


def _child_env(platform: str, chips: int, one_device: bool) -> Dict[str, str]:
    """Environment of a phase child on a machine with `chips` devices;
    `one_device` shows it a single one of them. Cache-hit logging is on
    for every child: it is how the parent counts the server's
    persistent-cache hits (the trainer's come from its start-up log)."""
    env = dict(os.environ)
    env["JAX_DEBUG_LOG_MODULES"] = "jax._src.compiler"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if platform == "cpu":
        # Rehearsal (tests/test_chip_smoke.py): virtual host devices.
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d" % (
            1 if one_device else chips
        )
    elif one_device and chips > 1:
        # One chip of a multi-chip host, the way jax's own multi-process
        # tests carve one up (jax/_src/test_multiprocess.py); libtpu reads
        # the bounds under an older pair of names too, which a TPU VM's
        # environment may already set for the whole host.
        env["TPU_VISIBLE_CHIPS"] = "0"
        for name in ("TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
                     "TPU_CHIPS_PER_HOST_BOUNDS", "TPU_HOST_BOUNDS"):
            env[name] = "1,1,1"
    return env


def _kill_group(proc: subprocess.Popen) -> None:
    if proc.poll() is None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()


class Smoke:
    """One run: phases share a workdir, a deadline and the device record."""

    def __init__(self, chips: int, config_path: str, platform: str,
                 workdir: str, pallas_interpret: bool):
        self.chips = chips
        self.config_path = config_path
        self.config = _load_config(os.path.join(REPO, config_path))
        self.platform = platform
        self.workdir = workdir
        self.pallas_interpret = pallas_interpret
        self.deadline = time.monotonic() + TOTAL_BUDGET_S
        self.device: Dict[str, Any] = {
            "platform": None, "kind": None, "count": None
        }
        self.ok = True
        self.expect_cached_step = True

    # ------------------------------------------------------------ plumbing

    def _env(self, one_device: bool = False) -> Dict[str, str]:
        return _child_env(self.platform, self.chips, one_device)

    def _limit(self, phase: str) -> float:
        left = self.deadline - time.monotonic()
        if left <= 0:
            raise PhaseFailed(f"no time left for phase {phase}")
        return min(PHASE_LIMIT_S[phase], left)

    def _run(self, phase: str, argv: Sequence[str],
             env: Dict[str, str]) -> Tuple[str, str]:
        """Run one child to its end; returns its (stdout, stderr).
        A child past its limit is killed (whole process group) and the
        phase fails as a hang."""
        out_path = os.path.join(self.workdir, f"{phase}.stdout")
        err_path = os.path.join(self.workdir, f"{phase}.stderr")
        limit = self._limit(phase)
        with open(out_path, "w") as out_f, open(err_path, "w") as err_f:
            proc = subprocess.Popen(
                list(argv), cwd=REPO, env=env, stdout=out_f, stderr=err_f,
                start_new_session=True,
            )
            try:
                rc = proc.wait(timeout=limit)
            except subprocess.TimeoutExpired:
                raise PhaseFailed(
                    f"{phase}: still running after its {limit:.0f}s limit "
                    f"— reported as a hang, not waited out"
                ) from None
            finally:
                _kill_group(proc)
        with open(out_path) as f:
            stdout = f.read()
        with open(err_path) as f:
            stderr = f.read()
        if rc != 0:
            raise PhaseFailed(
                f"{phase}: exit code {rc}\n--- stderr tail ---\n"
                + stderr[-3000:]
            )
        return stdout, stderr

    def _require_platform(self, phase: str, platform: str) -> None:
        if platform != self.platform:
            raise PhaseFailed(
                f"{phase} ran on platform {platform!r}, not "
                f"{self.platform!r}"
            )

    def phase(self, name: str, fn, *args) -> Optional[Dict[str, Any]]:
        """Run one phase and print its JSON line. A failure is printed and
        marks the run failed, which `finish` turns into the exit code — no
        phase failure lets the run exit 0."""
        t0 = time.monotonic()
        try:
            facts = fn(*args)
        except Exception as exc:  # noqa: BLE001 - printed; the run exits 1
            if not isinstance(exc, PhaseFailed):
                traceback.print_exc()
            self.ok = False
            print(json.dumps({
                "phase": name, "ok": False,
                "error": str(exc) if isinstance(exc, PhaseFailed)
                else repr(exc),
                "seconds": round(time.monotonic() - t0, 1),
            }), flush=True)
            return None
        print(json.dumps({
            "phase": name, "ok": True, **facts,
            "seconds": round(time.monotonic() - t0, 1),
        }), flush=True)
        return facts

    def finish(self) -> int:
        print(json.dumps({"ok": self.ok, "device": self.device}), flush=True)
        return 0 if self.ok else 1

    # -------------------------------------------------------------- phases

    def probe(self, one_device: bool = False) -> Dict[str, Any]:
        """What jax finds, which episode reader loads, where the cache is.
        The all-devices probe is where the final line's `device` comes
        from: the parent itself never asks jax."""
        tag = "probe_one_device" if one_device else "probe"
        stdout, _ = self._run(
            tag,
            [sys.executable, "-c",
             "import chip_smoke; chip_smoke.probe_child()"],
            self._env(one_device),
        )
        facts = json.loads(stdout.strip().splitlines()[-1])
        if not one_device:
            self.device = {
                "platform": facts["platform"],
                "kind": facts["device_kind"],
                "count": facts["device_count"],
            }
        self._require_platform(tag, facts["platform"])
        want = 1 if one_device else self.chips
        if facts["device_count"] != want:
            raise PhaseFailed(
                f"{tag}: jax found {facts['device_count']} devices, this "
                f"run needs {want}"
            )
        return facts

    def _train(self, phase: str, workdir: str, num_steps: int,
               one_device: bool = False,
               overrides: Sequence[str] = ()) -> Dict[str, Any]:
        """One launch of the trainer CLI; facts parsed from its log."""
        _, log = self._run(
            phase,
            [sys.executable, "-m", "rt1_tpu.train.train",
             "--config", self.config_path, "--workdir", workdir,
             f"--config.num_steps={num_steps}",
             "--config.log_every_steps=1", *overrides],
            self._env(one_device),
        )
        dev = _DEVICES.search(log)
        if dev is None:
            raise PhaseFailed(f"{phase}: trainer logged no devices line")
        self._require_platform(phase, dev.group(1))
        losses = {int(s): float(v) for s, v in _LOSS.findall(log)}
        bad = {s: v for s, v in losses.items() if not math.isfinite(v)}
        if bad:
            raise PhaseFailed(f"{phase}: non-finite loss {bad}")
        with open(os.path.join(workdir, "goodput_summary.json")) as f:
            goodput = json.load(f)
        # What the child's own start-up log counted: every backend compile
        # or fetch, and the train step's by its role.
        totals = goodput["startup"]["totals"]
        step = goodput["startup"]["roles"].get("train_step")
        placement = _PLACEMENT.search(log)
        peak = _PEAK.search(log)
        return {
            "platform": dev.group(1),
            "device_kind": dev.group(2),
            "device_count": int(dev.group(3)),
            "steps": sorted(losses),
            "losses": [losses[s] for s in sorted(losses)],
            "compile_seconds": round(goodput["buckets_s"]["compile"], 2),
            "ckpt_restore_seconds": round(
                goodput["buckets_s"]["ckpt_restore"], 2
            ),
            "cache_hits": totals["cache_hits"],
            "cache_misses": totals["compiles"] - totals["cache_hits"],
            "train_step_cache": (
                "not compiled" if step is None or not step["compiles"]
                else "hit" if step["cache_hits"] else "miss"
            ),
            # jax writes no cache entry for a compile under its time floor
            # (1 s, rt1_tpu/compilation_cache.py) — only the tiny CPU
            # rehearsal compiles the step that fast.
            "train_step_in_cache_after": step is not None
            and step["cache_hits"] + step["cache_writes"] > 0,
            "peak_bytes_in_use": int(peak.group(1)) if peak else None,
            "memory_stats": json.loads(peak.group(2)) if peak else None,
            "state_total_bytes": (
                int(placement.group(1)) if placement else None
            ),
            "state_per_device_bytes": (
                json.loads(placement.group(2)) if placement else None
            ),
            "checkpoints": sorted(
                int(d) for d in os.listdir(os.path.join(workdir, "checkpoints"))
                if d.isdigit()
            ),
        }

    def train(self) -> Dict[str, Any]:
        workdir = os.path.join(self.workdir, "train")
        facts = self._train("train", workdir, TRAIN_STEPS)
        want = list(range(1, TRAIN_STEPS + 1))
        if facts["steps"] != want:
            raise PhaseFailed(
                f"train: logged steps {facts['steps']}, wanted {want}"
            )
        if TRAIN_STEPS not in facts["checkpoints"]:
            raise PhaseFailed(
                f"train: no checkpoint at step {TRAIN_STEPS}: "
                f"{facts['checkpoints']}"
            )
        self.expect_cached_step = facts["train_step_in_cache_after"]
        return facts

    def train_resume(self) -> Dict[str, Any]:
        workdir = os.path.join(self.workdir, "train")
        last = TRAIN_STEPS + RESUME_STEPS
        facts = self._train("train_resume", workdir, last)
        want = list(range(TRAIN_STEPS + 1, last + 1))
        if facts["steps"] != want:
            raise PhaseFailed(
                f"train_resume: logged steps {facts['steps']}, wanted {want} "
                f"(restore step {TRAIN_STEPS}, no duplicate step)"
            )
        if facts["ckpt_restore_seconds"] <= 0:
            raise PhaseFailed("train_resume: no checkpoint restore recorded")
        if self.expect_cached_step and facts["train_step_cache"] != "hit":
            raise PhaseFailed(
                "train_resume: the train step was not served from the "
                f"persistent cache ({facts['train_step_cache']})"
            )
        return facts

    def serve(self) -> Dict[str, Any]:
        """Boot the server CLI, drive /act over HTTP, SIGTERM, drain."""
        limit = self._limit("serve")
        t_end = time.monotonic() + limit
        err_path = os.path.join(self.workdir, "serve.stderr")
        with open(err_path, "w") as err_f:
            proc = subprocess.Popen(
                [sys.executable, "-m", "rt1_tpu.serve",
                 "--config", self.config_path, "--random_init",
                 "--port", "0"],
                cwd=REPO, env=self._env(),
                stdout=subprocess.PIPE, stderr=err_f, text=True,
                start_new_session=True,
            )
        lines: "queue.Queue[Optional[str]]" = queue.Queue()

        def _pump():
            for line in proc.stdout:
                lines.put(line)
            lines.put(None)

        threading.Thread(target=_pump, daemon=True).start()

        def _next_status(status: str) -> Dict[str, Any]:
            while True:
                try:
                    line = lines.get(timeout=max(t_end - time.monotonic(), 0))
                except queue.Empty:
                    raise PhaseFailed(
                        f"serve: no {status!r} line inside the phase's "
                        f"{limit:.0f}s limit — reported as a hang"
                    ) from None
                if line is None:
                    with open(err_path) as f:
                        tail = f.read()[-3000:]
                    raise PhaseFailed(
                        f"serve: exited rc={proc.wait()} before its "
                        f"{status!r} line\n--- stderr tail ---\n{tail}"
                    )
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(msg, dict) and msg.get("status") == status:
                    return msg

        try:
            ready = _next_status("serving")
            self._require_platform("serve", ready["platform"])
            url = f"http://{ready['host']}:{ready['port']}"
            window = int(self.config.model.time_sequence_length)
            steps = window + 3  # past roll-over of the rolling state
            shape = (int(self.config.data.height),
                     int(self.config.data.width), 3)
            sessions = ["smoke-a", "smoke-b"]
            answers: Dict[str, List[Dict[str, Any]]] = {s: [] for s in sessions}
            errors: List[str] = []

            def _drive(idx: int, sid: str) -> None:
                try:
                    for step in range(steps):
                        frame = _frame(shape, seed=1000 * idx + step)
                        answers[sid].append(_post(url + "/act", {
                            "session_id": sid,
                            "image_b64": base64.b64encode(frame).decode(),
                            "instruction": "push the red block to the blue block",
                        }, timeout=max(t_end - time.monotonic(), 1)))
                except Exception as exc:  # noqa: BLE001 - reported below
                    errors.append(f"{sid}: {exc!r}")

            threads = [
                threading.Thread(target=_drive, args=(i, s))
                for i, s in enumerate(sessions)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errors:
                raise PhaseFailed(f"serve: /act failed: {errors}")
            max_abs = 0.0
            for sid, got in answers.items():
                if len(got) != steps:
                    raise PhaseFailed(f"serve: {sid} answered {len(got)}/{steps}")
                for i, ans in enumerate(got):
                    action = [float(a) for a in ans["action"]]
                    if not all(math.isfinite(a) for a in action):
                        raise PhaseFailed(
                            f"serve: non-finite action {action} ({sid} step {i})"
                        )
                    if max(abs(a) for a in action) > ACTION_BOUND + 1e-6:
                        raise PhaseFailed(
                            f"serve: action {action} outside +-{ACTION_BOUND}"
                        )
                    max_abs = max(max_abs, max(abs(a) for a in action))
            metrics = _get(url + "/metrics")
            if metrics["compile_count"] != len(ready["buckets"]):
                raise PhaseFailed(
                    f"serve: compile_count {metrics['compile_count']} != "
                    f"bucket count {len(ready['buckets'])}"
                )
            proc.send_signal(signal.SIGTERM)
            drained = _next_status("drained")
            try:
                rc = proc.wait(timeout=max(t_end - time.monotonic(), 1))
            except subprocess.TimeoutExpired:
                raise PhaseFailed(
                    "serve: did not exit after its drained line"
                ) from None
            if rc != 0:
                raise PhaseFailed(f"serve: exit code {rc} after drain")
        finally:
            _kill_group(proc)
        with open(err_path) as f:
            log = f.read()
        return {
            "platform": ready["platform"],
            "device_kind": ready["device_kind"],
            "device_count": ready["device_count"],
            "sessions": len(sessions),
            "steps_per_session": steps,
            "window": window,
            "requests": sum(len(v) for v in answers.values()),
            "max_abs_action": max_abs,
            "buckets": ready["buckets"],
            "compile_count": int(metrics["compile_count"]),
            "cache_hits": len(_HIT.findall(log)),
            "cache_misses": len(_MISS.findall(log)),
            "drained_requests_total": drained.get("requests_total"),
            "exit_code": rc,
        }

    def pallas(self) -> Dict[str, Any]:
        stdout, _ = self._run(
            "pallas",
            [sys.executable, "-c",
             "import chip_smoke, sys; "
             "chip_smoke.pallas_child(sys.argv[1], sys.argv[2] == '1')",
             self.config_path, "1" if self.pallas_interpret else "0"],
            self._env(),
        )
        facts = json.loads(stdout.strip().splitlines()[-1])
        self._require_platform("pallas", facts["platform"])
        if not facts["finite"]:
            raise PhaseFailed("pallas: non-finite logits")
        if not self.pallas_interpret and not (
            facts["pallas_program_has_kernel"]
            and not facts["dense_program_has_kernel"]
        ):
            raise PhaseFailed(
                "pallas: tpu_custom_call must be in the pallas program and "
                f"not in the dense one: {facts}"
            )
        tol = PALLAS_TOL[facts["dtype"]]
        if facts["max_rel_logit_diff"] > tol:
            raise PhaseFailed(
                f"pallas: logits differ from dense by "
                f"{facts['max_rel_logit_diff']} > {tol}"
            )
        return {**facts, "tolerance": tol}

    def train_sharded(self) -> Dict[str, Any]:
        facts = self._train(
            "train_sharded", os.path.join(self.workdir, "train_sharded"),
            TRAIN_STEPS, overrides=["--config.parallel.auto=True"],
        )
        per_device = facts["state_per_device_bytes"] or {}
        total = facts["state_total_bytes"]
        if len(per_device) != self.chips:
            raise PhaseFailed(
                f"train_sharded: state has shards on {len(per_device)} "
                f"devices, wanted {self.chips}: {per_device}"
            )
        if not all(b < total for b in per_device.values()):
            raise PhaseFailed(
                f"train_sharded: a device holds the replicated total "
                f"({total} bytes): {per_device}"
            )
        return facts

    def train_reference(self, sharded: Dict[str, Any]) -> Dict[str, Any]:
        facts = self._train(
            "train_reference", os.path.join(self.workdir, "train_reference"),
            TRAIN_STEPS, one_device=True,
        )
        if facts["device_count"] != 1:
            raise PhaseFailed(
                f"train_reference: ran on {facts['device_count']} devices"
            )
        if facts["steps"] != sharded["steps"]:
            raise PhaseFailed(
                f"train_reference: steps {facts['steps']} vs sharded "
                f"{sharded['steps']}"
            )
        rel = [
            abs(a - b) / max(abs(b), 1e-6)
            for a, b in zip(sharded["losses"], facts["losses"])
        ]
        tol = [
            SHARDED_LOSS_RTOL_THROUGH_FIRST_UPDATE if step <= 2
            else SHARDED_LOSS_RTOL_LATER
            for step in facts["steps"]
        ]
        if any(r > t for r, t in zip(rel, tol)):
            raise PhaseFailed(
                f"sharded vs one-device loss: relative differences {rel} "
                f"exceed {tol}: {sharded['losses']} vs {facts['losses']}"
            )
        return {
            **facts,
            "sharded_losses": sharded["losses"],
            "rel_loss_diff": rel,
            "tolerance": tol,
        }


# ------------------------------------------------------------- HTTP client


def _frame(shape, seed: int) -> bytes:
    """Deterministic uint8 frame bytes without numpy in the parent."""
    import random

    return random.Random(seed).randbytes(shape[0] * shape[1] * shape[2])


def _post(url: str, payload: Dict[str, Any], timeout: float) -> Dict[str, Any]:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def _get(url: str) -> Dict[str, Any]:
    with urllib.request.urlopen(url, timeout=30) as resp:
        return json.loads(resp.read())


# ------------------------------------------------- child bodies (own process)


def probe_child() -> None:
    """Child: report the device, the episode reader and the cache dir."""
    from rt1_tpu import compilation_cache

    compilation_cache.enable_persistent_cache()
    import jax

    from rt1_tpu.data import native
    from rt1_tpu.parallel.distributed import describe_devices

    print(json.dumps({
        **describe_devices(),
        # A failed native build was logged with the compiler's output by
        # rt1_tpu/data/native.py; the numpy reader then loads episodes.
        "episode_reader": "native" if native.available() else "numpy",
        "window_sampler": (
            "native" if native.sampler_available() else "not built"
        ),
        "compilation_cache_dir": jax.config.jax_compilation_cache_dir,
        "cache_dir_from_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
    }), flush=True)


def pallas_child(config_path: str, interpret: bool) -> None:
    """Child: `infer_step` under dense and pallas attention on the same
    seeded weights, past window roll-over; logits compared step by step.
    `interpret` exists for the CPU rehearsal only (tests/test_chip_smoke)."""
    from rt1_tpu import compilation_cache

    compilation_cache.enable_persistent_cache()
    import copy

    import jax
    import numpy as np

    from rt1_tpu.eval.restore import build_model_and_state
    from rt1_tpu.train.train import build_model

    config = _load_config(os.path.join(REPO, config_path))
    dense, state, _, _ = build_model_and_state(config)
    variables = {"params": state.params}
    if state.batch_stats:
        variables["batch_stats"] = state.batch_stats
    pallas_config = copy.deepcopy(config)
    with pallas_config.unlocked():
        pallas_config.model.attention_impl = "pallas"
    pallas = build_model(pallas_config.model)
    if interpret:
        pallas = pallas.clone(pallas_interpret=True)

    batch = 2
    h, w = config.data.height, config.data.width
    rng = np.random.default_rng(0)

    def observation():
        return {
            "image": rng.random((batch, h, w, 3), dtype=np.float32),
            "natural_language_embedding": rng.standard_normal(
                (batch, 512), dtype=np.float32
            ),
        }

    steps = {}
    has_kernel = {}
    for name, model in (("dense", dense), ("pallas", pallas)):
        steps[name] = jax.jit(
            lambda v, o, s, model=model: model.apply(
                v, o, s, method=model.infer_step
            )
        ).lower(
            variables, observation(), model.initial_state(batch_size=batch)
        ).compile()
        has_kernel[name] = "tpu_custom_call" in steps[name].as_text()

    rng = np.random.default_rng(1)  # same frames for both paths
    frames = [observation() for _ in range(config.model.time_sequence_length + 2)]
    logits = {}
    for name, model in (("dense", dense), ("pallas", pallas)):
        rolling = model.initial_state(batch_size=batch)
        logits[name] = []
        for obs in frames:
            out, rolling = steps[name](variables, obs, rolling)
            logits[name].append(np.asarray(out["action_logits"], np.float32))
    d = np.stack(logits["dense"])
    p = np.stack(logits["pallas"])
    device = jax.devices()[0]
    print(json.dumps({
        "platform": device.platform,
        "device_kind": device.device_kind,
        "dtype": config.model.dtype,
        "interpret": interpret,
        "infer_steps": len(frames),
        "batch": batch,
        "finite": bool(np.isfinite(d).all() and np.isfinite(p).all()),
        "max_abs_logit_diff": float(np.max(np.abs(d - p))),
        "max_rel_logit_diff": float(
            np.max(np.abs(d - p)) / max(1.0, float(np.max(np.abs(d))))
        ),
        "max_abs_logit": float(np.max(np.abs(d))),
        "token_agreement": float(
            np.mean(np.argmax(d, -1) == np.argmax(p, -1))
        ),
        "pallas_program_has_kernel": has_kernel["pallas"],
        "dense_program_has_kernel": has_kernel["dense"],
    }), flush=True)


# -------------------------------------------------------------------- driver


def smoke(chips: int = 1, *, config: str = FLAGSHIP, platform: str = "tpu",
          pallas_interpret: bool = False) -> int:
    """Run the smoke; returns the exit code. The keyword arguments exist
    for the CPU rehearsal in tests/test_chip_smoke.py — the command line
    exposes only `--chips`, so the script cannot be made to pass off-chip."""
    workdir = tempfile.mkdtemp(prefix="rt1_chip_smoke_")
    run = Smoke(chips, config, platform, workdir, pallas_interpret)
    try:
        if run.phase("probe", run.probe) is None:
            return run.finish()
        if chips == 1:
            if run.phase("train", run.train) is not None:
                run.phase("train_resume", run.train_resume)
            run.phase("serve", run.serve)
            run.phase("pallas", run.pallas)
        else:
            one = run.phase("probe_one_device", run.probe, True)
            sharded = one and run.phase("train_sharded", run.train_sharded)
            if sharded:
                run.phase("train_reference", run.train_reference, sharded)
        return run.finish()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, default=1, choices=[1, 4],
        help="1 (default): trainer, resume, server and pallas phases on one "
             "chip. 4: only the dp 2 x fsdp 2 sharded trainer and its "
             "one-device reference.")
    args = parser.parse_args(argv)
    try:
        return smoke(args.chips)
    except Exception:  # noqa: BLE001 - any crash is a failed smoke, exit 1
        traceback.print_exc()
        print(json.dumps({
            "ok": False,
            "device": {"platform": None, "kind": None, "count": None},
        }), flush=True)
        return 1


if __name__ == "__main__":
    sys.exit(main())

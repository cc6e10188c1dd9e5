"""chip_smoke.py off the chip: it must FAIL as shipped, and its phases must
work when the TEST steers them onto the CPU at the tiny config.

The steering is keyword arguments of `chip_smoke.smoke()` that the command
line does not expose — there is no option that lets the script pass
without a TPU. The parent never imports jax; every phase below is a real
child process (trainer CLI, server CLI over HTTP, ...).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = os.path.join("rt1_tpu", "train", "configs", "tiny.py")


def _lines(text):
    return [json.loads(l) for l in text.strip().splitlines() if l.startswith("{")]


def test_without_a_chip_the_script_fails_and_names_the_cpu():
    """As the driver runs it, in a sandbox with no accelerator: non-zero
    exit, `"ok": false`, and the device it found is the CPU — no phase
    result is printed under a TPU name."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=300,
        # The sandbox as the driver has it: CPU only, and without this
        # test session's 8 virtual devices.
        env={**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
    )
    assert out.returncode != 0
    lines = _lines(out.stdout)
    assert lines[-1] == {
        "ok": False,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    assert [l["phase"] for l in lines[:-1]] == ["probe"]
    assert lines[0]["ok"] is False and "'cpu'" in lines[0]["error"]
    assert "tpu" not in json.dumps(lines[-1]).lower()


def test_alone_in_a_directory_the_script_fails(tmp_path):
    """The chip check also runs the script without the program."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode != 0
    assert _lines(out.stdout)[-1]["ok"] is False


def test_trainer_log_parsing():
    """The facts the parent reads off the trainer's log: per-step train
    losses (not eval losses), devices, placement, cache hits."""
    log = "\n".join([
        "I0926 12:46:03.29 1 train.py:500] devices: platform=tpu "
        "device_kind=TPU v5 lite count=4",
        "I0926 12:46:20.31 1 train.py:727] state placement: params+opt_state "
        'total_bytes=100 per_device_bytes={"0": 30, "1": 30}',
        "I0926 12:46:37.82 2 logging_writer.py:48] [1] action_loss_mean=0.5, "
        "examples_per_sec=1.9, goodput/step_s=0, loss=5.25, stall_pct=0",
        "I0926 12:46:38.36 2 logging_writer.py:48] [1] eval_loss=0.03",
        "I0926 12:46:38.37 2 logging_writer.py:48] [2] loss=nan",
        "I0926 12:46:25.70 1 compiler.py:112] PERSISTENT COMPILATION CACHE "
        "MISS for 'jit_train_step_guarded' with key 'k'",
        "I0926 12:46:25.70 1 compiler.py:102] Persistent compilation cache "
        "hit for 'jit_fold_in' with key 'k'",
        "I0926 12:47:00.00 1 train.py:1190] device memory: "
        'peak_bytes_in_use=123 stats={"bytes_limit": 456}',
    ])
    assert chip_smoke._DEVICES.search(log).groups() == ("tpu", "TPU v5 lite", "4")
    assert chip_smoke._LOSS.findall(log) == [("1", "5.25"), ("2", "nan")]
    placement = chip_smoke._PLACEMENT.search(log)
    assert placement.group(1) == "100"
    assert json.loads(placement.group(2)) == {"0": 30, "1": 30}
    assert chip_smoke._HIT.findall(log) == ["jit_fold_in"]
    assert chip_smoke._MISS.findall(log) == ["jit_train_step_guarded"]
    assert chip_smoke._PEAK.search(log).groups() == (
        "123", '{"bytes_limit": 456}'
    )


@pytest.mark.slow  # ~30 s; tier-1 runs within 60 s of its time limit
def test_serve_phase_on_cpu_at_tiny_config(tmp_path, capfd):
    """The phase with the most moving parts on its own: boot the real
    server CLI, two sessions over HTTP past window roll-over, SIGTERM,
    drained line, exit 0. (The whole script is the test below.)"""
    run = chip_smoke.Smoke(1, TINY, "cpu", str(tmp_path), True)
    facts = run.phase("serve", run.serve)
    assert facts is not None, capfd.readouterr().out
    assert facts["platform"] == "cpu"
    assert facts["sessions"] == 2
    assert facts["steps_per_session"] > facts["window"]
    assert facts["requests"] == 2 * facts["steps_per_session"]
    assert facts["compile_count"] == len(facts["buckets"])
    assert facts["exit_code"] == 0
    assert run.ok


@pytest.mark.slow  # ~3 min: five + four child processes, each booting jax
@pytest.mark.parametrize("chips", [1, 4], ids=["one_chip", "four_chips"])
def test_cpu_rehearsal_at_tiny_config(chips, capfd):
    """Every phase of the real script, steered by the test onto CPU devices
    at the tiny config (Pallas in interpret mode). With 4 the script runs
    ONLY the sharded trainer and its one-device reference."""
    rc = chip_smoke.smoke(
        chips, config=TINY, platform="cpu", pallas_interpret=True
    )
    lines = _lines(capfd.readouterr().out)
    by_phase = {l["phase"]: l for l in lines[:-1]}
    assert all(l["ok"] for l in lines), lines
    assert rc == 0
    assert lines[-1] == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": chips},
    }
    if chips == 1:
        assert list(by_phase) == [
            "probe", "train", "train_resume", "serve", "pallas"
        ]
        assert by_phase["probe"]["episode_reader"] in ("native", "numpy")
        assert by_phase["train"]["steps"] == [1, 2, 3, 4]
        assert by_phase["train_resume"]["steps"] == [5, 6]
        if by_phase["train"]["train_step_in_cache_after"]:
            assert by_phase["train_resume"]["train_step_cache"] == "hit"
        serve = by_phase["serve"]
        assert serve["steps_per_session"] > serve["window"]
        assert serve["compile_count"] == len(serve["buckets"])
        assert serve["exit_code"] == 0
        assert by_phase["pallas"]["max_rel_logit_diff"] <= 2e-3
    else:
        assert list(by_phase) == [
            "probe", "probe_one_device", "train_sharded", "train_reference"
        ]
        sharded = by_phase["train_sharded"]
        assert len(sharded["state_per_device_bytes"]) == 4
        assert all(
            b < sharded["state_total_bytes"]
            for b in sharded["state_per_device_bytes"].values()
        )
        assert by_phase["train_reference"]["device_count"] == 1
        assert max(by_phase["train_reference"]["rel_loss_diff"]) <= 5e-3

"""Shared by tests/benchmark: a temporary copy of the benchmark's data files
with CPU-rehearsal cells appended to its manifest, and a device check that
lets the rest of a run be driven without a chip (patched here, in the tests:
the program has no switch for it)."""

import io
import json
import os
import shutil
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

REHEARSAL_CELLS = {
    "small.packed": ("rt1-small-test", "train-packed-test"),
    "small.pool": ("rt1-small-test", "train-pool-test"),
}


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def temp_checkout(tmp_path, extra_workloads=()):
    """Data files of the benchmark copied under tmp_path, plus the rehearsal
    configurations, mixes and cells.  The code is the repo's own."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "benchmarks"), exist_ok=True)
    for d in ("configs", "traffic", "metrics"):
        shutil.copytree(os.path.join(REPO, "benchmarks", d),
                        os.path.join(root, "benchmarks", d), dirs_exist_ok=True)
    shutil.copy(os.path.join(REPO, "benchmarks", "peaks.json"),
                os.path.join(root, "benchmarks", "peaks.json"))
    m = manifest()
    for cell, (config, mix) in REHEARSAL_CELLS.items():
        shutil.copy(os.path.join(DATA, config + ".json"),
                    os.path.join(root, "benchmarks", "configs"))
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    os.path.join(root, "benchmarks", "traffic"))
        if not any(c["name"] == config for c in m["configs"]):
            m["configs"].append({"name": config, "source": "rehearsal", "reduced": [],
                                 "file": f"benchmarks/configs/{config}.json",
                                 "why": "CPU rehearsal"})
        m["workloads"].append({"name": cell, "config": config, "traffic": mix,
                               "chips": 1, "why": "CPU rehearsal"})
    m["workloads"].extend(extra_workloads)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(m, f)
    return root


def add_rehearsal_cell(root, cell, config, mix="train-tokens-test", per_layer=()):
    """One more rehearsal cell in a temporary checkout: its configuration and
    mix copied from tests/benchmark/data, its entries (and any per-layer
    entries of its own) appended to the checkout's manifest."""
    shutil.copy(os.path.join(DATA, config + ".json"),
                os.path.join(root, "benchmarks", "configs"))
    shutil.copy(os.path.join(DATA, mix + ".json"), os.path.join(root, "benchmarks", "traffic"))
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        m = json.load(f)
    m["configs"].append({"name": config, "source": "rehearsal", "reduced": [],
                         "file": f"benchmarks/configs/{config}.json", "why": "CPU rehearsal"})
    m["workloads"].append({"name": cell, "config": config, "traffic": mix, "chips": 1,
                           "why": "CPU rehearsal"})
    m["per_layer"].extend(per_layer)
    with open(path, "w") as f:
        json.dump(m, f)
    return root


def pretend_chip(monkeypatch):
    from benchmarks import devices

    monkeypatch.setattr(devices, "describe", lambda chips: {
        "platform": "cpu-rehearsal", "kind": "TPU v5 lite", "count": chips})


def run_cell(root, workload, seconds=1.5, trace=0, seed=3000000019):
    """(exit code, parsed last line or None, everything printed to stdout)."""
    from benchmarks import run

    out = io.StringIO()
    with redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)], root=root)
    text = out.getvalue().strip()
    return rc, (json.loads(text.splitlines()[-1]) if text else None), text

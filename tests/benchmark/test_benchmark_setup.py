"""The seven ``setup_*`` readers: each reads the program's own start-up log
(rt1_tpu/obs/startup.py) through benchmarks/setup_log.py.  From a hand-made
snapshot each returns its number; from an empty one, and from a program that
has no such module (an older commit's), None; and with nothing handed in they
read the process's own log, after a rehearsal run of a cell as after a run on
the chip.

Their seven ``per_layer`` entries in BENCHMARK.json carry no ``workloads``
list: every cell reports ``setup_s``, so every cell, and every cell added
later, reports all seven.  They are found by name, never by place."""

import builtins

import pytest

from bench_testlib import REPO, manifest, pretend_chip, run_cell, temp_checkout
from benchmarks import run, setup_log

CELLS = [w["name"] for w in manifest()["workloads"]]
READERS = {
    "setup_backend_init_s": 1.25,
    "setup_build_s": 2.0 + 0.5 + 3.0 + 0.25 + 0.125 + 0.75 * 2 + 0.0625,
    "setup_step_trace_s": 14.5,
    "setup_step_inner_traces": 18485,
    "setup_step_lower_s": 5.5,
    "setup_step_compile_s": 1.75,
    "setup_cache_misses": 2,
}


def _phase(seconds, self_s=None, count=1):
    return {"count": count, "seconds": seconds, "self_s": seconds if self_s is None else self_s}


def hand_made():
    """A snapshot as `startup.snapshot()` returns it, with numbers that tell
    every field from every other."""
    return {
        "phase_s": {
            "backend_init": _phase(1.25),
            "build_model": _phase(6.0, self_s=2.0),      # init_state ran inside it
            "make_optimizer": _phase(0.5),
            "init_state": _phase(3.0),
            "make_step_fns": _phase(0.25),
            "shard_state": _phase(0.125),
            "open_feed": _phase(1.5, count=2),
            "first_batch": _phase(0.0625),
            "restore": _phase(9.0),                      # not part of the build
            "first_step": _phase(30.0),
        },
        "roles": {
            "train_step": {
                "functions": ["train_step_guarded"], "traces": 1, "trace_s": 14.5,
                "inner_traces": 18485, "lowerings": 1, "lower_s": 5.5, "compiles": 1,
                "backend_s": 1.75, "cache_hits": 1, "fetch_s": 1.5, "cache_writes": 0,
                "recompiles": 0,
                "totals_at_executable": {"compiles": 9, "cache_hits": 7, "traces": 18500},
            },
            "eval_step": {"functions": ["eval_step"], "trace_s": 99.0},
        },
        "totals": {"compiles": 40, "cache_hits": 20},
    }


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_reads_its_number_from_a_hand_made_snapshot(name):
    reader = run.metric_reader(REPO, name)
    assert reader.read({"startup": hand_made()}) == READERS[name]


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_none_where_the_log_lacks_it(name):
    reader = run.metric_reader(REPO, name)
    assert reader.read({"startup": {}}) is None
    assert reader.read({"startup": {"phase_s": {}, "roles": {}, "totals": {}}}) is None
    # a role with no executable yet has no stamped totals
    unstamped = hand_made()
    del unstamped["roles"]["train_step"]["totals_at_executable"]
    if name == "setup_cache_misses":
        assert reader.read({"startup": unstamped}) is None


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_returns_none_from_a_program_without_the_log(name, monkeypatch):
    """The driver lays these files over the parent's checkout for its traced
    runs: there `rt1_tpu.obs.startup` does not exist."""
    real_import = builtins.__import__

    def no_startup(module, globals=None, locals=None, fromlist=(), level=0):
        if module == "rt1_tpu.obs" and "startup" in (fromlist or ()):
            raise ImportError("cannot import name 'startup' from 'rt1_tpu.obs'")
        return real_import(module, globals, locals, fromlist, level)

    monkeypatch.setattr(builtins, "__import__", no_startup)
    assert run.metric_reader(REPO, name).read({"trace": {}}) is None


@pytest.mark.parametrize("cell", ["small.packed", "small.pool"])
def test_after_a_rehearsal_run_the_readers_read_the_programs_own_set_up(
        tmp_path, monkeypatch, cell):
    """The whole path on the CPU: the driver builds the program through
    ``program.build`` (the phases), ``make_train_step_fns`` marks the step's
    role, the first steps trace, lower and compile it, and the readers the
    cell's entries name, run after the driver returns, find all of it in the
    process's log."""
    from rt1_tpu.obs import startup
    from rt1_tpu.parallel import describe_devices

    describe_devices()          # as benchmarks/devices.py::describe does on the chip
    pretend_chip(monkeypatch)
    before = startup.snapshot()
    root = temp_checkout(tmp_path)
    rc, line, _ = run_cell(root, cell)
    assert rc == 0 and line["correct"] is True, line
    after = startup.snapshot()
    for phase in setup_log.BUILD_PHASES:
        assert after["phase_s"][phase]["count"] > before["phase_s"].get(phase, {"count": 0})["count"]
    role = after["roles"]["train_step"]
    assert set(role["functions"]) <= {"train_step", "train_step_guarded"}
    assert after["totals"]["traces"] > before["totals"]["traces"]
    names = [x["name"] for x in run.cell_metrics(run.load_manifest(root), "per_layer", cell)
             if x["moves"] == "setup_s"]
    assert sorted(names) == sorted(READERS)
    reading = {"trace": {}}
    values = {name: run.metric_reader(root, name).read(reading) for name in names}
    assert all(v is not None for v in values.values()), values
    assert values["setup_step_inner_traces"] > 0 and values["setup_build_s"] > 0
    assert values["setup_step_trace_s"] > 0 and values["setup_step_lower_s"] > 0
    assert values["setup_step_compile_s"] > 0 and values["setup_cache_misses"] >= 0
    assert values["setup_backend_init_s"] == after["phase_s"]["backend_init"]["seconds"]
    assert reading["startup"]["totals"] == after["totals"]      # one snapshot a run, kept


@pytest.mark.parametrize("name", sorted(READERS))
def test_a_manifest_entry_keeps_the_rules(name):
    """Each entry, found by name: no list of cells, under ``setup_s`` in the
    entry point's layer, its source by its unit."""
    entry = next(x for x in manifest()["per_layer"] if x["name"] == name)
    assert set(entry) == {"name", "unit", "better", "source", "layer", "moves"}
    assert entry["unit"] in ("s", "count") and entry["better"] == "lower"
    assert entry["source"] == ("program_counter" if entry["unit"] == "count" else "program_span")
    assert entry["moves"] == "setup_s" and entry["layer"] == "Entry point"


def test_every_cell_reports_the_seven_and_each_reader_resolves(tmp_path):
    """The manifest's cells, and the cells a temporary checkout adds (the
    rehearsal cells and one more of a configuration that is there)."""
    extra = {"name": "lfm2-24b-a2b.train-tokens-16k", "config": "lfm2-24b-a2b",
             "traffic": "train-tokens-16k", "chips": 1, "why": "added by a test"}
    root = temp_checkout(tmp_path, extra_workloads=[extra])
    m = run.load_manifest(root)
    cells = [w["name"] for w in m["workloads"]]
    assert set(CELLS) | {"small.packed", "small.pool", extra["name"]} <= set(cells)
    for cell in cells:
        mine = [x["name"] for x in run.cell_metrics(m, "per_layer", cell) if x["moves"] == "setup_s"]
        assert sorted(mine) == sorted(READERS), cell
        for name in mine:
            assert callable(run.metric_reader(root, name).read)

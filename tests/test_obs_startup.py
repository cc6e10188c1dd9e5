"""obs/startup.py: the start-up log.

Phases under an injected clock; the compile log against real jax on the CPU
(`jax.monitoring` fires there as on the chip) and against synthetic events;
what it hands the goodput ledger and the step timeline.
"""

import logging

import pytest

from rt1_tpu.obs import startup
from rt1_tpu.obs.goodput import GoodputLedger
from rt1_tpu.obs.steps import StepTimeline


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def advance(self, seconds):
        self.t += seconds

    def __call__(self):
        return self.t


@pytest.fixture
def log():
    """A log of its own on jax's listeners, beside the process's."""
    import jax  # noqa: F401 - install() looks jax up, it does not import it

    log = startup.StartupLog()
    assert log.install()
    yield log
    log.uninstall()


def _nested_pair():
    import jax

    @jax.jit
    def inner_fn(x):
        return x * 2 + 1

    @jax.jit
    def outer_fn(x):
        return inner_fn(x) + inner_fn(x + 1)

    return outer_fn


# ------------------------------------------------------------------ phases


def test_phase_nesting_and_self_time_under_an_injected_clock():
    clock = FakeClock()
    log = startup.StartupLog(clock=clock)
    with log.phase("build_model"):
        clock.advance(2.0)
        with log.phase("init_state"):
            clock.advance(3.0)
        with log.phase("init_state"):
            clock.advance(1.0)
        clock.advance(0.5)
    with log.phase("open_feed"):
        clock.advance(0.25)
    snap = log.snapshot()
    by_name = snap["phase_s"]
    assert by_name["build_model"] == {"count": 1, "seconds": 6.5, "self_s": 2.5}
    assert by_name["init_state"] == {"count": 2, "seconds": 4.0, "self_s": 4.0}
    assert by_name["open_feed"]["seconds"] == 0.25
    records = snap["phases"]
    assert [p["parent"] for p in records] == [None, "build_model", "build_model", None]
    assert records[0]["start_s"] == 100.0 and records[0]["end_s"] == 106.5
    assert all(p["thread"] == "MainThread" for p in records)
    # self times add up to what the outermost phases cover
    assert sum(p["self_s"] for p in records) == pytest.approx(6.75)


def test_an_open_phase_reads_up_to_now_and_the_list_is_bounded():
    clock = FakeClock()
    log = startup.StartupLog(clock=clock)
    with log.phase("first_step"):
        clock.advance(4.0)
        assert log.snapshot()["phase_s"]["first_step"]["seconds"] == 4.0
    for _ in range(startup.MAX_PHASES + 40):
        with log.phase("open_feed"):
            pass
    snap = log.snapshot()
    assert len(snap["phases"]) == startup.MAX_PHASES
    assert snap["phases_dropped"] == 41


def test_phase_is_a_span_of_the_ring():
    from rt1_tpu.obs import trace

    recorder = trace.enable()
    try:
        with startup.StartupLog().phase("build_model", family="rt1"):
            pass
        events = [e for e in recorder.to_dict()["traceEvents"] if e.get("ph") == "X"]
    finally:
        trace.disable()
    assert events[-1]["name"] == "setup/build_model"
    assert events[-1]["args"] == {"family": "rt1"}


def test_phased_wraps_every_call():
    before = startup.snapshot()["phase_s"].get("_test_phase", {"count": 0})["count"]

    @startup.phased("_test_phase")
    def build(x, scale=1):
        return x * scale

    assert build(3, scale=2) == 6 and build.__name__ == "build"
    assert startup.snapshot()["phase_s"]["_test_phase"]["count"] == before + 1


# ---------------------------------------------------- the compile log, real jax


def test_outermost_function_holds_its_inner_traces_and_self_times_add_up(log):
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    outer_fn(jnp.ones(3)).block_until_ready()
    snap = log.snapshot()
    entry = snap["functions"]["outer_fn"]
    assert "inner_fn" not in snap["functions"]      # never outermost
    assert entry["traces"] == 1 and entry["lowerings"] == 1 and entry["compiles"] == 1
    assert entry["inner"]["inner_fn"]["count"] == 2
    assert entry["inner_traces"] == sum(v["count"] for v in entry["inner"].values())
    assert entry["inner_traces"] >= 2
    inner_self = sum(v["self_s"] for v in entry["inner"].values())
    assert entry["trace_self_s"] + inner_self == pytest.approx(entry["trace_s"], abs=1e-6)
    assert entry["trace_s"] > 0 and entry["lower_s"] > 0 and entry["backend_s"] > 0
    totals = snap["totals"]
    assert totals["traces"] >= 1 + entry["inner_traces"]
    assert totals["seconds"] >= entry["trace_s"] + entry["lower_s"] + entry["backend_s"]
    assert set(entry["last"]) == {"trace", "lower", "backend"}
    start, end = entry["last"]["backend"]
    assert end - start == pytest.approx(entry["backend_s"], abs=1e-6)


def test_a_second_call_records_nothing_and_warm_steps_leave_every_total(log):
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    x = jnp.ones(3)
    outer_fn(x).block_until_ready()
    before = log.snapshot()
    for _ in range(50):
        x = outer_fn(x)
    x.block_until_ready()
    after = log.snapshot()
    assert after["totals"] == before["totals"]
    assert after["functions"]["outer_fn"] == before["functions"]["outer_fn"]


@pytest.fixture
def warn_every_recompile(monkeypatch):
    """A test's functions compile in milliseconds: no floor under WARNING."""
    monkeypatch.setattr(startup, "WARN_SECONDS", 0.0)


def test_a_new_shape_is_a_recompile_with_one_warning(log, caplog, warn_every_recompile):
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    outer_fn(jnp.ones(3)).block_until_ready()
    assert log.snapshot()["totals"]["recompiles"] == 0
    log.current_step = 7
    with caplog.at_level(logging.WARNING, logger="rt1_tpu.obs.startup"):
        outer_fn(jnp.ones(5)).block_until_ready()
    snap = log.snapshot()
    assert snap["totals"]["recompiles"] == 1
    assert snap["functions"]["outer_fn"]["recompiles"] == 1
    assert snap["functions"]["outer_fn"]["compiles"] == 2
    warnings = [r.getMessage() for r in caplog.records if "recompiled" in r.getMessage()]
    assert len(warnings) == 1
    assert "recompiled outer_fn" in warnings[0] and "step 7" in warnings[0]


def test_a_cheap_recompile_is_counted_and_logged_at_info(log, caplog):
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    outer_fn(jnp.ones(3)).block_until_ready()
    with caplog.at_level(logging.INFO, logger="rt1_tpu.obs.startup"):
        outer_fn(jnp.ones(5)).block_until_ready()      # far under WARN_SECONDS
    assert log.snapshot()["totals"]["recompiles"] == 1
    levels = [r.levelno for r in caplog.records if "recompiled outer_fn" in r.getMessage()]
    assert levels == [logging.INFO]
    log._on_start(startup.BACKEND, 0.0, fun_name="jit(outer_fn)")
    with caplog.at_level(logging.INFO, logger="rt1_tpu.obs.startup"):
        log._on_span(startup.BACKEND, 0.0, 41.0, fun_name="jit(outer_fn)")
    assert caplog.records[-1].levelno == logging.WARNING
    assert "the compile took 41.000 s" in caplog.records[-1].getMessage()


def test_set_up_compiles_by_one_name_are_no_recompiles_but_a_role_is(
        log, caplog, warn_every_recompile):
    """While a set-up phase is open jax's own small programs compile once a
    shape under one name (`add`, `broadcast_in_dim`): set-up's normal work.
    A function with a role is a recompile wherever it happens."""
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    with log.phase("init_state"):
        outer_fn(jnp.ones(2)).block_until_ready()
        outer_fn(jnp.ones(4)).block_until_ready()
    assert log.snapshot()["totals"]["recompiles"] == 0
    log.mark_role("train_step", "outer_fn")
    with log.phase("first_step"), caplog.at_level(logging.WARNING):
        outer_fn(jnp.ones(6)).block_until_ready()
    assert log.snapshot()["totals"]["recompiles"] == 1
    assert any("phase first_step" in r.getMessage() for r in caplog.records)


def test_roles_sum_their_functions_and_stamp_the_totals(log):
    import jax
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    log.mark_role("train_step", "outer_fn")
    log.mark_role("train_step", "never_traced")
    log.mark_role("eval_step", "not_yet")
    outer_fn(jnp.ones(3)).block_until_ready()
    at_step = log.snapshot()["totals"]
    jax.jit(lambda x: x - 1)(jnp.ones(3)).block_until_ready()   # a later program
    snap = log.snapshot()
    assert set(snap["roles"]) == {"train_step"}
    role = snap["roles"]["train_step"]
    assert role["functions"] == ["outer_fn"]
    assert role["inner_traces"] == snap["functions"]["outer_fn"]["inner_traces"]
    assert role["totals_at_executable"] == at_step
    assert snap["totals"]["compiles"] > at_step["compiles"]


def test_the_persistent_cache_answers_the_second_compile(log, tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes", "jax_enable_compilation_cache")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        compilation_cache.reset_cache()

        def cached_fn(x):
            return jnp.tanh(x) * 3

        jax.jit(cached_fn)(jnp.ones(7)).block_until_ready()
        first = log.snapshot()["functions"]["cached_fn"]
        assert first["cache_hits"] == 0 and first["cache_writes"] == 1
        jax.clear_caches()
        jax.jit(cached_fn)(jnp.ones(7)).block_until_ready()
        second = log.snapshot()["functions"]["cached_fn"]
        assert second["compiles"] == 2 and second["cache_hits"] == 1
        assert second["fetch_s"] > 0 and second["cache_writes"] == 1
        totals = log.snapshot()["totals"]
        assert totals["cache_hits"] >= 1 and totals["fetch_s"] >= second["fetch_s"]
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()


def test_compile_events_are_spans_of_the_ring(log):
    import jax.numpy as jnp

    from rt1_tpu.obs import trace

    recorder = trace.enable()
    try:
        _nested_pair()(jnp.ones(9)).block_until_ready()
        events = [e for e in recorder.to_dict()["traceEvents"] if e.get("ph") == "X"]
    finally:
        trace.disable()
    ours = [e for e in events if e["args"].get("fun") == "outer_fn"]
    assert [e["name"] for e in ours] == ["compile/trace", "compile/lower", "compile/backend"]
    assert ours[0]["ts"] <= ours[1]["ts"] <= ours[2]["ts"] <= trace.now_us()


def test_install_is_idempotent_and_needs_jax(monkeypatch):
    import sys

    import jax

    log = startup.StartupLog()
    calls = []
    monkeypatch.setattr(jax.monitoring, "register_event_time_span_listener", calls.append)
    try:
        assert log.install() and log.install()
        assert len(calls) == 1
    finally:
        monkeypatch.undo()
        log._installed = False      # the span listener above was never registered
    monkeypatch.setitem(sys.modules, "jax", None)
    assert startup.StartupLog().install() is False


# ------------------------------------------------- the compile log, synthetic


def _trace(log, name, start, end, children=()):
    """One trace event as jax hands it over: start marker, children, span."""
    log._on_start(startup.TRACE, start, fun_name=name)
    for child in children:
        _trace(log, *child)
    log._on_span(startup.TRACE, start, end, fun_name=name)


def test_self_time_is_the_duration_less_the_children():
    log = startup.StartupLog(clock=FakeClock())
    _trace(log, "step", 0.0, 10.0, [
        ("body", 1.0, 4.0, [("leaf", 2.0, 3.0)]),
        ("body", 5.0, 9.0),
    ])
    entry = log.snapshot()["functions"]["step"]
    assert entry["trace_s"] == 10.0 and entry["trace_self_s"] == 3.0
    assert entry["inner_traces"] == 3
    assert entry["inner"] == {"body": {"count": 2, "self_s": 6.0},
                              "leaf": {"count": 1, "self_s": 1.0}}
    assert log.snapshot()["totals"]["traces"] == 4
    assert log.compile_seconds() == 10.0


def test_a_program_compiled_inside_a_trace_is_counted_once_in_the_seconds():
    log = startup.StartupLog(clock=FakeClock())
    log._on_start(startup.TRACE, 0.0, fun_name="step")
    log._on_start(startup.BACKEND, 1.0, fun_name="jit(constant)")
    log._on_event(startup.CACHE_HIT)
    log._on_duration(startup.CACHE_FETCH, 0.5)
    log._on_span(startup.BACKEND, 1.0, 3.0, fun_name="jit(constant)")
    log._on_span(startup.TRACE, 0.0, 5.0, fun_name="step")
    snap = log.snapshot()
    assert snap["totals"]["seconds"] == 5.0 and snap["totals"]["backend_s"] == 2.0
    assert snap["functions"]["step"]["trace_self_s"] == 3.0
    constant = snap["functions"]["constant"]
    assert constant["cache_hits"] == 1 and constant["fetch_s"] == 0.5


def test_the_log_stays_bounded_under_100000_events():
    log = startup.StartupLog(clock=FakeClock())
    t = 0.0
    for i in range(1000):
        log._on_start(startup.TRACE, t, fun_name=f"outer_{i}")
        for j in range(99):
            name = f"inner_{i}_{j}"
            log._on_start(startup.TRACE, t, fun_name=name)
            log._on_span(startup.TRACE, t, t + 1e-3, fun_name=name)
            t += 1e-3
        log._on_span(startup.TRACE, t - 0.099, t, fun_name=f"outer_{i}")
        log._on_start(startup.BACKEND, t, fun_name=f"jit(outer_{i})")
        log._on_span(startup.BACKEND, t, t + 1e-3, fun_name=f"jit(outer_{i})")
    snap = log.snapshot()
    assert snap["totals"]["traces"] == 100_000 and snap["totals"]["compiles"] == 1000
    assert len(snap["functions"]) == startup.MAX_FUNCTIONS + 1
    assert all(len(f["inner"]) <= startup.TOP_INNER for f in snap["functions"].values())
    other = snap["functions"][startup.OTHER]
    assert other["traces"] == 1000 - startup.MAX_FUNCTIONS
    assert log._local.frames == [] and log._local.inner == {}
    # one outermost trace with more distinct inner names than it may hold
    log._on_start(startup.TRACE, 0.0, fun_name="wide")
    for j in range(startup.MAX_INNER_NAMES + 10):
        log._on_start(startup.TRACE, 0.0, fun_name=f"n{j}")
        if j == startup.MAX_INNER_NAMES + 9:
            assert len(log._local.inner) == startup.MAX_INNER_NAMES + 1    # and <other>
        log._on_span(startup.TRACE, 0.0, 1e-6, fun_name=f"n{j}")
    log._on_span(startup.TRACE, 0.0, 1.0, fun_name="wide")


def test_block_names_phases_roles_functions_and_totals():
    clock = FakeClock()
    log = startup.StartupLog(clock=clock)
    with log.phase("build_model"):
        clock.advance(1.5)
    log.mark_role("train_step", "step")
    _trace(log, "step", 0.0, 4.0, [("streams_maps", 0.5, 3.5)])
    log._on_start(startup.LOWER, 4.0, fun_name="jit(step)")
    log._on_span(startup.LOWER, 4.0, 5.0, fun_name="jit(step)")
    log._on_start(startup.BACKEND, 5.0, fun_name="jit(step)")
    log._on_span(startup.BACKEND, 5.0, 25.0, fun_name="jit(step)")
    text = "\n".join(startup.block(log.snapshot()))
    assert "setup/build_model: 1.500 s, self 1.500 s" in text
    assert "train_step (step): trace 4.000 s with 1 inner traces, lower 1.000 s, compile 20.000 s" in text
    assert "streams_maps x1 3.000 s" in text
    assert "1 compiles (0 fetched, 0 written), 0 recompiles, 25.000 s in all" in text
    assert log.scalars() == {"compile/seconds_total": 25.0, "compile/compiles_total": 1.0,
                             "compile/recompiles_total": 0.0}


# ------------------------------------------- the ledger and the step timeline


def test_the_ledgers_compile_bucket_is_the_logs_seconds():
    clock = FakeClock()
    log = startup.StartupLog(clock=clock)
    _trace(log, "before_the_run", 0.0, 2.0)         # not this ledger's
    led = GoodputLedger(clock=clock, compile_seconds=log.compile_seconds)
    with led.phase("init"):
        clock.advance(10.0)
        _trace(log, "init", 0.0, 4.0)               # 4 s of init were tracing
    # the first step: 30 s of which 29 trace, lower and compile
    clock.advance(30.0)
    _trace(log, "step", 0.0, 29.0)
    led.note_step({"total_ms": 30_000.0, "compile_ms": 29_000.0})
    for _ in range(3):
        clock.advance(1.0)
        led.note_step({"total_ms": 1000.0, "wait_data_ms": 200.0, "compile_ms": 0.0})
    # a new shape reaches the step in mid-run: 20 s of a 21 s step
    clock.advance(21.0)
    _trace(log, "step", 0.0, 20.0)
    led.note_step({"total_ms": 21_000.0, "compile_ms": 20_000.0})
    s = led.summary()
    b = s["buckets_s"]
    assert b["compile"] == pytest.approx(log.compile_seconds() - 2.0) == pytest.approx(53.0)
    assert b["init"] == pytest.approx(6.0)
    assert b["step"] == pytest.approx(1.0 + 3 * 0.8 + 1.0)   # the recompile left `step`
    assert b["data_stall"] == pytest.approx(0.6)
    assert b["unattributed"] == pytest.approx(0.0)
    assert s["steps_productive"] == 5
    assert sum(s["fractions"].values()) == pytest.approx(1.0, abs=1e-12)


def test_the_timeline_puts_a_steps_compile_seconds_into_its_record(log):
    import jax.numpy as jnp

    outer_fn = _nested_pair()
    tl = StepTimeline(window=4)
    tl.start_step(11)
    assert startup._LOG.current_step == 11
    tl.end_step()
    assert startup._LOG.current_step is None
    # the process's own log feeds the timeline
    startup.install()
    tl.start_step(12)
    before = startup.compile_seconds()
    outer_fn(jnp.ones(13)).block_until_ready()
    record = tl.end_step()
    assert record["compile_ms"] == pytest.approx((startup.compile_seconds() - before) * 1e3)
    assert 0 < record["compile_ms"] <= record["total_ms"]
    tl.start_step(13)
    outer_fn(jnp.ones(13)).block_until_ready()
    assert tl.end_step()["compile_ms"] == 0.0


def test_a_shape_change_in_a_loop_logs_one_recompile_naming_the_step(
        caplog, warn_every_recompile):
    """The process's own log, as the trainer's loop feeds it: five steps, a
    batch of another shape at the fourth."""
    import jax
    import jax.numpy as jnp

    startup.install()

    @jax.jit
    def loop_step_for_test(x):
        return x.sum()

    tl = StepTimeline(window=8)
    batches = [jnp.ones(6 if step == 3 else 4) for step in range(5)]
    before = startup.snapshot()["totals"]["recompiles"]
    records = []
    with caplog.at_level(logging.WARNING, logger="rt1_tpu.obs.startup"):
        for step, batch in enumerate(batches):
            tl.start_step(step)
            loop_step_for_test(batch).block_until_ready()
            records.append(tl.end_step())
    # (making the odd batch compiled `broadcast_in_dim` at a new shape: a
    # recompile of its own, outside any step)
    warnings = [r.getMessage() for r in caplog.records if "during step" in r.getMessage()]
    assert len(warnings) == 1
    assert warnings[0].startswith("recompiled loop_step_for_test: the compile took")
    assert "during step 3" in warnings[0]
    assert startup.snapshot()["totals"]["recompiles"] == before + 1
    assert [r["compile_ms"] > 0 for r in records] == [True, False, False, True, False]

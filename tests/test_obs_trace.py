"""obs/trace.py: thread-safe Chrome-trace recording + disabled fast path."""

import glob
import json
import subprocess
import sys
import threading

import pytest

from rt1_tpu.obs import trace


@pytest.fixture(autouse=True)
def _clean_global_tracer():
    """The module-level recorder is process-wide state; isolate every test."""
    trace._tracer = None
    yield
    trace._tracer = None


def test_disabled_tracer_is_a_shared_noop(monkeypatch):
    """The ring's part of a span is the shared no-op while no recorder is
    installed: alone in a process without jax (the serve stub), beside an
    inactive TraceMe where jax is loaded."""
    assert not trace.enabled()
    import jax

    s = trace.span("anything", step=1)
    assert isinstance(s, jax.profiler.TraceAnnotation)
    with s:
        pass
    monkeypatch.delitem(sys.modules, "jax")
    s = trace.span("anything", step=1)
    assert s is trace._NULL_SPAN
    with s:
        pass
    monkeypatch.undo()
    # Counter/complete/dump are no-ops, not errors.
    trace.complete("marker", trace.now_us(), 1.0)
    trace.counter("depth", 3)
    assert trace.dump() is None

    # Nothing recorded once enabled afterwards: the disabled-period calls
    # left no buffered state behind.
    rec = trace.enable()
    assert rec.to_dict()["traceEvents"] == []


def test_spans_from_two_threads_serialize_to_valid_chrome_trace(tmp_path):
    path = str(tmp_path / "trace.json")
    trace.enable(path)

    def worker():
        for i in range(3):
            with trace.span("worker_assemble", ticket=i):
                pass

    t = threading.Thread(target=worker, name="rt1-test-worker")
    with trace.span("main_phase", step=0):
        t.start()
        t.join()
    trace.counter("queue_depth", 2)
    written = trace.dump()
    assert written == path

    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    spans = [e for e in events if e["ph"] == "X"]
    tids = {e["tid"] for e in spans}
    assert len(tids) >= 2, "expected spans from the main + worker threads"
    for e in spans:
        assert {"name", "pid", "tid", "ts", "dur"} <= set(e)
        assert e["dur"] >= 0
    # Thread-name metadata present for both threads, with the worker's name.
    names = {
        e["tid"]: e["args"]["name"]
        for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert set(names) >= tids
    assert "rt1-test-worker" in names.values()
    # Counter event carries its series.
    counters = [e for e in events if e["ph"] == "C"]
    assert counters and counters[0]["args"] == {"value": 2}


def test_span_args_and_stamped_events(tmp_path):
    rec = trace.enable()
    with trace.span("phase", step=7):
        trace.complete("inside", trace.now_us(), 0.0, detail="x")
    inside, phase = [e for e in rec.to_dict()["traceEvents"] if e["ph"] == "X"]
    assert phase["args"] == {"step": 7}
    assert inside["name"] == "inside" and inside["args"] == {"detail": "x"}
    # A span stamped after the fact falls inside the live one on the same
    # thread's clock.
    assert phase["ts"] <= inside["ts"] <= phase["ts"] + phase["dur"]


def test_ring_bounds_memory_and_reports_drops():
    rec = trace.enable(max_events=10)
    for i in range(25):
        with trace.span("s", i=i):
            pass
    doc = rec.to_dict()
    spans = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 10
    # Most recent survive.
    assert [e["args"]["i"] for e in spans] == list(range(15, 25))
    assert doc["otherData"]["dropped_events"] == 15


def test_enable_updates_existing_recorder(tmp_path):
    """A stale recorder (aborted prior run) must not hijack the new run's
    dump path or ring size — explicit enable() args win, events survive."""
    rec = trace.enable(str(tmp_path / "old.json"), max_events=100)
    with trace.span("kept"):
        pass
    same = trace.enable(str(tmp_path / "new.json"), max_events=5)
    assert same is rec
    assert rec.path == str(tmp_path / "new.json")
    assert rec._events.maxlen == 5
    assert [e["name"] for e in rec.to_dict()["traceEvents"] if e["ph"] == "X"] == ["kept"]
    # Omitted args keep the installed configuration.
    trace.enable()
    assert rec.path == str(tmp_path / "new.json")
    assert rec._events.maxlen == 5


def test_disable_dumps_when_path_configured(tmp_path):
    path = str(tmp_path / "out" / "trace.json")
    trace.enable(path)
    with trace.span("s"):
        pass
    trace.disable()
    assert not trace.enabled()
    with open(path) as f:
        doc = json.load(f)
    assert any(e["ph"] == "X" for e in doc["traceEvents"])


def _profiled_events(tmp_path, body):
    """Every (line index, event name, stats) of a CPU profile taken around ``body``."""
    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return [
        (i, e.name, {k: str(v) for k, v in e.stats})
        for plane in ProfileData.from_file(path).planes
        for i, line in enumerate(plane.lines)
        for e in line.events
        if e.name.startswith(trace.PROFILE_PREFIX)
    ]


@pytest.mark.parametrize("ring", [False, True], ids=["ring_off", "ring_on"])
def test_span_is_a_traceme_on_the_profilers_clock(tmp_path, ring):
    """Whoever runs a profile finds the program's spans in it as
    ``rt1/<name>`` with their args, each on its thread's line, whether or
    not the ring records them too."""
    rec = trace.enable() if ring else None

    def body():
        def worker():
            with trace.span("feeder/assemble", ticket=7):
                pass

        t = threading.Thread(target=worker, name="rt1-test-worker")
        with trace.span("feeder/next", ticket=7, ready=3):
            t.start()
            t.join(timeout=30)
        assert not t.is_alive()
        trace.complete("after_the_fact", trace.now_us(), 5.0)

    events = _profiled_events(tmp_path, body)
    by_name = {name: (line, stats) for line, name, stats in events}
    assert set(by_name) == {"rt1/feeder/assemble", "rt1/feeder/next"}
    assert by_name["rt1/feeder/next"][1] == {"ticket": "7", "ready": "3"}
    assert by_name["rt1/feeder/assemble"][1] == {"ticket": "7"}
    assert by_name["rt1/feeder/next"][0] != by_name["rt1/feeder/assemble"][0]
    if ring:
        names = [e["name"] for e in rec.to_dict()["traceEvents"] if e["ph"] == "X"]
        assert sorted(names) == ["after_the_fact", "feeder/assemble", "feeder/next"]


def test_a_process_without_jax_opens_no_traceme_and_imports_none():
    probe = (
        "import sys\n"
        "from rt1_tpu.obs import trace\n"
        "assert trace.span('x', a=1) is trace._NULL_SPAN\n"
        "rec = trace.enable()\n"
        "with trace.span('x', a=1):\n"
        "    pass\n"
        "assert [e['name'] for e in rec.to_dict()['traceEvents'] if e['ph'] == 'X'] == ['x']\n"
        "assert 'jax' not in sys.modules\n"
    )
    done = subprocess.run([sys.executable, "-c", probe], capture_output=True, text=True, timeout=120)
    assert done.returncode == 0, done.stderr

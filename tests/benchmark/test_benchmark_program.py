"""benchmarks/trace/program.py: device time by scope group and the program's
host spans, on hand-made events with known answers, on one whole step
recorded on the chip (tests/benchmark/data/program_rt1_tpu_v5e.json.gz: the
fifth traced run of rt1-b3-lt at batch 16 in PR 25's first chip call, its ops
with their scopes and the host spans around it), on a hand-encoded protobuf
and on a profile taken here on the CPU, which has no device plane."""

import glob
import os
import threading

import pytest

from bench_testlib import DATA
from benchmarks.trace import program, reduce, xplane

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = reduce.OPS_LINE + "#3", reduce.MODULES_LINE + "#2"
LOOP, WORKER = "python3#9", "python3#8"
US = 1000
FWD = "jit(train_step_guarded)/jvp(RT1Policy)/RT1Policy._tokenize_images/image_tokenizer/encoder/EfficientNet_0/"
BWD = FWD.replace("jvp(RT1Policy)", "transpose(jvp(RT1Policy))")

SCOPES = {
    "fusion.1": FWD + "block_2/depthwise/conv/conv_general_dilated:",
    "fusion.2": BWD + "block_2/depthwise/conv/conv_general_dilated:",
    "fusion.3": BWD + "block_9/expand/conv/conv_general_dilated:",
    "fusion.4": "jit(train_step_guarded)/optimizer/mul:",
    "fusion.5": "jit(train_step_guarded)/jvp(RT1Policy)/a_module_renamed/add:",
    "while.1": "jit(train_step_guarded)/jvp(RT1Policy)/loss/while:",
    "fusion.6": "jit(train_step_guarded)/jvp(RT1Policy)/loss/while/body/add:",
    "fusion.7": "jit(train_step_guarded)/health/reduce_sum:",
    "fusion.8": "jit(train_step_guarded)/jvp(RT1Policy)/RT1Policy._tokenize_images/preprocess/convert_element_type:",
    "fusion.9": "jit(train_step_guarded)/transpose(jvp(RT1Policy))/RT1Policy._tokenize_images/image_tokenizer/token_learner/conv1/dot:",
    "fusion.10": "jit(train_step_guarded)/cast_bf16/convert_element_type:",
    "fusion.11": BWD.replace("EfficientNet_0/", "") + "film/projection_add/dot_general:",
}


def _events():
    ev = lambda plane, line, name, start, dur, **args: (  # noqa: E731
        plane, line, name, start * US, dur * US, {k: str(v) for k, v in args.items()})
    return [
        # the harness's window: 0 .. 400 us
        ev(HOST, LOOP, "bench/h2d", 0, 10), ev(HOST, LOOP, "bench/sync", 10, 390),
        # the program's spans: the loop's thread and a worker's
        ev(HOST, LOOP, "rt1/feeder/next", 1, 2, ticket=7, ready=3),
        ev(HOST, LOOP, "rt1/h2d/put", 4, 5, ticket=7, bytes=1000),
        ev(HOST, LOOP, "rt1/feeder/next", 201, 4, ticket=8, ready=1),
        ev(HOST, WORKER, "rt1/feeder/assemble", 150, 60, ticket=9),
        ev(HOST, WORKER, "rt1/feeder/put_wait", 0, 150, ticket=8),
        # two whole runs of the step, a run the trace cut at its start, another program
        ev(DEV, MODS, "jit_train_step_guarded(1)", -50, 40),
        ev(DEV, MODS, "jit_train_step_guarded(1)", 20, 160),
        ev(DEV, MODS, "jit_fold_in(2)", 185, 4),
        ev(DEV, MODS, "jit_train_step_guarded(1)", 220, 160),
        # run 1: 20 .. 180
        ev(DEV, OPS, "fusion.1", 20, 30), ev(DEV, OPS, "fusion.2", 50, 40),
        ev(DEV, OPS, "while.1", 90, 50), ev(DEV, OPS, "fusion.6", 95, 10),
        ev(DEV, OPS, "fusion.6", 110, 20),
        ev(DEV, OPS, "fusion.4", 140, 20), ev(DEV, OPS, "copy.5", 160, 5),
        ev(DEV, OPS, "fusion.5", 165, 15),
        # between the runs: another program's op, and in the cut run before the window
        ev(DEV, OPS, "fusion.4", 185, 4), ev(DEV, OPS, "fusion.1", -40, 30),
        # run 2: 220 .. 380
        ev(DEV, OPS, "fusion.1", 220, 30), ev(DEV, OPS, "fusion.2", 250, 40),
        ev(DEV, OPS, "fusion.3", 290, 30), ev(DEV, OPS, "fusion.7", 320, 10),
        ev(DEV, OPS, "fusion.8", 330, 10), ev(DEV, OPS, "fusion.9", 340, 10),
        ev(DEV, OPS, "fusion.10", 350, 10), ev(DEV, OPS, "fusion.11", 360, 20),
    ]


@pytest.fixture(scope="module")
def hand_made():
    return program.reduce_events(_events(), SCOPES)


def test_whole_runs_of_the_step_program(hand_made):
    assert hand_made["step_program"] == "jit_train_step_guarded(1)"
    assert hand_made["runs"] == 2                  # the run cut at the window's start is out
    assert hand_made["step_s"] == pytest.approx(160e-6)


@pytest.mark.parametrize("group, us_a_step", [
    ("encoder_hi_res_fwd", 30.0),       # fusion.1 in both runs; the one in the cut run is out
    ("encoder_hi_res_bwd", 40.0),       # 'transpose(' in the path
    ("encoder_rest", 25.0),             # block 9 (30) and the FiLM layer (20), both directions in one group
    ("decoder", 25.0),                  # the while by its self time 50 - 10 - 20, and its body 30: 50 in run 1
    ("optimizer", 15.0),                # 20 in run 1 and cast_bf16 10 in run 2; the op between the runs is out
    ("health", 5.0),
    ("preprocess", 5.0),
    ("token_learner", 5.0),
    ("unscoped", 10.0),                 # copy.5 has no scope (5), the renamed module matches no rule (15)
])
def test_scope_groups_by_self_time(hand_made, group, us_a_step):
    assert hand_made["scope_s"][group] == pytest.approx(us_a_step * 1e-6)


def test_the_groups_sum_to_the_ops_self_time(hand_made):
    assert sum(hand_made["scope_s"].values()) == pytest.approx(hand_made["op_self_s"])
    assert hand_made["op_self_s"] == pytest.approx(160e-6)   # both runs are busy end to end
    assert set(hand_made["scope_s"]) == set(program.group_names(program.load_rules()))
    name, group, seconds, scope = hand_made["top_ops"][0]
    assert (name, group, scope) == ("fusion.2", "encoder_hi_res_bwd", SCOPES["fusion.2"])
    assert seconds == pytest.approx(40e-6)


def test_spans_by_name_with_their_arguments(hand_made):
    spans = hand_made["spans"]
    assert set(spans) == {"rt1/feeder/next", "rt1/h2d/put", "rt1/feeder/assemble",
                          "rt1/feeder/put_wait"}
    nxt = spans["rt1/feeder/next"]
    assert nxt["count"] == 2 and nxt["mean_s"] == pytest.approx(3e-6)
    assert nxt["args"]["ready"] == pytest.approx(2.0) and nxt["lines"] == [LOOP]
    assert spans["rt1/h2d/put"]["args"]["bytes"] == 1000.0
    assert spans["rt1/feeder/assemble"]["lines"] == [WORKER]


def test_a_gap_goes_to_the_span_of_whichever_thread_covers_it(hand_made):
    gaps = {round(g["start_s"] * 1e6): g for g in hand_made["gaps"]}
    assert set(gaps) == {0, 180, 189, 380}
    # 0-20: the loop's thread was in next (2) and put (5); the worker's put_wait is no cause
    assert gaps[0]["span"] == "rt1/h2d/put" and gaps[0]["covered"] == pytest.approx(0.25)
    # 180-185 and 189-220: the worker was assembling while the loop sat in bench/sync
    assert gaps[180]["span"] == "rt1/feeder/assemble" and gaps[180]["covered"] == 1.0
    assert gaps[189]["span"] == "rt1/feeder/assemble"
    assert gaps[189]["covered"] == pytest.approx(21 / 31)
    assert gaps[380]["span"] is None and gaps[380]["seconds"] == pytest.approx(20e-6)
    lines = program.describe(hand_made)
    assert any("no rt1/* span covered it" in l for l in lines)


def test_a_stall_with_a_step_queued_is_no_gap_of_the_program():
    """reduce.py takes it out of the window; it is not attributed here either."""
    events = [e for e in _events() if not (e[0] == DEV and e[3] >= 200 * US)]
    events += [(DEV, MODS, "jit_train_step_guarded(1)", 385 * US, 10 * US, {}),
               (DEV, OPS, "fusion.1", 385 * US, 10 * US, {})]
    starts = [round(g["start_s"] * 1e6) for g in program.reduce_events(events, SCOPES)["gaps"]]
    assert 189 not in starts and 180 in starts       # 189-385 us: longer than a step, under bench/sync


FIXTURE = os.path.join(DATA, "program_rt1_tpu_v5e.json.gz")


def test_one_step_recorded_on_the_chip():
    assert os.path.getsize(FIXTURE) < 1 << 20
    events, scopes = program.load_events(FIXTURE)
    s = program.reduce_events(events, scopes)
    assert "train_step" in s["step_program"] and s["runs"] == 1
    assert s["step_s"] == pytest.approx(126.366e-3, abs=1e-6)
    ms = {k: v * 1e3 for k, v in s["scope_s"].items()}
    assert sum(ms.values()) == pytest.approx(s["op_self_s"] * 1e3)
    assert abs(sum(ms.values()) - s["step_s"] * 1e3) < 0.01 * s["step_s"] * 1e3
    assert ms == pytest.approx({
        "preprocess": 0.1123, "health": 0.6278, "optimizer": 1.1396, "decoder": 2.7672,
        "token_learner": 0.144, "encoder_hi_res_fwd": 16.6292, "encoder_hi_res_bwd": 47.5552,
        "encoder_rest": 50.9309, "unscoped": 6.0731}, abs=1e-3)
    assert ms["unscoped"] < 0.05 * s["step_s"] * 1e3
    name, group, seconds, scope = s["top_ops"][0]
    assert name == "convert_reduce_fusion.1" and group == "encoder_hi_res_bwd"
    assert "transpose(jvp(RT1Policy))" in scope and "/block_2/depthwise/" in scope
    assert s["spans"]["rt1/feeder/next"]["args"]["ready"] == 4.0
    assert s["spans"]["rt1/h2d/put"]["args"]["bytes"] == 33817792.0
    assert len(s["spans"]["rt1/feeder/put_wait"]["lines"]) == 2      # both workers were ahead
    assert all(g["span"] != "rt1/feeder/put_wait" for g in s["gaps"])


def test_the_first_reduction_reads_what_it_read():
    """Nothing of this file's subject is in reduce.py's summary, and its keys
    are PR 24's: the metrics the benchmark has read what they read."""
    s = reduce.reduce_rows(xplane.load_rows(os.path.join(DATA, "rows_rt1_tpu_v5e.json.gz")))
    assert sorted(s) == sorted([
        "window_s", "busy_s", "idle_share", "in_program_idle_s", "device_planes", "programs",
        "device_ops", "idle_gaps", "longest_gap_s", "queued_stalls", "slice_s"])


# -- the file's wire format

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _map_entry(number, key, message):
    return _field(number, _field(1, key) + _field(2, message))


def test_event_metadata_stats_from_the_wire_format(tmp_path):
    """xplane.proto: XSpace.planes 1; XPlane name 2, lines 3, event_metadata 4,
    stat_metadata 5; XEventMetadata name 2, stats 5; XStat metadata_id 1,
    double 2 (fixed64), str_value 5, ref_value 7."""
    stat = lambda **kw: b"".join(_field({"id": 1, "text": 5, "ref": 7}[k], v) for k, v in kw.items())  # noqa: E731
    double = _varint(2 << 3 | 1) + b"\0" * 8
    plane = (
        _field(2, "/device:TPU:0")
        + _field(3, _field(2, "XLA Ops") + _field(4, _field(1, 1) + _field(2, 5) + _field(3, 7)))
        + _map_entry(5, 7, _field(1, 7) + _field(2, "tf_op"))
        + _map_entry(5, 8, _field(1, 8) + _field(2, "flops"))
        + _map_entry(5, 9, _field(1, 9) + _field(2, "jit(f)/optimizer/mul:"))
        + _map_entry(4, 1, _field(1, 1) + _field(2, "%fusion.1 = f32[] fusion()")
                     + _field(5, _field(1, 8) + double)
                     + _field(5, stat(id=7, text="jit(f)/health/add:")))
        + _map_entry(4, 2, _field(1, 2) + _field(2, "%fusion.2 = f32[] fusion()")
                     + _field(5, stat(id=7, ref=9)))
        + _map_entry(4, 3, _field(1, 3) + _field(2, "%copy-start.3 = f32[] copy-start()"))
    )
    other = _field(2, "/host:CPU") + _map_entry(4, 1, _field(1, 1) + _field(2, "rt1/step"))
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(_field(1, plane) + _field(1, other) + _field(4, "hostname"))
    assert program.metadata_stats(str(path)) == {"/device:TPU:0": {
        "%fusion.1 = f32[] fusion()": "jit(f)/health/add:",
        "%fusion.2 = f32[] fusion()": "jit(f)/optimizer/mul:"}}


def test_a_profile_without_a_device_plane_gives_spans_and_no_scopes(tmp_path):
    """The CPU's profile: the program's spans are there with their threads and
    arguments; there is no device plane, so no scope table, and no error."""
    import jax
    import jax.numpy as jnp

    from rt1_tpu.obs import trace

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        def worker():
            with trace.span("feeder/assemble", ticket=3):
                pass

        t = threading.Thread(target=worker)
        with trace.span("feeder/next", ticket=3, ready=2):
            t.start()
            t.join(timeout=30)
        jax.jit(lambda x: x * 2)(jnp.ones((4,))).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    s = program.reduce_xplane(path)
    assert "scope_s" not in s and s["runs"] == 0 and s["step_program"] is None
    assert s["spans"]["rt1/feeder/next"]["args"] == {"ticket": 3.0, "ready": 2.0}
    assert s["spans"]["rt1/feeder/assemble"]["lines"] != s["spans"]["rt1/feeder/next"]["lines"]
    assert program.describe(s)

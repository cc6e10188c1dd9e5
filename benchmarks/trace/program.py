"""What the program itself says in a profile: the device's time by scope, and
the program's own host spans.

A device op's scope is the ``tf_op`` stat of its event metadata on the device
plane, ``jit(train_step_guarded)/jvp(RT1Policy)/.../block_3/depthwise/conv/
conv_general_dilated:``: the HLO ``op_name``, which Flax's module scopes and
the program's ``jax.named_scope``s make.  ``jax.profiler.ProfileData`` gives
an event's own stats and not its metadata's, so the few fields needed are read
from the file's protobuf wire format (``metadata_stats``); events, starts and
durations come from ``ProfileData`` as in xplane.py.  A fused op carries the
scope of the fusion's root.  The names are those of the executable that ran: a
persistent-cache hit on one compiled before a scope was renamed shows the old
name (the cache's key leaves metadata out).

``reduce_events`` gives, for the one profile:

* ``scope_s``: device seconds a step per scope group (scopes.json: ordered
  rules, first match wins), each op counted by its self time (its duration
  minus the events nested in it on the "XLA Ops" line, a ``while``'s body),
  only ops inside whole runs of the step program, divided by the runs;
* ``tally_s``, where the caller hands tallies over: the same self times added
  to every tally whose pattern the op's scope holds.  Tallies do not
  partition: they overlap the groups and each other, and an op may be in none;
* ``spans``: per ``rt1/*`` span name the count, total and mean seconds, and
  the mean of every numeric argument (``ready`` of ``rt1/feeder/next``);
* ``gaps``: each idle gap of the kept window (as reduce.py finds and keeps
  them) by the ``rt1/*`` span of any host thread that covers most of it
  (``rt1/feeder/put_wait`` apart: it says the feeder was ahead).
"""

from __future__ import annotations

import json
import os
import re
from collections import defaultdict
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

from benchmarks.trace import reduce
from benchmarks.trace.xplane import ANNOTATION_PREFIX, is_device_plane, short_name

PROGRAM_PREFIX = "rt1/"
# a worker that waits to hand over a finished batch is not what the device waits for
NOT_A_CAUSE = (PROGRAM_PREFIX + "feeder/put_wait",)
SCOPE_STAT = "tf_op"
UNSCOPED = "unscoped"
RULES_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "scopes.json")
MIN_GAP_NS = 1000          # shorter gaps are the device between two ops

# (plane, line, name, start_ns, duration_ns, args); ``line`` is the line's
# name and its index in the plane, so that two threads of one name stay apart
Event = Tuple[str, str, str, int, int, Dict[str, str]]


# -- the protobuf wire format, as far as tsl/profiler/protobuf/xplane.proto needs it

def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf, i: int, end: int) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message; a length-delimited
    value is its (start, end) in ``buf`` and is not copied."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value = (i, i + size)
            i += size
        elif wire == 1:
            value, i = None, i + 8
        elif wire == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} is not in an xplane file")
        yield key >> 3, wire, value


def _text(buf, span: Tuple[int, int]) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


# XSpace.planes = 1; XPlane: name 2, event_metadata 4, stat_metadata 5 (maps:
# entries of key 1, value 2); XEventMetadata: name 2, stats 5; XStatMetadata:
# name 2; XStat: metadata_id 1, str_value 5, ref_value 7
def metadata_stats(path: str, stat: str = SCOPE_STAT) -> Dict[str, Dict[str, str]]:
    """``{plane: {event name: value}}`` of one string stat of the planes'
    event metadata.  Lines and events are skipped over, not parsed."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, wire, plane_span in _fields(buf, 0, len(buf)):
        if field != 1 or wire != 2:
            continue
        plane_name = ""
        stat_names: Dict[int, str] = {}
        events: List[Tuple[int, int]] = []
        for f2, w2, span in _fields(buf, *plane_span):
            if f2 == 2 and w2 == 2:
                plane_name = _text(buf, span)
            elif f2 == 4 and w2 == 2:
                events.extend(v for k, w, v in _fields(buf, *span) if k == 2 and w == 2)
            elif f2 == 5 and w2 == 2:
                key, name = 0, ""
                for k, w, v in _fields(buf, *span):
                    if k == 1 and w == 0:
                        key = v
                    elif k == 2 and w == 2:
                        name = next((_text(buf, s) for kk, ww, s in _fields(buf, *v)
                                     if kk == 2 and ww == 2), "")
                stat_names[key] = name
        wanted = {k for k, v in stat_names.items() if v == stat}
        if not wanted:
            continue
        values: Dict[str, str] = {}
        for span in events:
            name, value = "", None
            for k, w, v in _fields(buf, *span):
                if k == 2 and w == 2:
                    name = _text(buf, v)
                elif k == 5 and w == 2:
                    fields = {kk: vv for kk, _, vv in _fields(buf, *v)}
                    if fields.get(1) in wanted:
                        if 5 in fields:
                            value = _text(buf, fields[5])
                        elif 7 in fields:
                            value = stat_names.get(fields[7], "")
            if value is not None:
                values[name] = value
        out[plane_name] = values
    return out


def events_from_xplane(path: str) -> Tuple[List[Event], Dict[str, str]]:
    """Device events and the ``rt1/*`` and ``bench/*`` host events with their
    arguments, and the scope of every device op by its short name."""
    from jax.profiler import ProfileData

    events: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for index, line in enumerate(plane.lines):
            key = f"{line.name}#{index}"
            for e in line.events:
                if device:
                    events.append((plane.name, key, short_name(e.name), int(e.start_ns),
                                   int(e.duration_ns), {}))
                elif e.name.startswith((PROGRAM_PREFIX, ANNOTATION_PREFIX)):
                    events.append((plane.name, key, e.name, int(e.start_ns), int(e.duration_ns),
                                   {k: str(v) for k, v in e.stats}))
    scopes: Dict[str, str] = {}
    for plane, values in metadata_stats(path).items():
        if is_device_plane(plane):
            scopes.update((short_name(name), value) for name, value in values.items())
    return events, scopes


# -- scopes to groups

def compile_rules(rules: Sequence[Dict[str, Any]]) -> List[Dict[str, Any]]:
    return [dict(r, regex=re.compile(r["pattern"])) for r in rules]


def load_rules(path: str = RULES_FILE) -> List[Dict[str, Any]]:
    with open(path) as f:
        return compile_rules(json.load(f)["rules"])


def group_names(rules: Sequence[Dict[str, Any]]) -> List[str]:
    names: List[str] = []
    for r in rules:
        for n in ([r["group"] + "_fwd", r["group"] + "_bwd"] if r.get("split_bwd")
                  else [r["group"]]):
            if n not in names:
                names.append(n)
    return names + [UNSCOPED]


def group_of(scope: Optional[str], rules: Sequence[Dict[str, Any]]) -> str:
    """First rule whose pattern is found in the scope; a rule with
    ``split_bwd`` gives ``<group>_bwd`` where the path holds ``transpose(``
    and ``<group>_fwd`` elsewhere.  No scope, or no rule: ``unscoped``."""
    if scope:
        for r in rules:
            if r["regex"].search(scope):
                if r.get("split_bwd"):
                    return r["group"] + ("_bwd" if "transpose(" in scope else "_fwd")
                return r["group"]
    return UNSCOPED


# -- the reduction

def _line_is(event: Event, line: str) -> bool:
    return event[1].split("#", 1)[0] == line


def self_times(ops: Sequence[Event]) -> List[Tuple[Event, int]]:
    """Each op with its duration less the events nested in it."""
    out: List[List[Any]] = []
    stack: List[Tuple[int, int]] = []          # (end, index in out)
    for e in sorted(ops, key=lambda e: (e[3], -e[4])):
        start, end = e[3], e[3] + e[4]
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and end <= stack[-1][0]:
            out[stack[-1][1]][1] -= e[4]
        out.append([e, e[4]])
        stack.append((end, len(out) - 1))
    return [(e, max(0, t)) for e, t in out]


def _window(events: Sequence[Event]) -> Optional[Tuple[int, int]]:
    """As reduce.py takes it: the span of the harness's annotations; of the
    program's own spans in a profile that has no harness."""
    for prefix in (ANNOTATION_PREFIX, PROGRAM_PREFIX):
        notes = [e for e in events if e[2].startswith(prefix)]
        if notes:
            return min(e[3] for e in notes), max(e[3] + e[4] for e in notes)
    return None


def reduce_events(events: Sequence[Event], scopes: Dict[str, str],
                  rules: Optional[Sequence[Dict[str, Any]]] = None,
                  tallies: Sequence[Dict[str, Any]] = ()) -> Dict[str, Any]:
    rules = load_rules() if rules is None else rules
    window = _window(events)
    planes = sorted({e[0] for e in events if is_device_plane(e[0])})
    device = [e for e in events if planes and e[0] == planes[0]]
    ops = [e for e in device if _line_is(e, reduce.OPS_LINE)]
    program_spans = [e for e in events if e[2].startswith(PROGRAM_PREFIX)]

    # whole runs of the step program: the one with most device time
    runs_of: Dict[str, List[Event]] = defaultdict(list)
    for e in device:
        if _line_is(e, reduce.MODULES_LINE) and (
                window is None or (window[0] <= e[3] and e[3] + e[4] <= window[1])):
            runs_of[e[2]].append(e)
    step = max(runs_of, key=lambda k: sum(e[4] for e in runs_of[k])) if runs_of else None
    runs = sorted(runs_of[step], key=lambda e: e[3]) if step else []

    out: Dict[str, Any] = {"step_program": step, "runs": len(runs)}
    if runs:
        out.update(_scope_seconds(ops, runs, scopes, rules, tallies))
    out["spans"] = _span_table(program_spans)
    if window is not None and ops:
        step_ns = sum(r[4] for r in runs) // len(runs) if runs else 0
        out["gaps"] = _gaps(events, ops, program_spans, window, step_ns)
    return out


def _scope_seconds(ops, runs, scopes, rules, tallies=()) -> Dict[str, Any]:
    by_group: Dict[str, int] = defaultdict(int)
    by_tally: Dict[str, int] = defaultdict(int)
    by_op: Dict[str, int] = defaultdict(int)
    bounds = [(r[3], r[3] + r[4]) for r in runs]
    k = 0
    for e, self_ns in self_times(ops):
        while k < len(bounds) and bounds[k][1] <= e[3]:
            k += 1
        if k == len(bounds):
            break
        if e[3] < bounds[k][0]:
            continue
        by_op[e[2]] += self_ns
    for name, ns in by_op.items():
        scope = scopes.get(name)
        by_group[group_of(scope, rules)] += ns
        for t in tallies:
            if scope and t["regex"].search(scope):
                by_tally[t["group"]] += ns
    n = len(runs)
    out = {
        "scope_s": {g: by_group.get(g, 0) / n / 1e9 for g in group_names(rules)},
        "op_self_s": sum(by_group.values()) / n / 1e9,
        "step_s": sum(r[4] for r in runs) / n / 1e9,
        "top_ops": [
            [name, group_of(scopes.get(name), rules), ns / n / 1e9, scopes.get(name, "")]
            for name, ns in sorted(by_op.items(), key=lambda kv: -kv[1])[:10]],
    }
    if tallies:
        out["tally_s"] = {t["group"]: by_tally.get(t["group"], 0) / n / 1e9 for t in tallies}
    return out


def _span_table(program_spans) -> Dict[str, Dict[str, Any]]:
    spans: Dict[str, Dict[str, Any]] = {}
    for e in program_spans:
        s = spans.setdefault(e[2], {"count": 0, "total_s": 0.0, "lines": set(), "args": {}})
        s["count"] += 1
        s["total_s"] += e[4] / 1e9
        s["lines"].add(e[1])
        for key, value in e[5].items():
            try:
                s["args"].setdefault(key, []).append(float(value))
            except ValueError:
                pass
    for s in spans.values():
        s["mean_s"] = s["total_s"] / s["count"]
        s["lines"] = sorted(s["lines"])
        s["args"] = {k: sum(v) / len(v) for k, v in s["args"].items()}
    return spans


def _gaps(events, ops, program_spans, window, step_ns) -> List[Dict[str, Any]]:
    """The idle gaps reduce.py keeps (a stall with a step queued is the
    profiler's and is left out), each with the program's span that covers
    most of it, of whichever thread."""
    lo, hi = window
    busy = reduce.union([(max(e[3], lo), min(e[3] + e[4], hi)) for e in ops
                         if e[3] < hi and e[3] + e[4] > lo])
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    sync = [e for e in events if e[2] == reduce.SYNC]
    causes = [e for e in program_spans if e[2] not in NOT_A_CAUSE]

    def cover(spans, a, b):
        by: Dict[str, int] = defaultdict(int)
        for e in spans:
            c = min(e[3] + e[4], b) - max(e[3], a)
            if c > 0:
                by[e[2]] += c
        return by

    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < MIN_GAP_NS:
            continue
        under_sync = cover(sync, a, b).get(reduce.SYNC, 0)
        if step_ns and b - a > step_ns and under_sync >= reduce.QUEUED_STALL_SYNC_SHARE * (b - a):
            continue
        by = cover(causes, a, b)
        name = max(by, key=by.get) if by else None
        gaps.append({"start_s": (a - lo) / 1e9, "seconds": (b - a) / 1e9, "span": name,
                     "covered": by[name] / (b - a) if name else 0.0})
    return gaps


def reduce_xplane(path: str) -> Dict[str, Any]:
    events, scopes = events_from_xplane(path)
    return reduce_events(events, scopes)


def load_events(path: str) -> Tuple[List[Event], Dict[str, str]]:
    """A recorded ``{"events": [...], "scopes": {...}}`` (tests/benchmark/data)."""
    import gzip

    with gzip.open(path, "rt") as f:
        doc = json.load(f)
    return [tuple(e) for e in doc["events"]], doc["scopes"]


def describe(summary: Dict[str, Any]) -> List[str]:
    """The summary as lines for a log."""
    lines = []
    if "scope_s" in summary:
        step = summary["step_s"]
        lines.append(f"{summary['step_program']}: {summary['runs']} whole runs, {step * 1e3:.3f} ms "
                     f"a run, op self time {summary['op_self_s'] * 1e3:.3f} ms a run; by scope group:")
        for group, s in sorted(summary["scope_s"].items(), key=lambda kv: -kv[1]):
            lines.append(f"  {group:24s} {s * 1e3:9.3f} ms  {s / step * 100:6.2f} %")
        empty = [g for g, s in summary["scope_s"].items() if not s]
        if empty:
            lines.append(f"  no op under {', '.join(empty)}: not in this program, renamed, or the "
                         "executable was compiled before the scope existed (persistent cache hit)")
        for group, s in summary.get("tally_s", {}).items():
            lines.append(f"  tally {group:18s} {s * 1e3:9.3f} ms  {s / step * 100:6.2f} %  "
                         "(beside the groups above, not one of them)")
        for name, group, s, scope in summary["top_ops"]:
            lines.append(f"  op {name} {s * 1e3:.3f} ms in {group}: {scope}")
    for name, s in sorted(summary["spans"].items()):
        args = ", ".join(f"mean {k} {v:.2f}" for k, v in s["args"].items() if k != "ticket")
        lines.append(f"span {name}: {s['count']} on {len(s['lines'])} thread(s), mean "
                     f"{s['mean_s'] * 1e3:.3f} ms, total {s['total_s']:.4f} s"
                     f"{', ' + args if args else ''}")
    by: Dict[str, List[float]] = defaultdict(list)
    for g in summary.get("gaps", []):
        by[g["span"] or "(no rt1/* span covered it)"].append(g["seconds"])
    for name, secs in sorted(by.items(), key=lambda kv: -sum(kv[1])):
        lines.append(f"idle gaps under {name}: {len(secs)}, {sum(secs) * 1e3:.3f} ms in all, "
                     f"longest {max(secs) * 1e3:.3f} ms")
    return lines

"""Second half of the trace reduction: flat rows to what the per-layer
metrics read.

``busy`` is the union of the intervals in which an operation ran on a device
(the "XLA Ops" line of its plane; asynchronous copies, which have a line
of their own, do not count), averaged over the device planes; the
window is the span of the harness's ``bench/*`` annotations, to which every
device interval is clipped.  A program's device time is the sum of its
events on the "XLA Modules" line.  An idle gap is a maximal interval of the
window in which no operation ran; it is attributed to the annotation that
covers most of it.

A gap longer than one run of the step program, spent almost wholly under
``bench/sync``, is a stall with work queued: the host had dispatched the next
step and was waiting for the device, and the device ran nothing.  The loop
cannot cause that.  With this profiler it happens every few steps (its device
buffer drains; PERF.md section 5), and the same loop untraced completes a step
in the program's device time.  Such stalls are taken out of the window, listed
apart, and never counted as the system's idle time.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Any, Dict, List, Sequence, Tuple

from benchmarks.trace.xplane import ANNOTATION_PREFIX, Row, is_device_plane

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SYNC = ANNOTATION_PREFIX + "sync"
QUEUED_STALL_SYNC_SHARE = 0.9     # of the gap, under bench/sync
REMOVED = "removed:queued_step_not_started"


class NoDevicePlane(RuntimeError):
    """The trace holds no device operation: nothing to read, no numbers."""


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def _clip(start: int, dur: int, lo: int, hi: int):
    a, b = max(start, lo), min(start + dur, hi)
    return (a, b) if b > a else None


def reduce_rows(rows: Sequence[Row]) -> Dict[str, Any]:
    notes = [r for r in rows if r[2].startswith(ANNOTATION_PREFIX)]
    if not notes:
        raise ValueError("the trace holds no bench/* annotation: no window")
    lo = min(r[3] for r in notes)
    hi = max(r[3] + r[4] for r in notes)
    planes = sorted({r[0] for r in rows if is_device_plane(r[0])})
    busy_ns = []
    in_program_idle_ns = 0
    ops: Dict[str, int] = defaultdict(int)
    programs: Dict[str, List[int]] = defaultdict(lambda: [0, 0])
    gaps: List[Tuple[int, int]] = []
    for plane in planes:
        intervals = []
        running = []
        for _, line, name, start, dur in (r for r in rows if r[0] == plane):
            clipped = _clip(start, dur, lo, hi)
            if clipped is None:
                continue
            if line == OPS_LINE:
                intervals.append(clipped)
                ops[name] += clipped[1] - clipped[0]
            elif line == MODULES_LINE:
                running.append(clipped)
                if lo <= start and start + dur <= hi:
                    programs[name][0] += 1
                    programs[name][1] += dur
        if not intervals:
            continue
        merged = union(intervals)
        busy_ns.append(sum(b - a for a, b in merged))
        if plane == planes[0]:
            # time a program held the device while none of its ops ran
            both = union(list(merged) + union(running))
            in_program_idle_ns = (sum(b - a for a, b in both)
                                  - sum(b - a for a, b in merged))
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]
    if not busy_ns or sum(busy_ns) == 0:
        raise NoDevicePlane(
            "no operation ran on a device plane inside the traced window "
            f"(planes seen: {sorted({r[0] for r in rows})})"
        )
    step_ns = 0
    if programs:
        count, total = max(programs.values(), key=lambda ct: ct[1])
        step_ns = total // max(1, count)
    by_note: Dict[str, int] = defaultdict(int)
    stalls: List[int] = []
    kept_gaps: List[int] = []
    for a, b in gaps:
        cover: Dict[str, int] = defaultdict(int)
        for _, _, name, start, dur in notes:
            c = _clip(start, dur, a, b)
            if c is not None:
                cover[name] += c[1] - c[0]
        if step_ns and b - a > step_ns and cover.get(SYNC, 0) >= QUEUED_STALL_SYNC_SHARE * (b - a):
            stalls.append(b - a)
            continue
        kept_gaps.append(b - a)
        by_note[max(cover, key=cover.get) if cover else "bench/(outside)"] += b - a
    window_s = (hi - lo - sum(stalls)) / 1e9
    busy_s = sum(busy_ns) / len(busy_ns) / 1e9
    idle_gaps = [[k, v / 1e9] for k, v in sorted(by_note.items(), key=lambda kv: -kv[1])]
    if stalls:
        idle_gaps.append([REMOVED, sum(stalls) / 1e9])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "in_program_idle_s": in_program_idle_ns / 1e9,
        "device_planes": len(busy_ns),
        "programs": {k: {"count": c, "seconds": t / 1e9} for k, (c, t) in programs.items()},
        "device_ops": [[k, v / 1e9 / len(busy_ns)] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])],
        "idle_gaps": idle_gaps,
        "longest_gap_s": max(kept_gaps, default=0) / 1e9,
        "queued_stalls": {"count": len(stalls), "seconds": sum(stalls) / 1e9,
                          "longest_s": max(stalls, default=0) / 1e9},
        "slice_s": (hi - lo) / 1e9,
    }


def step_program(summary: Dict[str, Any]) -> Tuple[str, Dict[str, float]]:
    """The program that took most device time in the window: the step."""
    if not summary["programs"]:
        raise NoDevicePlane("no program event on a device plane in the window")
    name = max(summary["programs"], key=lambda k: summary["programs"][k]["seconds"])
    return name, summary["programs"][name]

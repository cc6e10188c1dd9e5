"""The start-up log: where a job's (re)start goes, measured from inside.

A job pays its start at every launch and after every preemption: reach the
chip, build the model and the state, open the feeds, trace the step, lower
it, compile it or fetch it from the persistent cache. Two instruments, both
always on (they work only while something is set up, traced or compiled; a
steady loop fires neither):

* **Set-up phases.** `phase(name, **args)` is `trace.span("setup/" + name)`
  (so a running profile and the Chrome-trace ring get `rt1/setup/<name>`
  like every other span) and an entry of a small process-wide list kept
  whether or not the ring is on: name, start, end, the phase open on that
  thread when it began (its parent), the thread. A phase's self time is its
  duration less what its children cover.
* **The compile log.** `install()` (called from
  `parallel.distributed.describe_devices`, a process's first touch of the
  chip, before anything is traced) registers listeners on `jax.monitoring`.
  JAX hands each trace, each lowering (jaxpr -> MLIR) and each backend
  compile its start, its end and the function's name, children before
  their parents, and says when the persistent cache answered. Kept, bounded
  (a dict by function name, not a list of events):

  - for each **outermost** traced function (its trace ends while no other
    trace is open on its thread): traces, trace seconds, traces nested
    inside it, lowering seconds, backend seconds (the compile, or the fetch
    where the cache answered), hits, fetch seconds, entries written, and
    the wall time of the last of each on the ring's clock, where a ring
    dump shows them as `compile/trace`, `compile/lower`, `compile/backend`;
  - for each **inner** function name its count and *self* trace seconds,
    the top `TOP_INNER` by self time kept per outermost function;
  - process totals: `traces`, `lowerings`, `compiles` (backend events:
    compiled or fetched), `cache_hits`, `cache_writes`, `seconds` (the
    events no other event encloses: the wall time tracing, lowering,
    compiling and fetching took) and **`recompiles`**: a backend compile or
    fetch of a function name that has its executable already, after set-up
    (no phase open on any thread) or of a function with a role; each is
    logged with the name, the seconds and the step or phase open: at
    WARNING where it took `WARN_SECONDS` or more (a step that compiled again
    in mid-run), at INFO below (jax's own small programs at a new shape).

  `mark_role(role, name)` says which function names are the train step's
  and the eval step's; when a role's function first has its executable the
  running totals are stamped onto the role ("up to and including the
  step's own": programs compiled later in the process are not in it).

`snapshot()` returns all of it as plain numbers; `block()` renders the
lines the trainer logs when its loop starts. Readers: the goodput ledger's
`compile` bucket (`compile_seconds`), `StepTimeline`'s `compile_ms`, the
writer's `compile/*` scalars, `goodput_summary.json`'s `startup` key,
`chip_smoke.py`, and the benchmark's `setup_*` metrics.

Stdlib at import; jax is looked up, never imported (tests/test_obs_imports.py).
"""

from __future__ import annotations

import functools
import logging
import sys
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from rt1_tpu.obs import trace

logger = logging.getLogger(__name__)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"
#: jax fires "cache_misses" where it writes a compiled program into the
#: cache, not where a lookup fails: a compile under the cache's time floor
#: fires nothing. So it is kept as `cache_writes`; programs compiled and not
#: fetched are `compiles - cache_hits`.
CACHE_WRITE = "/jax/compilation_cache/cache_misses"
CACHE_FETCH = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED = "/jax/compilation_cache/compile_time_saved_sec"

_KINDS = {TRACE: "trace", LOWER: "lower", BACKEND: "backend"}
TOP_INNER = 8
BLOCK_FUNCTIONS = 6
MAX_PHASES = 256
MAX_FUNCTIONS = 512
MAX_INNER_NAMES = 2048   # distinct inner names held while one outermost trace is open
OTHER = "<other>"
#: A recompile that took this long is logged at WARNING, a shorter one at
#: INFO: an op of jax's own at a new shape fetches in milliseconds and
#: compiles in under a tenth of a second, a step never does.
WARN_SECONDS = 0.5

_TOTALS = ("traces", "lowerings", "compiles", "cache_hits", "cache_writes",
           "recompiles", "seconds", "trace_s", "lower_s", "backend_s",
           "fetch_s", "saved_s")


def _function(fun_name: Any) -> str:
    """`jit(train_step)` and `pmap(f)`, as lowering and compile events name
    a function, to the `train_step` its trace event gave."""
    name = str(fun_name)
    if name.endswith(")") and "(" in name:
        name = name[name.index("(") + 1:-1]
    return name


class _Frame:
    """One open trace, lowering or backend compile of a thread."""

    __slots__ = ("kind", "children_s", "nested", "hit", "fetch_s", "saved_s", "wrote")

    def __init__(self, kind: str):
        self.kind = kind
        self.children_s = 0.0
        self.nested = 0
        self.hit = False
        self.fetch_s = 0.0
        self.saved_s = 0.0
        self.wrote = False


class _PerThread(threading.local):
    def __init__(self):
        self.frames: List[_Frame] = []
        self.traces_open = 0
        self.inner: Dict[str, List[float]] = {}
        self.phases: List[int] = []


class _Phase:
    __slots__ = ("_log", "_name", "_span", "_index")

    def __init__(self, log: "StartupLog", name: str, args: Dict[str, Any]):
        self._log = log
        self._name = name
        self._span = trace.span("setup/" + name, **args)
        self._index = -1

    def __enter__(self):
        self._span.__enter__()
        self._index = self._log._open_phase(self._name)
        return self

    def __exit__(self, *exc):
        self._log._close_phase(self._index)
        self._span.__exit__(*exc)
        return False


class StartupLog:
    """Phases and compile events of one process. `clock` gives seconds (the
    ring's clock by default); tests hand in their own and feed the listener
    methods directly."""

    def __init__(self, clock: Optional[Callable[[], float]] = None):
        self._clock = clock or (lambda: trace.now_us() / 1e6)
        self._lock = threading.Lock()
        self._local = _PerThread()
        self._installed = False
        self._wall_to_ring = 0.0
        self.current_step: Optional[int] = None    # StepTimeline sets it
        self._phases: List[Dict[str, Any]] = []
        self._phases_open = 0
        self._phases_dropped = 0
        self._functions: Dict[str, Dict[str, Any]] = {}
        self._totals: Dict[str, float] = {k: 0 for k in _TOTALS}
        self._roles: Dict[str, List[str]] = {}
        self._stamps: Dict[str, Dict[str, float]] = {}

    # ------------------------------------------------------------- phases

    def phase(self, name: str, **args) -> _Phase:
        return _Phase(self, name, args)

    def _open_phase(self, name: str) -> int:
        stack = self._local.phases
        with self._lock:
            self._phases_open += 1
            if len(self._phases) >= MAX_PHASES:
                self._phases_dropped += 1
                index = -1
            else:
                index = len(self._phases)
                self._phases.append({
                    "name": name, "start_s": self._clock(), "end_s": None,
                    "parent": stack[-1] if stack else None,
                    "thread": threading.current_thread().name,
                })
        stack.append(index)
        return index

    def _close_phase(self, index: int) -> None:
        stack = self._local.phases
        if stack:
            stack.pop()
        with self._lock:
            self._phases_open -= 1
            if index >= 0:
                self._phases[index]["end_s"] = self._clock()

    def _open_now(self) -> str:
        """What a recompile's warning names: the step, else the phase open
        on this thread."""
        if self.current_step is not None:
            return f"step {self.current_step}"
        stack = self._local.phases
        if stack and stack[-1] >= 0:
            return "phase " + self._phases[stack[-1]]["name"]
        return "no step or phase open"

    # ----------------------------------------------------- jax's listeners

    def install(self) -> bool:
        """Register the listeners, once. False where the process has not
        imported jax (nothing can be traced there)."""
        jax = sys.modules.get("jax")
        monitoring = getattr(jax, "monitoring", None)
        if monitoring is None:
            return False
        with self._lock:
            if self._installed:
                return True
            self._installed = True
            # jax stamps its events with time.time(); the ring counts from
            # the process's perf_counter epoch
            self._wall_to_ring = trace.now_us() / 1e6 - time.time()
        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_time_span_listener(self._on_span)
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)
        return True

    def uninstall(self) -> None:
        monitoring = getattr(sys.modules.get("jax"), "monitoring", None)
        with self._lock:
            if not self._installed or monitoring is None:
                return
            self._installed = False
        monitoring.unregister_scalar_listener(self._on_start)
        monitoring.unregister_event_time_span_listener(self._on_span)
        monitoring.unregister_event_listener(self._on_event)
        monitoring.unregister_event_duration_listener(self._on_duration)

    def _on_start(self, event: str, _value: float, **_kw) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        local = self._local
        local.frames.append(_Frame(kind))
        if kind == "trace":
            local.traces_open += 1

    def _on_event(self, event: str, **_kw) -> None:
        frames = self._local.frames
        if not frames:
            return
        if event == CACHE_HIT:
            frames[-1].hit = True
        elif event == CACHE_WRITE:
            frames[-1].wrote = True

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        frames = self._local.frames
        if not frames:
            return
        if event == CACHE_FETCH:
            frames[-1].fetch_s += seconds
        elif event == CACHE_SAVED:
            frames[-1].saved_s += seconds

    def _on_span(self, event: str, start: float, end: float, **kw) -> None:
        kind = _KINDS.get(event)
        if kind is None:
            return
        local = self._local
        frames = local.frames
        # a frame whose start was not seen (listeners installed inside it)
        frame = frames.pop() if frames and frames[-1].kind == kind else _Frame(kind)
        seconds = max(end - start, 0.0)
        name = _function(kw.get("fun_name", "?"))
        parent = frames[-1] if frames else None
        if parent is not None:
            parent.children_s += seconds
        if kind == "trace":
            local.traces_open = max(local.traces_open - 1, 0)
            if local.traces_open:
                if parent is not None:
                    parent.nested += 1 + frame.nested
                inner = local.inner
                if name not in inner and len(inner) >= MAX_INNER_NAMES:
                    name = OTHER
                tally = inner.setdefault(name, [0, 0.0])
                tally[0] += 1
                tally[1] += max(seconds - frame.children_s, 0.0)
                return
        # on the ring's clock, where a dump shows it beside the phases
        when = [start + self._wall_to_ring, end + self._wall_to_ring]
        if kind == "trace":
            inner, local.inner = local.inner, {}
            self._outermost_trace(name, seconds, frame, inner, parent is None, when)
        else:
            self._lower_or_backend(kind, name, seconds, frame, parent is None, when)
        trace.complete("compile/" + kind, when[0] * 1e6, seconds * 1e6, fun=name)

    def _entry(self, name: str) -> Dict[str, Any]:
        """The function's entry; the lock is held."""
        entry = self._functions.get(name)
        if entry is None:
            if len(self._functions) >= MAX_FUNCTIONS:
                name = OTHER
                entry = self._functions.get(name)
            if entry is None:
                entry = self._functions[name] = {
                    "traces": 0, "trace_s": 0.0, "trace_self_s": 0.0, "inner_traces": 0,
                    "lowerings": 0, "lower_s": 0.0, "compiles": 0, "backend_s": 0.0,
                    "cache_hits": 0, "fetch_s": 0.0, "saved_s": 0.0, "cache_writes": 0,
                    "recompiles": 0, "inner": {}, "last": {},
                }
        return entry

    def _outermost_trace(self, name, seconds, frame, inner, top: bool, when) -> None:
        with self._lock:
            entry = self._entry(name)
            entry["traces"] += 1
            entry["trace_s"] += seconds
            entry["trace_self_s"] += max(seconds - frame.children_s, 0.0)
            entry["inner_traces"] += frame.nested
            held = entry["inner"]
            for inner_name, (count, self_s) in inner.items():
                tally = held.setdefault(inner_name, [0, 0.0])
                tally[0] += count
                tally[1] += self_s
            if len(held) > TOP_INNER:
                keep = sorted(held, key=lambda k: held[k][1], reverse=True)[:TOP_INNER]
                entry["inner"] = {k: held[k] for k in keep}
            entry["last"]["trace"] = when
            totals = self._totals
            totals["traces"] += 1 + frame.nested
            totals["trace_s"] += seconds
            if top:
                totals["seconds"] += seconds

    def _lower_or_backend(self, kind, name, seconds, frame, top: bool, when) -> None:
        recompiled = None
        with self._lock:
            entry = self._entry(name)
            totals = self._totals
            entry["last"][kind] = when
            if top:
                totals["seconds"] += seconds
            if kind == "lower":
                entry["lowerings"] += 1
                entry["lower_s"] += seconds
                totals["lowerings"] += 1
                totals["lower_s"] += seconds
                return
            role = next((r for r, names in self._roles.items() if name in names), None)
            known = entry["compiles"] and entry is not self._functions.get(OTHER)
            if known and (role is not None or not self._phases_open):
                entry["recompiles"] += 1
                totals["recompiles"] += 1
                recompiled = self._open_now()
            entry["compiles"] += 1
            entry["backend_s"] += seconds
            totals["compiles"] += 1
            totals["backend_s"] += seconds
            for key, value in (("cache_hits", int(frame.hit)), ("fetch_s", frame.fetch_s),
                               ("saved_s", frame.saved_s), ("cache_writes", int(frame.wrote))):
                entry[key] += value
                totals[key] += value
            if role is not None and role not in self._stamps:
                self._stamps[role] = dict(totals)
        if recompiled is not None:
            logger.log(
                logging.WARNING if seconds >= WARN_SECONDS else logging.INFO,
                "recompiled %s: %s took %.3f s during %s (a function that had its "
                "executable: a new shape, dtype or static argument reached it)",
                name, "the fetch from the persistent cache" if frame.hit else "the compile",
                seconds, recompiled)

    # --------------------------------------------------------------- roles

    def mark_role(self, role: str, name: str) -> None:
        with self._lock:
            names = self._roles.setdefault(role, [])
            if name not in names:
                names.append(name)

    # ------------------------------------------------------------- reading

    def compile_seconds(self) -> float:
        """Wall seconds tracing, lowering, compiling and fetching have taken
        in this process (the goodput ledger's `compile` bucket)."""
        return float(self._totals["seconds"])

    def scalars(self, prefix: str = "compile/") -> Dict[str, float]:
        totals = self._totals
        return {f"{prefix}seconds_total": float(totals["seconds"]),
                f"{prefix}compiles_total": float(totals["compiles"]),
                f"{prefix}recompiles_total": float(totals["recompiles"])}

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            now = self._clock()
            records = [dict(p) for p in self._phases]
            functions = {
                name: dict(e, inner={k: {"count": c, "self_s": s} for k, (c, s) in e["inner"].items()},
                           last=dict(e["last"]))
                for name, e in self._functions.items()}
            totals = dict(self._totals)
            roles = {r: list(names) for r, names in self._roles.items()}
            stamps = {r: dict(s) for r, s in self._stamps.items()}
            dropped = self._phases_dropped
        covered = [0.0] * len(records)
        for p in records:
            p["seconds"] = (now if p["end_s"] is None else p["end_s"]) - p["start_s"]
            if p["parent"] is not None and p["parent"] >= 0:
                covered[p["parent"]] += p["seconds"]
        phase_s: Dict[str, Dict[str, float]] = {}
        for p, children in zip(records, covered):
            p["self_s"] = max(p["seconds"] - children, 0.0)
            if p["parent"] is not None:
                p["parent"] = records[p["parent"]]["name"] if p["parent"] >= 0 else OTHER
            by_name = phase_s.setdefault(p["name"], {"count": 0, "seconds": 0.0, "self_s": 0.0})
            by_name["count"] += 1
            by_name["seconds"] += p["seconds"]
            by_name["self_s"] += p["self_s"]
        role_out = {}
        for role, names in roles.items():
            held = [functions[n] for n in names if n in functions]
            if not held:
                continue
            role_out[role] = {
                "functions": [n for n in names if n in functions],
                **{k: sum(f[k] for f in held) for k in (
                    "traces", "trace_s", "inner_traces", "lowerings", "lower_s", "compiles",
                    "backend_s", "cache_hits", "fetch_s", "cache_writes", "recompiles")},
            }
            if role in stamps:
                role_out[role]["totals_at_executable"] = stamps[role]
        return {"phases": records, "phases_dropped": dropped, "phase_s": phase_s,
                "functions": functions, "roles": role_out, "totals": totals}


def block(snapshot: Dict[str, Any]) -> List[str]:
    """The start-up block as lines: phases with self times, a line a role,
    the `BLOCK_FUNCTIONS` costliest functions with their inner names, the
    totals."""
    lines = ["start-up: phases (seconds, self seconds)"]
    for name, p in snapshot["phase_s"].items():
        lines.append(f"  setup/{name}: {p['seconds']:.3f} s, self {p['self_s']:.3f} s"
                     f"{' x' + str(p['count']) if p['count'] > 1 else ''}")
    for role, r in snapshot["roles"].items():
        fetched = r["cache_hits"] and r["cache_hits"] == r["compiles"]
        lines.append(
            f"  {role} ({', '.join(r['functions'])}): trace {r['trace_s']:.3f} s with "
            f"{r['inner_traces']} inner traces, lower {r['lower_s']:.3f} s, "
            f"{'fetch' if fetched else 'compile'} {r['backend_s']:.3f} s")
    functions = snapshot["functions"]

    def cost(name):
        f = functions[name]
        return f["trace_s"] + f["lower_s"] + f["backend_s"]

    for name in sorted(functions, key=cost, reverse=True)[:BLOCK_FUNCTIONS]:
        f = functions[name]
        inner = sorted(f["inner"].items(), key=lambda kv: kv[1]["self_s"], reverse=True)
        lines.append(
            f"  {name}: {f['traces']} traces {f['trace_s']:.3f} s (self {f['trace_self_s']:.3f}, "
            f"{f['inner_traces']} inner), lower {f['lower_s']:.3f} s, backend {f['backend_s']:.3f} s "
            f"({f['cache_hits']} of {f['compiles']} fetched)"
            + ("; inner by self time: " + ", ".join(
                f"{k} x{v['count']} {v['self_s']:.3f} s" for k, v in inner) if inner else ""))
    t = snapshot["totals"]
    lines.append(
        f"  totals: {t['traces']} traces, {t['lowerings']} lowerings, {t['compiles']} compiles "
        f"({t['cache_hits']} fetched, {t['cache_writes']} written), {t['recompiles']} recompiles, "
        f"{t['seconds']:.3f} s in all")
    return lines


# ---------------------------------------------------------------- module API
#
# One process-wide log, as trace.py keeps one recorder: call sites stay
# dependency-free.

_LOG = StartupLog()

phase = _LOG.phase
install = _LOG.install
uninstall = _LOG.uninstall
mark_role = _LOG.mark_role
compile_seconds = _LOG.compile_seconds
scalars = _LOG.scalars
snapshot = _LOG.snapshot


def phased(name: str):
    """`phase(name)` around every call of the function it decorates."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def set_step(step: Optional[int]) -> None:
    """The step the loop has open (`StepTimeline`), for a recompile's warning."""
    _LOG.current_step = step

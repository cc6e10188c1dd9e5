"""What the harness asks of the machine: the device, its peaks, its memory,
the compile cache and the count of compilations."""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List


class NoAccelerator(RuntimeError):
    pass


def describe(chips_needed: int) -> Dict[str, Any]:
    """platform / kind / count as JAX reports them; refuses a CPU and a
    machine with fewer chips than the cell asks for."""
    import jax

    from rt1_tpu.parallel import describe_devices

    d = describe_devices()
    if d["platform"] == "cpu":
        raise NoAccelerator(
            "JAX found no accelerator (platform cpu): the benchmark measures "
            "only on the chip"
        )
    if d["device_count"] < chips_needed:
        raise NoAccelerator(
            f"the cell asks for {chips_needed} chip(s), JAX sees {d['device_count']}"
        )
    return {"platform": d["platform"], "kind": d["device_kind"],
            "count": d["device_count"]}


def peaks(root: str, kind: str) -> Dict[str, float]:
    with open(os.path.join(root, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table or kind.startswith("_"):
        raise KeyError(
            f"device kind {kind!r} is not in benchmarks/peaks.json: an unknown "
            "device is an error, not a default"
        )
    return table[kind]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent cache at ``JAX_COMPILATION_CACHE_DIR`` if set, else at
    a fixed path inside the checkout; every program is kept, however short
    its compile, so that a second run finds all of them."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(root, ".jax_cache")
        )
    # The flagship's step executable is 277 MB and its reference's as much:
    # under a size cap from the environment (the chip tool's machines set
    # JAX_COMPILATION_CACHE_MAX_SIZE to 192 MiB) neither is ever kept, and
    # every run compiles for six minutes.
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return str(jax.config.jax_compilation_cache_dir)


class CacheLog(logging.Handler):
    """What JAX says about its persistent cache, gathered for one line of the
    run's own log: a set-up that grew names the programs it compiled."""

    def __init__(self):
        super().__init__(logging.DEBUG)
        self.hits: List[str] = []
        self.written: List[str] = []
        self.other: List[str] = []

    def emit(self, record: logging.LogRecord) -> None:
        msg = " ".join(record.getMessage().split())
        if "cache hit for" in msg:
            self.hits.append(msg.split("'")[1])
        elif msg.startswith("Writing "):
            self.written.append(msg.split()[1])
        elif "Not writing" in msg or "rror" in msg:
            self.other.append(msg[:200])

    def summary(self) -> str:
        return (f"persistent cache: {len(self.hits)} hits; compiled and wrote "
                f"{len(self.written)}: {sorted(set(self.written))[:12]}; notes {self.other[:6]}")


def log_cache_traffic() -> CacheLog:
    handler = CacheLog()
    for name in ("jax._src.compiler", "jax._src.compilation_cache"):
        logger = logging.getLogger(name)
        logger.setLevel(logging.DEBUG)
        logger.addHandler(handler)
        logger.propagate = False
    return handler


class CompileCounter:
    """Counts traces and backend compiles through ``jax.monitoring``."""

    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.traces = 0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == self.TRACE:
            self.traces += 1
        elif event == self.COMPILE:
            self.compiles += 1

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> Dict[str, int]:
        return {"traces": self.traces, "compiles": self.compiles,
                "cache_hits": self.cache_hits}


def memory(devices: List[Any]) -> Dict[str, int]:
    """Peak of the fullest chip.  On this backend ``peak_bytes_in_use`` does
    not see a program's scratch and ``peak_bytes_reserved`` does (PERF.md
    section 4), so the larger of the two is the peak."""
    out = {"peak_bytes_in_use": 0, "peak_bytes_reserved": 0, "bytes_limit": 0}
    for d in devices:
        s = d.memory_stats() or {}
        for k in out:
            out[k] = max(out[k], int(s.get(k, 0)))
    out["memory_peak_bytes"] = max(out["peak_bytes_in_use"], out["peak_bytes_reserved"])
    return out

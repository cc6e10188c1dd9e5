"""One run of one cell:

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name BENCHMARK.json gives
it: benchmarks/configs/<config>.json, benchmarks/traffic/<traffic>.json,
benchmarks/metrics/<metric>.py; the window loop is benchmarks/drivers/<kind>.py
for the mix's ``kind``.  The last line of standard output is the result.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()
T_PROCESS_START_WALL = time.time()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Any, Callable, Dict, List, Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _since_exec() -> float:
    """Seconds between the process's start and this module's first line
    (interpreter start-up, site imports), from /proc where it is there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        started = uptime - start_ticks / os.sysconf("SC_CLK_TCK")
        return max(0.0, started - (time.time() - T_PROCESS_START_WALL))
    except (OSError, ValueError, IndexError):
        return 0.0


CLOCK_OFFSET = _since_exec()


@dataclasses.dataclass
class Context:
    root: str
    cell: Dict[str, Any]
    config_file: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    device: Dict[str, Any]
    t_process_start: float
    clock_offset: float
    log: Callable[[str], None]
    make_tracer: Callable[[], Any]


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_manifest(root: str) -> Dict[str, Any]:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(manifest: Dict[str, Any], name: str) -> Dict[str, Any]:
    for cell in manifest["workloads"]:
        if cell["name"] == name:
            return cell
    raise SystemExit(f"no workload {name!r} in BENCHMARK.json")


def config_path(manifest: Dict[str, Any], name: str) -> str:
    for c in manifest["configs"]:
        if c["name"] == name:
            return c["file"]
    raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")


def metric_reader(root: str, name: str):
    path = os.path.join(root, "benchmarks", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics._" + name.replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(manifest: Dict[str, Any], group: str, cell: str) -> List[Dict[str, Any]]:
    return [m for m in manifest[group] if cell in m.get("workloads", [cell])]


def main(argv: Optional[List[str]] = None, root: str = ROOT) -> int:
    """``root`` is for tests; the command line has none."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if root not in sys.path:
        sys.path.insert(0, root)
    manifest = load_manifest(root)
    cell = find_cell(manifest, args.workload)
    from benchmarks import devices, program, traffic

    config_file = program.load_config_file(
        os.path.join(root, config_path(manifest, cell["config"]))
    )
    mix = traffic.load_traffic_file(
        os.path.join(root, "benchmarks", "traffic", cell["traffic"] + ".json")
    )
    try:
        device = devices.describe(int(cell["chips"]))
    except (devices.NoAccelerator, RuntimeError) as exc:
        log(str(exc))
        return 1
    cache_dir = devices.enable_compile_cache(root)
    cache_log = devices.log_cache_traffic()
    log(f"cell {cell['name']} seed {args.seed} seconds {args.seconds} trace {args.trace}; "
        f"device {device}; compile cache {cache_dir}")

    def make_tracer():
        from benchmarks.trace import window as trace_window

        return trace_window.Tracer(
            os.path.join(traffic.cache_root(root), "trace", cell["name"]), log,
            seconds=args.seconds,
        )

    ctx = Context(
        root=root, cell=cell, config_file=config_file, traffic=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), device=device,
        t_process_start=T_PROCESS_START, clock_offset=CLOCK_OFFSET, log=log,
        make_tracer=make_tracer,
    )
    from benchmarks.trace.reduce import NoDevicePlane

    driver = importlib.import_module(f"benchmarks.drivers.{mix['kind']}")
    try:
        out = driver.run(ctx)
    except NoDevicePlane as exc:
        log(f"no device numbers can be read: {exc}")
        return 1

    log(cache_log.summary())
    for fault in out["harness_faults"]:
        log(f"FAULT: {fault}")
    correct = all(c["ok"] for c in out["checks"]) and not out["harness_faults"]
    device_out = dict(device, memory_peak_bytes=out["memory"]["memory_peak_bytes"])
    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if args.trace:
        summary = out["trace"]
        device_out["busy_s"] = summary["busy_s"]
        device_out["window_s"] = summary["window_s"]
        breakdown = {"device_ops": summary["device_ops"][:10],
                     "idle_gaps": summary["idle_gaps"][:10]}
        reading = {
            "trace": summary, "config_file": config_file, "batch": out["batch"],
            "peaks": devices.peaks(root, device["kind"]), "chips": int(cell["chips"]),
            "log": log,
        }
        for m in cell_metrics(manifest, "per_layer", cell["name"]):
            value = metric_reader(root, m["name"]).read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell_metrics(manifest, "end_to_end", cell["name"]):
            metrics[m["name"]] = {"value": out["end_to_end"][m["name"]], "unit": m["unit"]}

    compared = {c["name"]: {"value": c["value"], "limit": c["limit"]} for c in out["checks"]}
    for c in out["checks"]:
        log(f"compared {c['name']}: {c['value']:.6g} (limit {c['limit']:.6g})"
            f"{' at ' + c['at'] if c['at'] else ''}{'' if c['ok'] else '  <-- OVER'}")
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": out["attempted"], "failed": out["failed"],
        "metrics": metrics, "device": device_out,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Kernels: the grouped expert products against their roof.  The least time
the chip could take for the rows that were computed in the traced steps (the
program's ``moe/assignments_held`` counter, mean over those steps, summed over
the routed layers): max(FLOPs / peak, bytes / bandwidth) by
benchmarks/flops_lm.py::experts_cost, over the device time a step under
``moe/experts``.  That time holds the SiLU and the stacks' cast to bfloat16
too, so the share is of the scope, not of the bare kernel."""

from benchmarks import flops_lm


def read(r):
    trace = r["trace"]
    seconds = ((trace.get("program") or {}).get("scope_s") or {}).get("moe_experts")
    counters = trace.get("counters") or {}
    rows = counters.get("moe/assignments_held")
    if not seconds or not rows:
        return None
    lm = flops_lm.lm_sizes(r["config_file"]["overrides"])
    flops, nbytes = flops_lm.experts_cost(rows, counters["routed_layers"], lm)
    by_flops = flops / r["peaks"]["flops_per_s"]
    by_bytes = nbytes / r["peaks"]["hbm_bytes_per_s"]
    r["log"](f"expert products: {rows:.0f} rows a step over {counters['routed_layers']} layers, "
             f"{by_flops * 1e3:.3f} ms by FLOPs, {by_bytes * 1e3:.3f} ms by bytes, "
             f"{seconds * 1e3:.3f} ms under moe/experts")
    return max(by_flops, by_bytes) / seconds * 100.0

"""Kernels: today the jitted step is the one kernel.  The least time the chip
could take for one step, max(FLOPs / peak, bytes / bandwidth) with the
configuration's frozen counts, over the step program's device time."""

from benchmarks.trace import reduce


def read(r):
    cf, peaks = r["config_file"], r["peaks"]
    if not cf.get("flops_per_sample") or not cf.get("min_bytes_per_step"):
        return None
    _, program = reduce.step_program(r["trace"])
    by_flops = cf["flops_per_sample"] * r["batch"] / r["chips"] / peaks["flops_per_s"]
    by_bytes = cf["min_bytes_per_step"] / peaks["hbm_bytes_per_s"]
    r["log"](f"roofline of the step: {by_flops * 1e3:.3f} ms by FLOPs, "
             f"{by_bytes * 1e3:.3f} ms by bytes: bound by "
             f"{'FLOPs' if by_flops >= by_bytes else 'bytes'}")
    return max(by_flops, by_bytes) / (program["seconds"] / program["count"]) * 100.0

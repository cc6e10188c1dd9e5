"""Kernels: the attention kernel against its roof.  Causal attention forward
and backward at half the square (benchmarks/flops_lm.py::attention_cost),
max(FLOPs / peak, bytes / bandwidth), over the device time a step of the ops
under ``attention/kernel``.  None where attention is not a kernel (the
blockwise lax form has no such scope)."""

from benchmarks import flops_lm


def read(r):
    trace = r["trace"]
    seconds = ((trace.get("program") or {}).get("scope_s") or {}).get("attention_kernel")
    counters = trace.get("counters") or {}
    if not seconds or not counters.get("attention_layers"):
        return None
    lm = flops_lm.lm_sizes(r["config_file"]["overrides"])
    flops, nbytes = flops_lm.attention_cost(
        r["batch"], counters["seq_len"], counters["attention_layers"], lm)
    by_flops = flops / r["peaks"]["flops_per_s"]
    by_bytes = nbytes / r["peaks"]["hbm_bytes_per_s"]
    r["log"](f"attention kernel: {by_flops * 1e3:.3f} ms by FLOPs, {by_bytes * 1e3:.3f} ms by "
             f"bytes, {seconds * 1e3:.3f} ms under attention/kernel")
    return max(by_flops, by_bytes) / seconds * 100.0

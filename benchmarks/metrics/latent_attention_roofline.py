"""Kernels: the latent layers' attention kernel against its roof.  QK^T over
the keys' width (nope + rope) and PV over the values', forward and backward, on
the causal half of the square, over the heads held, every block of the step
(benchmarks/flops_lm_mla.py::attention_cost), max(FLOPs / peak, bytes /
bandwidth), over the device time a step of the ops under ``attention/kernel``.
None where attention is not a kernel (the blockwise lax form has no such
scope) or the configuration has no latent layer."""

from benchmarks import flops_lm_mla


def read(r):
    trace = r["trace"]
    seconds = ((trace.get("program") or {}).get("scope_s") or {}).get("attention_kernel")
    counters = trace.get("counters") or {}
    lm = flops_lm_mla.lm_sizes(r["config_file"]["overrides"])
    if not seconds or not counters.get("seq_len") or not lm.get("kv_lora_rank"):
        return None
    flops, nbytes = flops_lm_mla.attention_cost(r["batch"], counters["seq_len"], lm)
    by_flops = flops / r["peaks"]["flops_per_s"]
    by_bytes = nbytes / r["peaks"]["hbm_bytes_per_s"]
    r["log"](f"latent attention kernels: {by_flops * 1e3:.3f} ms by FLOPs, {by_bytes * 1e3:.3f} "
             f"ms by bytes, {seconds * 1e3:.3f} ms under attention/kernel")
    return max(by_flops, by_bytes) / seconds * 100.0

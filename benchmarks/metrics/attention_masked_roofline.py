"""Kernels: the attention kernels against the roof of the work their masks
keep.  QK^T and PV forward and backward over the (query, key) pairs each layer
of the configuration's ``layer_types`` keeps (half the square for a full causal
layer, ``s w - w (w - 1) / 2`` for a sliding one:
benchmarks/flops_lm_mixed.py::attention_cost), max(FLOPs / peak, bytes /
bandwidth), over the device time a step of the ops under ``attention/kernel``,
both kinds of layer.  None where attention is not a kernel (the blockwise lax
form has no such scope) or the configuration has no window."""

from benchmarks import flops_lm_mixed


def read(r):
    trace = r["trace"]
    seconds = ((trace.get("program") or {}).get("scope_s") or {}).get("attention_kernel")
    counters = trace.get("counters") or {}
    lm = flops_lm_mixed.lm_sizes(r["config_file"]["overrides"])
    if not seconds or not counters.get("seq_len") or not lm.get("sliding_window"):
        return None
    flops, nbytes = flops_lm_mixed.attention_cost(r["batch"], counters["seq_len"], lm)
    by_flops = flops / r["peaks"]["flops_per_s"]
    by_bytes = nbytes / r["peaks"]["hbm_bytes_per_s"]
    r["log"](f"attention kernels over the pairs their masks keep: {by_flops * 1e3:.3f} ms by "
             f"FLOPs, {by_bytes * 1e3:.3f} ms by bytes, {seconds * 1e3:.3f} ms under "
             f"attention/kernel")
    return max(by_flops, by_bytes) / seconds * 100.0

"""Kernels: the residual streams' maps and mixes against their roof.  Every
sublayer's n streams read once and written once each way at the type the
configuration holds them in, and the phi products
(benchmarks/flops_lm_mla.py::hyper_connection_cost), max(FLOPs / peak, bytes /
bandwidth), over the device time a step of the ops under ``hyper_connection/``
(the configuration's tally ``hyper_connection``).  Bound by bytes: the mixes are
element-wise.  None where the configuration has no such tally, no streams, or
no op ran under the scope."""

from benchmarks import flops_lm_mla


def read(r):
    trace = r["trace"]
    seconds = ((trace.get("program") or {}).get("tally_s") or {}).get("hyper_connection")
    counters = trace.get("counters") or {}
    lm = flops_lm_mla.lm_sizes(r["config_file"]["overrides"])
    if not seconds or not counters.get("seq_len") or int(lm.get("hc_mult", 1)) < 2:
        return None
    flops, nbytes = flops_lm_mla.hyper_connection_cost(r["batch"], counters["seq_len"], lm)
    by_flops = flops / r["peaks"]["flops_per_s"]
    by_bytes = nbytes / r["peaks"]["hbm_bytes_per_s"]
    r["log"](f"residual streams' maps and mixes: {by_flops * 1e3:.3f} ms by FLOPs, "
             f"{by_bytes * 1e3:.3f} ms by bytes, {seconds * 1e3:.3f} ms under hyper_connection/")
    return max(by_flops, by_bytes) / seconds * 100.0

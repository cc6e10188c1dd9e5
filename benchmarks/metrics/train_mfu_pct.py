"""The whole step against the chip's peak: the configuration's frozen
shape-derived forward+backward FLOPs per sample x samples of the untraced
part of the traced run's window / its wall time / chips / peak.  Recomputed work, the
optimizer and the health pack do not count."""


def read(r):
    s = r["trace"]["untraced"]
    flops = r["config_file"].get("flops_per_sample")
    if not flops or not s["seconds"]:
        return None
    rate = flops * s["steps"] * r["batch"] / s["seconds"]
    return rate / (r["chips"] * r["peaks"]["flops_per_s"]) * 100.0

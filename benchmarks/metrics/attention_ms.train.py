"""Model step: device self time a step under the attention layers' scopes
(``attention``: projections, head norms, rotary, layout changes; and
``attention/kernel``: the kernels of both kinds of layer, full and windowed),
forward and backward, from the scope reduction of the traced slice
(benchmarks/trace/program.py with trace/scopes_lm.json).  None where the driver
handed no scope reduction over or the program has no such scope."""

GROUPS = ("attention", "attention_kernel")


def read(r):
    scope_s = (r["trace"].get("program") or {}).get("scope_s") or {}
    total = sum(scope_s.get(g, 0.0) for g in GROUPS)
    if not total:
        return None
    r["log"]("attention, ms a step: " + ", ".join(
        f"{g} {scope_s.get(g, 0.0) * 1e3:.3f}" for g in GROUPS))
    return total * 1e3

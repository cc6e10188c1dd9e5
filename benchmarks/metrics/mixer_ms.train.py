"""Model step: device self time a step under the mixers' scopes
(``shortconv``; ``attention`` with its kernel), forward and backward, from the
scope reduction of the traced slice.  None where there is none."""

GROUPS = ("shortconv", "attention", "attention_kernel")


def read(r):
    scope_s = (r["trace"].get("program") or {}).get("scope_s") or {}
    total = sum(scope_s.get(g, 0.0) for g in GROUPS)
    if not total:
        return None
    r["log"]("mixers, ms a step: " + ", ".join(
        f"{g} {scope_s.get(g, 0.0) * 1e3:.3f}" for g in GROUPS))
    return total * 1e3

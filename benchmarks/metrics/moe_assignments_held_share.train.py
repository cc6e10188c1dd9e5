"""Model step: the share of a step's tokens x top-k assignments, over the
routed layers, that fell to the experts held here and were computed: the
program's ``moe/assignments_held`` counter over tokens x top-k x layers, mean
over the traced steps.  held / routed experts (0.125) x the live share of the
positions (tail padding takes no rows) when the router is balanced."""


def read(r):
    counters = r["trace"].get("counters") or {}
    held = counters.get("moe/assignments_held")
    if not held or not counters.get("assignments_total"):
        return None
    r["log"](f"largest held expert's rows over the mean, over the traced steps: "
             f"{counters.get('moe/load_max_over_mean', float('nan')):.4f}")
    return held / counters["assignments_total"]

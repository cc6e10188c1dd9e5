"""Model step: the share of the routed layers, mean over the traced steps,
whose held rows overflowed the row buffer and took the all-slots path behind
``lax.cond`` (the program's ``moe/fallback_layers`` step metric, which the
configuration's file names under ``counters``, over the routed layers).  Such
a layer costs about twice; 0 is a reading, not a gap.  None where the
configuration does not name the counter."""


def read(r):
    counters = r["trace"].get("counters") or {}
    layers, fallbacks = counters.get("routed_layers"), counters.get("moe/fallback_layers")
    if not layers or fallbacks is None:
        return None
    return fallbacks / layers

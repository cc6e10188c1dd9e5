"""Model step: device self time a step of the ops under ``moe/shared``: the shared
expert's SwiGLU of every routed layer, which every chip of an expert-parallel
group computes alike, both ways.  A tally of the configuration's own
(``scope_tallies`` in its file, group ``shared_expert``), beside the family's
scope groups, which it overlaps.  None where the configuration names no such
tally or no op ran under it (as at a parent commit without the scope)."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get("shared_expert")
    return seconds * 1e3 if seconds else None

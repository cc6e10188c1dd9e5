"""Model step: device self time a step of the ops under ``mtp/``: the whole multi-
token-prediction module, its block's attention, experts and maps and its own
pass through the head and the loss among them, both ways.  A tally of the
configuration's own (``scope_tallies`` in its file, group ``mtp``), beside the
family's scope groups, which it overlaps.  None where the configuration names
no such tally or no op ran under it (as at a parent commit without the scope)."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get("mtp")
    return seconds * 1e3 if seconds else None

"""Model step: device self time a step of the ops under ``hyper_connection/``:
every sublayer's maps (the norm over the n d-wide streams, the phi products,
the Sinkhorn rounds) and both mixes (the sublayer's input, the streams written
back), both ways.  A tally of the configuration's own (``scope_tallies`` in its
file, group ``hyper_connection``), beside the family's scope groups, which it
overlaps.  None where the configuration names no such tally or no op ran under
it (as at a parent commit without the scope)."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get("hyper_connection")
    return seconds * 1e3 if seconds else None

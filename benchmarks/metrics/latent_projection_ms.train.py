"""Model step: device self time a step of the ops under ``attention/latent``: a
latent-attention layer's two down-projections, latent norms, two up-
projections, the shared key's rotary, its broadcast to the heads and the
concatenations, both ways.  A tally of the configuration's own
(``scope_tallies`` in its file, group ``latent_projections``), beside the
family's scope groups, which it overlaps.  None where the configuration names
no such tally or no op ran under it (as at a parent commit without the scope)."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get("latent_projections")
    return seconds * 1e3 if seconds else None

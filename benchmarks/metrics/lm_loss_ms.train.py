"""Model step: device self time a step under ``lm_loss``: the output head
over the vocabulary rows held (a leaf of its own where the configuration does
not tie it to the embedding), log-softmax and cross-entropy, forward and
backward, from the scope reduction of the traced slice.  None where the driver
handed no scope reduction over or the program has no such scope."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("scope_s") or {}).get("lm_loss")
    return seconds * 1e3 if seconds else None

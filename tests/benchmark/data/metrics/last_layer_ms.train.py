"""A reader a configuration brings with its own tally (dry-tally-test.json's
``last_layer``): device self time a step of every op under ``/layer_3/``."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get("last_layer")
    return seconds * 1e3 if seconds else None

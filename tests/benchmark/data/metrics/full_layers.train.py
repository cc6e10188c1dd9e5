"""A reader a configuration brings with its own counter (dry-tally-test.json's
``attention/full_layers``): its mean over the traced steps."""


def read(r):
    return (r["trace"].get("counters") or {}).get("attention/full_layers")

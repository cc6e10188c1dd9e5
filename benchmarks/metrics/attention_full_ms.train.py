"""Model step: device self time a step of the ops under
``attention/kernel/full``: the full causal layers' attention kernels, forward
and backward, and the XLA ops around them.  A tally of the configuration's own
(``scope_tallies`` in its file, group ``attention_kernel_full``), beside the
family's scope groups: with ``attention_window_ms.train`` it adds up to the
``attention_kernel`` group.  None where the configuration names no such tally
or no op ran under it."""


def read(r):
    seconds = ((r["trace"].get("program") or {}).get("tally_s") or {}).get(
        "attention_kernel_full")
    return seconds * 1e3 if seconds else None

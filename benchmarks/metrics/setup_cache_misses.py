"""Entry point: programs compiled and not fetched from the persistent cache,
up to and including the train step's own (the log stamps its totals when
the step has its executable: the reference's programs come later).  0 in a
warm run; what tells a set-up that compiled from one that fetched."""

from benchmarks import setup_log


def read(r):
    at = setup_log.step(r, "totals_at_executable")
    return None if at is None else at["compiles"] - at["cache_hits"]

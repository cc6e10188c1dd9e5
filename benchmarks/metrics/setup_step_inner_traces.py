"""Entry point: the traces nested inside the train step's own (every jitted
function, ``custom_vjp`` and Pallas body traced while the step is traced), a
count.  It repeats exactly for one program; a change that traces a body at
every call site shows here first."""

from benchmarks import setup_log


def read(r):
    return setup_log.step(r, "inner_traces")

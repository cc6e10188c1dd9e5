"""Entry point: the train step's executable, ``backend_compile_duration`` of
the step's function: the backend's compile where the persistent cache had no
entry, else the fetch from the cache (read and deserialise), seconds."""

from benchmarks import setup_log


def read(r):
    return setup_log.step(r, "backend_s")

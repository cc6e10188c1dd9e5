"""Entry point: the process's first touch of the chip, the program's span
``rt1/setup/backend_init`` around ``jax.devices()``
(rt1_tpu/parallel/distributed.py::describe_devices), seconds."""

from benchmarks import setup_log


def read(r):
    return setup_log.phase_seconds(r, ("backend_init",))

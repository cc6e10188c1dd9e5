"""Entry point: lowering the train step, jaxpr to MLIR module
(``jaxpr_to_mlir_module_duration`` of the step's function), seconds."""

from benchmarks import setup_log


def read(r):
    return setup_log.step(r, "lower_s")

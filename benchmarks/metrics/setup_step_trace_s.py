"""Entry point: tracing the train step, the outermost trace of the function
``make_train_step_fns`` marked as the step (``jaxpr_trace_duration`` of
``train_step`` / ``train_step_guarded``, with every trace nested in it), seconds."""

from benchmarks import setup_log


def read(r):
    return setup_log.step(r, "trace_s")

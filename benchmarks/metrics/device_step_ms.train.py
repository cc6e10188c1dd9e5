"""Model step: device time of the train-step program per step, from its
events on the device plane's "XLA Modules" line, mean over the traced steps."""

from benchmarks.trace import reduce


def read(r):
    name, program = reduce.step_program(r["trace"])
    r["log"](f"step program on the device: {name}, {program['count']} runs, "
             f"{program['seconds']:.4f}s")
    return program["seconds"] / program["count"] * 1e3

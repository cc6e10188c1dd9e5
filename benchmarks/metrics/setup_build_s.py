"""Entry point: building what the step needs, the self times of the program's
spans ``rt1/setup/build_model``, ``make_optimizer``, ``init_state``,
``make_step_fns``, ``shard_state``, ``open_feed`` and ``first_batch``, summed,
seconds.  Self time: a phase's duration less what its children cover.  The
harness's own work between them (its weights from the seed) is not in it."""

from benchmarks import setup_log


def read(r):
    return setup_log.phase_seconds(r, setup_log.BUILD_PHASES, "self_s")

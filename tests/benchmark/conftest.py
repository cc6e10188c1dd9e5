"""``test_benchmark_flops.py::test_frozen_counts_are_the_walkers`` holds every
configuration of the manifest to ``benchmarks/flops.py``'s jaxpr walker, which
traces the loss over ``program.batch_spec``'s image batch.  A configuration
whose file names another yardstick (``yardstick_command``: a family whose
batch is not images, or whose work a jaxpr does not show, as a grouped
product's routed rows) is held to its frozen counts by that yardstick's own
test (``test_benchmark_tokens.py::test_the_frozen_counts_are_the_yardsticks``)
and skipped there.  Folding the two is a ``benchmark`` issue (ROADMAP R-W0)."""

import json
import os

import pytest

from bench_testlib import REPO

WALKER = "python -m benchmarks.flops "


def pytest_collection_modifyitems(config, items):
    for item in items:
        if getattr(item, "originalname", "") != "test_frozen_counts_are_the_walkers":
            continue
        with open(os.path.join(REPO, item.callspec.params["config"]["file"])) as f:
            command = json.load(f).get("yardstick_command", WALKER)
        if not command.startswith(WALKER):
            item.add_marker(pytest.mark.skip(
                reason=f"its yardstick is `{command}`, not the jaxpr walker"))

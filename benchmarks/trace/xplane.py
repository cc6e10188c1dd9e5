"""First half of the trace reduction: an ``.xplane.pb`` file to flat rows
``(plane, line, name, start_ns, duration_ns)``.

Only device planes and the harness's own ``bench/*`` annotations are kept:
the rest of the host plane is python frames by the hundred thousand.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from typing import List, Tuple

Row = Tuple[str, str, str, int, int]

ANNOTATION_PREFIX = "bench/"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def is_device_plane(plane: str) -> bool:
    return plane.startswith("/device:") and "CUSTOM" not in plane.upper()


def short_name(name: str) -> str:
    """A device event is named by its whole HLO line, ``%fusion.12 = (...)
    fusion(...)``: keep the instruction's own name."""
    return name.split(" = ", 1)[0].lstrip("%")[:120]


def rows_from_xplane(path: str) -> List[Row]:
    from jax.profiler import ProfileData

    rows: List[Row] = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        for line in plane.lines:
            for event in line.events:
                if device or event.name.startswith(ANNOTATION_PREFIX):
                    rows.append((plane.name, line.name, short_name(event.name),
                                 int(event.start_ns), int(event.duration_ns)))
    return rows


def load_rows(path: str) -> List[Row]:
    with gzip.open(path, "rt") as f:
        return [tuple(r) for r in json.load(f)]

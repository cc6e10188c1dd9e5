"""The one general traffic generator for ``kind: train`` mixes.

Reads a mix's parameters (benchmarks/traffic/<name>.json) and builds the host
iterator that the train driver hands to ``device_feeder``.  The construction
is copied from ``bench.py --mode e2e --packed`` (``_ensure_bench_episodes``,
``_e2e_feed``); the program's pack, cache and feeder classes are called, not
copied: they are the input layer under test.
"""

from __future__ import annotations

import hashlib
import itertools
import json
import os
import time
from typing import Any, Dict, Iterator, List

import numpy as np


def load_traffic_file(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def cache_root(root: str) -> str:
    return os.path.join(root, "benchmarks", "_cache")


def _corpus_key(corpus: Dict[str, Any]) -> str:
    blob = json.dumps(corpus, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()[:12]


def ensure_corpus(root: str, corpus: Dict[str, Any]) -> List[str]:
    """Episode files of the mix's synthetic corpus, written once per checkout.

    The schema is ``rt1_tpu/data/episodes.py``'s (rgb, action, is_first,
    is_terminal, instruction), one uncompressed ``.npz`` per episode.
    """
    out = os.path.join(cache_root(root), "corpus", _corpus_key(corpus))
    n, t = int(corpus["episodes"]), int(corpus["steps_per_episode"])
    paths = [os.path.join(out, f"episode_{i:04d}.npz") for i in range(n)]
    done = os.path.join(out, "DONE")
    if os.path.exists(done):
        return paths
    os.makedirs(out, exist_ok=True)
    h, w = int(corpus["src_height"]), int(corpus["src_width"])
    for i, path in enumerate(paths):
        rng = np.random.default_rng([int(corpus["corpus_seed"]), i])
        instruction = rng.standard_normal(int(corpus["instruction_dim"])).astype(np.float32)
        is_first = np.zeros(t, bool)
        is_first[0] = True
        is_terminal = np.zeros(t, bool)
        is_terminal[-1] = True
        tmp = path + ".tmp.npz"
        np.savez(
            tmp,
            rgb=rng.integers(0, 256, (t, h, w, 3), dtype=np.uint8),
            action=rng.uniform(-0.1, 0.1, (t, 2)).astype(np.float32),
            is_first=is_first, is_terminal=is_terminal,
            instruction=np.tile(instruction, (t, 1)),
        )
        os.replace(tmp, path)
    with open(done, "w") as f:
        f.write("ok\n")
    return paths


class TimedIterator:
    """Host iterator with the seconds spent inside ``next()`` summed up
    (``StepTimeline``'s wait_data arithmetic) and, when given, a trace
    annotation around each call."""

    def __init__(self, inner: Iterator, annotate=None):
        self._inner = iter(inner)
        self._annotate = annotate
        self.wait_s = 0.0
        self.calls = 0
        self.taps: List[Any] = []     # first batches, kept for the reference
        self.keep = 0

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        if self._annotate is not None:
            with self._annotate("bench/next_batch"):
                batch = next(self._inner)
        else:
            batch = next(self._inner)
        self.wait_s += time.perf_counter() - t0
        self.calls += 1
        if len(self.taps) < self.keep:
            self.taps.append(batch)
        return batch


class TrainFeed:
    """What the train driver needs from a mix: the host iterator, the task
    names the health pack wants, and a way to stop the feeder's threads."""

    def __init__(self, host_iter: Iterator, health_task_names, closer):
        self.host_iter = host_iter
        self.health_task_names = tuple(health_task_names)
        self._closer = closer

    def close(self) -> None:
        self._closer()


def build_train_feed(root: str, traffic: Dict[str, Any], config, seed: int,
                     emit_task_ids: bool) -> TrainFeed:
    from rt1_tpu.data import pack as pack_lib
    from rt1_tpu.data.feeder import SampleAheadFeeder

    paths = ensure_corpus(root, traffic["corpus"])
    h, w = int(config.data.height), int(config.data.width)
    crop = float(config.data.crop_factor)
    window = int(config.model.time_sequence_length)
    pack_dir = os.path.join(
        cache_root(root), "pack",
        f"{_corpus_key(traffic['corpus'])}_{h}x{w}_c{crop}",
    )
    pack_lib.pack_episodes(paths, pack_dir, h, w, crop)
    cache = pack_lib.PackedEpisodeCache(pack_dir, window=window)
    feeder = SampleAheadFeeder(
        cache, int(config.per_host_batch_size), seed=int(seed), shuffle=True,
        num_threads=int(config.data.get("feeder_threads", 2)),
        depth=int(config.data.get("feeder_depth", 2)),
        refresh_at_epoch=bool(config.data.get("packed_refresh", False)),
        emit_task_ids=emit_task_ids,
    )
    names = feeder.health_task_names
    if traffic["feed"] == "packed":
        return TrainFeed(feeder, names, feeder.close)
    if traffic["feed"] == "pool":
        pool = [next(feeder) for _ in range(int(traffic["pool_batches"]))]
        feeder.close()
        return TrainFeed(itertools.cycle(pool), names, lambda: None)
    raise ValueError(f"unknown feed {traffic['feed']!r} in {traffic['name']}")

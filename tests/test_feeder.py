"""Sample-ahead feeder: determinism, tf.data-path parity, lifecycle.

The spec (rt1_tpu/data/feeder.py): the batch stream is a function of
(seed, epoch, batch-index) only — thread count and timing must not change a
single byte — finite epochs exhaust exactly, and close() stops promptly
from any state. Batch content parity with the existing loaders is pinned
against `WindowedEpisodeDataset.numpy_batches` (same windows, same padding,
same labels) and, with augmentation on, via the packed cache's crop-parity
guarantees (tests/test_packed_cache.py).
"""

import itertools

import numpy as np
import pytest

from rt1_tpu.data import episodes as ep_lib
from rt1_tpu.data import pack as pack_lib
from rt1_tpu.data.feeder import SampleAheadFeeder
from rt1_tpu.data.pipeline import WindowedEpisodeDataset

SRC_H, SRC_W = 24, 40
H, W = 16, 28
WINDOW = 3


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("feeder_corpus")
    rng = np.random.default_rng(0)
    paths = []
    for i in range(4):
        p = str(tmp / f"episode_{i}.npz")
        ep_lib.save_episode(
            p,
            ep_lib.generate_synthetic_episode(
                rng, num_steps=6, height=SRC_H, width=SRC_W
            ),
        )
        paths.append(p)
    return paths


def _cache(tmp_path_factory, paths, crop_factor):
    out = str(tmp_path_factory.mktemp("packed"))
    pack_lib.pack_episodes(paths, out, H, W, crop_factor)
    return pack_lib.PackedEpisodeCache(out, window=WINDOW)


@pytest.fixture(scope="module")
def cache(tmp_path_factory, corpus):
    return _cache(tmp_path_factory, corpus, 0.95)


@pytest.fixture(scope="module")
def cache_nocrop(tmp_path_factory, corpus):
    return _cache(tmp_path_factory, corpus, None)


def _batches_equal(a, b):
    np.testing.assert_array_equal(
        a["observations"]["image"], b["observations"]["image"]
    )
    np.testing.assert_array_equal(
        a["observations"]["natural_language_embedding"],
        b["observations"]["natural_language_embedding"],
    )
    np.testing.assert_array_equal(
        a["actions"]["terminate_episode"], b["actions"]["terminate_episode"]
    )
    np.testing.assert_array_equal(a["actions"]["action"], b["actions"]["action"])


def test_feeder_shapes_and_dtypes(cache):
    with SampleAheadFeeder(cache, 4, seed=0) as f:
        batch = next(f)
    img = batch["observations"]["image"]
    assert img.shape == (4, WINDOW, H, W, 3) and img.dtype == np.uint8
    assert batch["observations"]["natural_language_embedding"].shape == (4, WINDOW, 512)
    assert batch["actions"]["terminate_episode"].shape == (4, WINDOW)
    assert batch["actions"]["action"].shape == (4, WINDOW, 2)


def test_feeder_deterministic_across_thread_counts(cache):
    """1 thread == 3 threads, batch for batch — assembly parallelism is
    invisible in the stream."""
    streams = []
    for n_threads in (1, 3):
        with SampleAheadFeeder(
            cache, 4, seed=7, num_epochs=2, num_threads=n_threads
        ) as f:
            streams.append(list(f))
    assert len(streams[0]) == len(streams[1]) > 0
    for a, b in zip(*streams):
        _batches_equal(a, b)


def test_feeder_restart_reproduces_stream(cache):
    with SampleAheadFeeder(cache, 4, seed=3, num_epochs=1) as f:
        first = list(f)
    with SampleAheadFeeder(cache, 4, seed=3, num_epochs=1) as f:
        again = list(f)
    for a, b in zip(first, again):
        _batches_equal(a, b)


def test_feeder_seed_changes_stream(cache):
    with SampleAheadFeeder(cache, 4, seed=1, num_epochs=1) as f:
        a = next(f)
    with SampleAheadFeeder(cache, 4, seed=2, num_epochs=1) as f:
        b = next(f)
    assert not np.array_equal(
        a["observations"]["image"], b["observations"]["image"]
    )


def test_feeder_exhaustion_count(cache):
    n_windows = len(cache)
    batch = 4
    epochs = 3
    with SampleAheadFeeder(cache, batch, seed=0, num_epochs=epochs) as f:
        got = sum(1 for _ in f)
    assert got == (n_windows // batch) * epochs
    # Exhausted for good — StopIteration, not a hang.
    assert list(itertools.islice(f, 2)) == []


def test_feeder_close_midstream_and_joins(cache):
    f = SampleAheadFeeder(cache, 4, seed=0, num_threads=2, depth=1)
    next(f)
    f.close()
    assert list(itertools.islice(f, 2)) == []
    for t in f._threads:
        assert not t.is_alive()
    f.close()  # idempotent


def test_feeder_worker_error_surfaces_on_consumer(cache, monkeypatch):
    """A dying worker must raise on the train loop's thread, not strand it
    in an eternal queue wait."""
    boom = ValueError("frames.bin ate itself")

    def explode(*a, **k):
        raise boom

    monkeypatch.setattr(cache, "fill_batch", explode)
    f = SampleAheadFeeder(cache, 4, seed=0, num_threads=2)
    with pytest.raises(RuntimeError, match="feeder worker failed") as ei:
        next(f)
    assert ei.value.__cause__ is boom
    f.close()


def test_feeder_close_without_consuming(cache):
    """close() with full queues and nothing consumed must not deadlock."""
    f = SampleAheadFeeder(cache, 4, seed=0, num_threads=2, depth=1)
    import time

    time.sleep(0.2)  # let workers fill their queues
    f.close()
    for t in f._threads:
        assert not t.is_alive()


def test_feeder_process_sharding_partitions_windows(cache):
    """Two process shards see disjoint windows covering the full epoch."""
    seen = []
    for pi in (0, 1):
        with SampleAheadFeeder(
            cache, 2, seed=5, shuffle=False, num_epochs=1,
            process_index=pi, process_count=2,
        ) as f:
            n = sum(1 for _ in f)
        order = f._epoch_order(0)
        seen.append(set(order.tolist()))
        assert n == f.batches_per_epoch
    assert seen[0].isdisjoint(seen[1])
    assert seen[0] | seen[1] == set(range(len(cache)))


def test_feeder_rejects_oversized_batch(cache):
    with pytest.raises(ValueError, match="exceeds"):
        SampleAheadFeeder(cache, len(cache) + 1, start=False)


# ------------------------------------------- multi-host slices (ISSUE 14)


def test_feeder_host_slices_partition_single_host_stream(cache):
    """Satellite (ISSUE 14): for process_count ∈ {1, 2, 4} the per-host
    window streams are a permutation-free partition of the single-host
    stream — concatenating the hosts' blocks global-batch by global-batch
    reproduces the single-host order EXACTLY (not merely as a set), and
    the per-host orders are disjoint and jointly exhaustive over the
    batched prefix."""
    global_batch = 4
    ref = None
    for pc in (1, 2, 4):
        feeders = [
            SampleAheadFeeder(
                cache, global_batch // pc, seed=11, num_epochs=1,
                process_index=pi, process_count=pc, start=False,
            )
            for pi in range(pc)
        ]
        orders = [f.host_order(0) for f in feeders]
        for f in feeders:
            f.close()
        # Disjoint + exhaustive over the batched prefix.
        union = np.concatenate(orders)
        assert len(set(union.tolist())) == len(union) == len(cache)
        # Exact stream: interleave host blocks back into global batches.
        nb = len(orders[0]) * pc // global_batch
        merged = (
            np.stack(
                [o.reshape(nb, global_batch // pc) for o in orders], axis=1
            ).reshape(-1)
        )
        if ref is None:
            ref = merged
        np.testing.assert_array_equal(merged, ref)


def test_feeder_host_shards_concat_to_single_host_batch(cache):
    """Per-host BATCHES (pixels, crops, labels — everything) concatenate
    to the exact single-host batch: the layout
    `jax.make_array_from_process_local_data` lays out over a host-major
    mesh. Augmentation included — each host draws the GLOBAL batch's crop
    offsets from the shared rng and keeps its rows (pack.fill_batch's
    `offsets` seam)."""
    single = list(
        itertools.islice(
            SampleAheadFeeder(cache, 4, seed=11, num_epochs=1), 4
        )
    )
    shards = [
        list(
            itertools.islice(
                SampleAheadFeeder(
                    cache, 2, seed=11, num_epochs=1,
                    process_index=pi, process_count=2,
                ),
                4,
            )
        )
        for pi in range(2)
    ]
    for b, want in enumerate(single):
        got = _tree_concat(shards[0][b], shards[1][b])
        _batches_equal(got, want)


def _tree_concat(a, b):
    if isinstance(a, dict):
        return {k: _tree_concat(a[k], b[k]) for k in a}
    return np.concatenate([a, b])


def test_feeder_uniform_batch_count_across_hosts(tmp_path):
    """Every host sees the SAME per-epoch batch count even when the corpus
    is not process-divisible — a per-host strided split hands one host an
    extra batch, which on a real mesh deadlocks the epoch's last
    collective. 3 episodes × 6 steps = 18 windows, global batch 4: every
    host must see 4 batches, the 2-window tail dropped on all alike."""
    rng = np.random.default_rng(3)
    paths = []
    for i in range(3):
        p = str(tmp_path / f"episode_{i}.npz")
        ep_lib.save_episode(
            p,
            ep_lib.generate_synthetic_episode(
                rng, num_steps=6, height=SRC_H, width=SRC_W
            ),
        )
        paths.append(p)
    out = str(tmp_path / "packed")
    pack_lib.pack_episodes(paths, out, H, W, 0.95)
    c = pack_lib.PackedEpisodeCache(out, window=WINDOW)
    counts = []
    for pi in range(2):
        f = SampleAheadFeeder(
            c, 2, seed=0, num_epochs=1, process_index=pi, process_count=2,
            start=False,
        )
        counts.append(f.batches_per_epoch)
        f.close()
    assert counts == [4, 4]


def test_feeder_matches_numpy_loader_without_augmentation(corpus, cache_nocrop):
    """crop_factor None: the feeder's batches equal the existing numpy
    loader's byte-for-byte (same windows, same padding, same labels; images
    resized once by the same backend) — content parity with the tf.data
    family under a fixed (here: absent) augmentation draw."""
    ds = WindowedEpisodeDataset(
        corpus, window=WINDOW, crop_factor=None, height=H, width=W
    )
    want = list(
        itertools.islice(ds.numpy_batches(4, shuffle=False, num_epochs=1), 3)
    )
    with SampleAheadFeeder(
        cache_nocrop, 4, seed=0, shuffle=False, num_epochs=1
    ) as f:
        got = list(itertools.islice(f, 3))
    for a, b in zip(got, want):
        _batches_equal(a, b)


# ------------------------------------------------- task mixture (ISSUE 13)


@pytest.fixture(scope="module")
def tagged_cache(tmp_path_factory):
    """Packed corpus with per-episode task tags: 2x 'block2block' +
    2x 'corner' episodes, 6 steps each."""
    tmp = tmp_path_factory.mktemp("tagged_corpus")
    rng = np.random.default_rng(3)
    paths = []
    for i, task in enumerate(
        ("block2block", "block2block", "corner", "corner")
    ):
        ep = ep_lib.generate_synthetic_episode(
            rng, num_steps=6, height=SRC_H, width=SRC_W
        )
        ep["task"] = ep_lib.encode_instruction_text(task)
        p = str(tmp / f"episode_{i}.npz")
        ep_lib.save_episode(p, ep)
        paths.append(p)
    out = str(tmp_path_factory.mktemp("tagged_packed"))
    pack_lib.pack_episodes(paths, out, H, W, 0.95)
    return pack_lib.PackedEpisodeCache(out, window=WINDOW)


def test_parse_task_weights():
    from rt1_tpu.data.feeder import parse_task_weights

    assert parse_task_weights(None) is None
    assert parse_task_weights("") is None
    assert parse_task_weights("a:3,b:1") == {"a": 3.0, "b": 1.0}
    # Task slugs may contain ':' — the weight is after the LAST colon.
    assert parse_task_weights("unknown:mystery:2") == {
        "unknown:mystery": 2.0
    }
    assert parse_task_weights({"a": 1}) == {"a": 1.0}
    with pytest.raises(ValueError, match="not a number"):
        parse_task_weights("a:x")
    with pytest.raises(ValueError, match="no positive weight"):
        parse_task_weights("a:0,b:0")
    with pytest.raises(ValueError, match=">= 0"):
        parse_task_weights("a:-1")


def test_task_weights_none_is_pre_pr_stream(cache):
    """weights=None must be the EXACT pre-task order draw: the legacy
    (seed, epoch)-keyed permutation, bit-identical — and a feeder built
    with an explicit None matches one that never heard of the kwarg."""
    with SampleAheadFeeder(
        cache, 4, seed=11, num_epochs=1, task_weights=None
    ) as f:
        got = list(f)
    with SampleAheadFeeder(cache, 4, seed=11, num_epochs=1) as g:
        want = list(g)
    for a, b in zip(got, want):
        _batches_equal(a, b)
    assert "task_id" not in got[0]["observations"]
    # The order formula itself is the pinned pre-PR one.
    order = g._compute_order(0, len(cache))
    legacy = np.arange(len(cache))
    np.random.default_rng([11, 0]).shuffle(legacy)
    np.testing.assert_array_equal(order, legacy)


def test_task_weights_deterministic_across_threads(tagged_cache):
    """Same (seed, epoch, corpus, weights) -> byte-identical stream
    (images, labels, AND task ids) regardless of worker thread count."""
    streams = []
    for n_threads in (1, 3):
        with SampleAheadFeeder(
            tagged_cache, 4, seed=5, num_epochs=2, num_threads=n_threads,
            task_weights={"block2block": 3, "corner": 1},
            emit_task_ids=True,
        ) as f:
            streams.append(list(f))
    assert len(streams[0]) == len(streams[1]) > 0
    for a, b in zip(*streams):
        _batches_equal(a, b)
        np.testing.assert_array_equal(
            a["observations"]["task_id"], b["observations"]["task_id"]
        )


def test_task_weights_change_the_stream_key(tagged_cache):
    """Different weights -> a different (reproducible) order; the weights
    digest is folded into the shuffle key."""
    f1 = SampleAheadFeeder(
        tagged_cache, 4, seed=5, start=False,
        task_weights={"block2block": 3, "corner": 1},
    )
    f2 = SampleAheadFeeder(
        tagged_cache, 4, seed=5, start=False,
        task_weights={"block2block": 1, "corner": 3},
    )
    o1 = f1._compute_order(0, len(tagged_cache))
    o2 = f2._compute_order(0, len(tagged_cache))
    assert not np.array_equal(o1, o2)
    # Same weights -> same order (pure function, no feeder state).
    f3 = SampleAheadFeeder(
        tagged_cache, 4, seed=5, start=False,
        task_weights={"block2block": 3, "corner": 1},
    )
    np.testing.assert_array_equal(
        o1, f3._compute_order(0, len(tagged_cache))
    )


def test_task_weights_empirical_mixture_frequency(tagged_cache):
    """A 3:1 weighted mixture's empirical task frequencies land within
    tolerance of 0.75/0.25 over a few epochs (each task owns half the
    corpus windows, so the uniform draw would give 0.5/0.5)."""
    with SampleAheadFeeder(
        tagged_cache, 4, seed=9, num_epochs=4,
        task_weights={"block2block": 3, "corner": 1},
        emit_task_ids=True,
    ) as f:
        names = f.health_task_names
        counts = np.zeros(len(names), np.int64)
        for batch in f:
            tid = batch["observations"]["task_id"]
            assert tid.dtype == np.int32 and tid.shape == (4,)
            counts += np.bincount(tid, minlength=len(names))
    freq = counts / counts.sum()
    by_name = dict(zip(names, freq))
    assert names == ("block2block", "corner", "other")
    assert abs(by_name["block2block"] - 0.75) < 0.12
    assert abs(by_name["corner"] - 0.25) < 0.12
    assert by_name["other"] == 0.0


def test_task_weights_wildcard_and_unmatched(tagged_cache):
    """'*' weights every unnamed task; weights matching no corpus task
    raise loudly at order-draw time instead of feeding an empty epoch."""
    f = SampleAheadFeeder(
        tagged_cache, 4, seed=0, start=False,
        task_weights={"corner": 1, "*": 0.0},
    )
    order = f._compute_order(0, len(tagged_cache))
    # Only corner windows (episodes 2-3 -> windows 12..23) can be drawn.
    assert set(np.asarray(order) // 6) <= {2, 3}
    with pytest.raises(ValueError, match="zero total weight"):
        SampleAheadFeeder(
            tagged_cache, 4, seed=0, start=False,
            task_weights={"zebra": 1.0},
        )


def test_task_weights_require_shuffle(tagged_cache):
    with pytest.raises(ValueError, match="shuffle"):
        SampleAheadFeeder(
            tagged_cache, 4, seed=0, shuffle=False, start=False,
            task_weights={"corner": 1},
        )


def test_emit_task_ids_member_and_names(tagged_cache, cache):
    """emit_task_ids adds ONE (batch,) int32 member whose ids index the
    frozen health_task_names table (sorted unique tasks + 'other');
    untagged corpora map every window to 'unknown'."""
    with SampleAheadFeeder(
        tagged_cache, 4, seed=2, num_epochs=1, emit_task_ids=True
    ) as f:
        assert f.health_task_names == ("block2block", "corner", "other")
        batch = next(f)
        tid = batch["observations"]["task_id"]
        order = f._epoch_order(0)
        for j, idx in enumerate(order[:4]):
            task = tagged_cache.episode_task(
                tagged_cache.index[int(idx)][0]
            )
            assert f.health_task_names[tid[j]] == task
    # Untagged corpus: every episode reports the UNKNOWN_TASK slug.
    with SampleAheadFeeder(
        cache, 4, seed=2, num_epochs=1, emit_task_ids=True
    ) as g:
        assert g.health_task_names == ("unknown", "other")
        assert set(next(g)["observations"]["task_id"]) == {0}
    # Off (the default): no member, pre-PR batch structure.
    with SampleAheadFeeder(cache, 4, seed=2, num_epochs=1) as h:
        assert h.health_task_names == ()
        assert "task_id" not in next(h)["observations"]


def test_emit_task_ids_literal_other_task_no_duplicate(tmp_path_factory):
    """A corpus whose episodes are literally tagged 'other' must not
    produce a duplicate name in the frozen id table — the real task and
    the overflow bucket share the one 'other' entry."""
    tmp = tmp_path_factory.mktemp("other_corpus")
    rng = np.random.default_rng(4)
    paths = []
    for i, task in enumerate(("other", "corner")):
        ep = ep_lib.generate_synthetic_episode(
            rng, num_steps=6, height=SRC_H, width=SRC_W
        )
        ep["task"] = ep_lib.encode_instruction_text(task)
        p = str(tmp / f"episode_{i}.npz")
        ep_lib.save_episode(p, ep)
        paths.append(p)
    out = str(tmp_path_factory.mktemp("other_packed"))
    pack_lib.pack_episodes(paths, out, H, W, None)
    cache = pack_lib.PackedEpisodeCache(out, window=WINDOW)
    with SampleAheadFeeder(
        cache, 4, seed=0, num_epochs=1, emit_task_ids=True
    ) as f:
        names = f.health_task_names
        assert names == ("corner", "other")
        assert len(names) == len(set(names))
        batch = next(f)
        tid = batch["observations"]["task_id"]
        assert set(tid) <= set(range(len(names)))


def test_train_dataset_batches_packed_switch(tmp_path, corpus):
    """train.dataset_batches honors data.packed_cache: fresh cache feeds
    through the feeder; missing cache falls back to the tf.data path."""
    jax = pytest.importorskip("jax")
    del jax
    from rt1_tpu.train.configs import tiny
    from rt1_tpu.train.train import dataset_batches

    import os
    import shutil

    data_dir = str(tmp_path / "store")
    os.makedirs(os.path.join(data_dir, "train"))
    for p in corpus:
        shutil.copy(p, os.path.join(data_dir, "train", os.path.basename(p)))
    paths = sorted(
        os.path.join(data_dir, "train", f)
        for f in os.listdir(os.path.join(data_dir, "train"))
    )

    config = tiny.get_config()
    with config.unlocked():
        config.data.data_dir = data_dir
        config.data.packed_cache = True
        config.per_host_batch_size = 2
    # No pack built yet -> falls back (tf.data path still yields batches).
    it = dataset_batches(config, "train")
    assert not isinstance(it, SampleAheadFeeder)

    pack_lib.pack_episodes(
        paths,
        pack_lib.default_pack_dir(data_dir, "train"),
        config.data.height,
        config.data.width,
        config.data.crop_factor,
    )
    it = dataset_batches(config, "train")
    assert isinstance(it, SampleAheadFeeder)
    batch = next(it)
    assert batch["observations"]["image"].shape == (
        2,
        config.model.time_sequence_length,
        config.data.height,
        config.data.width,
        3,
    )
    # tiny config ships model_health off -> no task-id member, no
    # mixture: the pre-task stream byte-for-byte.
    assert "task_id" not in batch["observations"]
    assert it.task_weights is None and not it.emit_task_ids
    it.close()

    # With model_health on, the train feeder arms per-task telemetry and
    # honors config.data.task_weights ("task:weight,..." string).
    with config.unlocked():
        config.obs.model_health = True
        config.data.task_weights = "unknown:2"
    it = dataset_batches(config, "train")
    assert isinstance(it, SampleAheadFeeder)
    assert it.emit_task_ids
    # This corpus is untagged -> one real task ("unknown") + overflow.
    assert it.health_task_names == ("unknown", "other")
    assert it.task_weights == {"unknown": 2.0}
    batch = next(it)
    tid = batch["observations"]["task_id"]
    assert tid.shape == (2,) and tid.dtype == np.int32
    assert set(tid) == {0}
    it.close()


def test_spans_at_the_layer_boundaries_line_up_by_ticket(cache):
    """assemble (worker threads) -> next (the loop's) -> h2d/put (the loop's):
    one batch, one ticket; `ready` says how far ahead the workers were; an
    epoch end is a span of its own. Read from the ring here; in a profile
    the same spans are `rt1/<name>` (tests/test_obs_trace.py)."""
    import jax
    from jax.sharding import SingleDeviceSharding

    from rt1_tpu.data.pipeline import device_feeder
    from rt1_tpu.obs import trace

    trace._tracer = None
    rec = trace.enable()
    try:
        with SampleAheadFeeder(
            cache, 4, seed=1, num_epochs=2, num_threads=2, depth=2
        ) as feeder:
            n = len(list(device_feeder(
                feeder, SingleDeviceSharding(jax.devices()[0]), depth=2
            )))
    finally:
        trace._tracer = None
    spans = [e for e in rec.to_dict()["traceEvents"] if e["ph"] == "X"]
    by = {}
    for e in spans:
        by.setdefault(e["name"], []).append(e)
    tickets = lambda name: [e["args"]["ticket"] for e in by[name]]  # noqa: E731
    assert n > 2 and tickets("feeder/next") == list(range(n))
    assert tickets("h2d/put") == list(range(n))
    assert set(tickets("feeder/assemble")) >= set(range(n))
    assert all(0 <= e["args"]["ready"] <= 4 for e in by["feeder/next"])
    assert all(e["args"]["bytes"] > 0 for e in by["h2d/put"])
    assert {e["args"]["epoch"] for e in by["feeder/epoch"]} >= {0, 1}
    # the loop's spans on one thread, the workers' on two others
    loop = {e["tid"] for e in by["feeder/next"] + by["h2d/put"]}
    workers = {e["tid"] for e in by["feeder/assemble"]}
    assert len(loop) == 1 and len(workers) == 2 and not loop & workers
    assert all(e["name"] != "feeder_assemble" for e in spans)

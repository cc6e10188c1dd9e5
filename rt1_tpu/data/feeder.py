"""Sample-ahead feeder: background batch assembly over the packed cache.

The tf.data loader interleaves window assembly with the train loop's own
host time slice; on a single-core host the two serialize and the device
starves (the 78% input stall, docs/performance.md). This feeder runs batch
assembly on background threads against `PackedEpisodeCache` — where a
window is mmap slices, not decodes, so assembly is memcpy-bound and the
GIL-free native gather lets N threads genuinely overlap — and parks
finished batches in a bounded ring of queues. The consumer (the train
loop, via `data.pipeline.device_feeder`) pops ready uint8 batches and
spends its host slice only on `jax.device_put`.

Determinism: the batch schedule and every crop draw are functions of
(seed, epoch, batch-in-epoch) only — never of thread count or timing — so
two feeders with the same seed yield identical batch streams, and a
1-thread feeder reproduces an 8-thread one bit-for-bit (pinned in
tests/test_feeder.py).

Multi-host (``process_count > 1``, docs/parallelism.md "Multi-host"): the
epoch order is drawn GLOBALLY — one permutation (or weighted draw), a pure
function of (seed, epoch, corpus[, weights]) that no process identity
enters — and each global batch of ``batch_size × process_count`` windows
is split into per-host blocks: host p assembles rows
``[p·batch_size, (p+1)·batch_size)`` of global batch b. Host slices are
therefore disjoint, jointly exhaustive over the batched prefix, and
CONCATENATE to the exact single-host batch (the layout
`jax.make_array_from_process_local_data` expects for a batch sharded over
a host-major mesh, data/pipeline.py `device_feeder`) — all pinned in
tests/test_feeder.py. Every host draws the same global order, so no
cross-host coordination happens at epoch boundaries, and — unlike a
per-host strided slice — every host sees the same per-epoch batch count
even when the corpus size is not process-divisible (a strided split can
hand one host an extra batch, which deadlocks the collective at the
epoch's last step).

Flywheel (`refresh_at_epoch=True`): at every epoch boundary the feeder asks
the cache to re-read its manifest and open any newly appended shards
(`PackedEpisodeCache.refresh`), then draws that epoch's shuffle over the
grown window set. The epoch stream stays a pure function of
(seed, epoch, corpus-at-epoch-start): because the crop rng is keyed on
(epoch, batch-in-epoch) — not on the flat ticket — a feeder that picked a
shard up mid-run emits byte-identical epochs to one constructed after the
append (pinned in tests/test_flywheel.py). A mid-epoch append never
perturbs the epoch in flight.

Lifecycle: `close()` (or the context manager / garbage collection) stops
the workers promptly even when queues are full; a finite `num_epochs`
stream raises StopIteration after exactly the per-epoch batch counts sum.
"""

from __future__ import annotations

import bisect
import queue
import threading
import time
import zlib
from typing import Dict, Iterator, List, Mapping, Optional, Tuple

import numpy as np

from rt1_tpu.data.pack import UNKNOWN_TASK, PackedEpisodeCache
from rt1_tpu.obs.health import TASK_ID_KEY
from rt1_tpu.obs import startup
from rt1_tpu.obs import trace as obs_trace
from rt1_tpu.resilience import faults

#: Trailing task-id bucket for episodes whose task appeared AFTER feeder
#: construction (a flywheel append introducing a brand-new workload tag):
#: the health pack's layout is frozen at step-build time, so late tasks
#: land in one stable overflow bucket instead of shifting the layout.
OTHER_TASK = "other"


def parse_task_weights(spec) -> Optional[Dict[str, float]]:
    """``"block2block:3,corner:1"`` -> ``{"block2block": 3.0, "corner": 1.0}``.

    The config-string form of per-task sampling weights
    (``config.data.task_weights``) — a string so a single
    ``--config.data.task_weights=...`` CLI override works. ``None``/empty
    returns None (mixture sampling off, the bit-identical pre-task
    stream). A mapping passes through (validated). Weights must be
    non-negative with at least one positive; a task absent from the
    corpus simply never matches (the feeder validates coverage against
    the actual corpus at order-draw time). The special key ``"*"`` sets
    the weight for every task not named explicitly (default 0 = excluded).
    """
    if spec is None:
        return None
    if isinstance(spec, Mapping):
        items = dict(spec)
    else:
        text = str(spec).strip()
        if not text:
            return None
        items = {}
        for part in text.split(","):
            part = part.strip()
            if not part:
                continue
            # rsplit: task slugs may themselves contain ':' ("unknown:foo").
            name, _, weight = part.rpartition(":")
            if not name:
                raise ValueError(
                    f"task_weights entry {part!r} is not '<task>:<weight>'"
                )
            items[name] = weight
    out = {}
    for name, weight in items.items():
        try:
            w = float(weight)
        except (TypeError, ValueError) as exc:
            raise ValueError(
                f"task_weights[{name!r}] = {weight!r} is not a number"
            ) from exc
        if w < 0 or not np.isfinite(w):
            raise ValueError(
                f"task_weights[{name!r}] = {w} must be finite and >= 0"
            )
        out[name] = w
    if not out:
        return None
    if not any(v > 0 for v in out.values()):
        raise ValueError(f"task_weights {out} has no positive weight")
    return out


class FeederStalledError(RuntimeError):
    """The consumer waited past `stall_timeout_s` with no batch and no error.

    A worker that raises is already surfaced by `_raise_or_stop`; this
    covers the worse case — a worker that deadlocks or dies *silently*
    (native-code hang, a thread killed without unwinding) — where a plain
    `q.get()` would block the train loop forever. The message names which
    worker threads are still alive and the per-queue depths, so the
    post-mortem starts with the right thread instead of a generic hang.
    """


class SampleAheadFeeder:
    """Iterator of training batch dicts assembled ahead of the consumer.

    Yields the same nested {"observations": ..., "actions": ...} dict as
    `WindowedEpisodeDataset`'s loaders, with uint8 images.
    """

    @startup.phased("open_feed")
    def __init__(
        self,
        cache: PackedEpisodeCache,
        batch_size: int,
        *,
        seed: int = 0,
        shuffle: bool = True,
        num_epochs: Optional[int] = None,
        num_threads: int = 2,
        depth: int = 2,
        process_index: int = 0,
        process_count: int = 1,
        start: bool = True,
        stall_timeout_s: Optional[float] = None,
        refresh_at_epoch: bool = False,
        task_weights: Optional[Mapping[str, float]] = None,
        emit_task_ids: bool = False,
    ):
        if batch_size <= 0:
            raise ValueError(f"batch_size must be positive, got {batch_size}")
        if stall_timeout_s is not None and stall_timeout_s <= 0:
            raise ValueError(
                f"stall_timeout_s must be positive or None, got "
                f"{stall_timeout_s}"
            )
        self.cache = cache
        self.batch_size = batch_size
        self.stall_timeout_s = stall_timeout_s
        self.seed = seed
        self.shuffle = shuffle
        self.num_epochs = num_epochs
        self.num_threads = max(1, num_threads)
        self.depth = max(1, depth)
        self.process_index = process_index
        self.process_count = process_count
        if refresh_at_epoch and process_count > 1:
            # The multi-host contract is "every host draws the same global
            # order by construction" — a pure function of (seed, epoch,
            # corpus). A flywheel refresh is a per-host filesystem read
            # with no cross-host barrier: host 0 could see an appended
            # shard at an epoch boundary that host 1's (slightly earlier,
            # or failed-and-swallowed) refresh missed, after which the
            # hosts draw different orders AND different per-epoch batch
            # counts — overlapping slices and a deadlocked collective at
            # the shorter host's epoch end. Refuse here, loudly, instead
            # of corrupting the stream; train/train.py disables the
            # flywheel hook on multi-process runs for the same reason.
            raise ValueError(
                "refresh_at_epoch (the flywheel's mid-run corpus pickup) "
                "is single-process only: epoch-boundary manifest reads "
                "have no cross-host synchronization, so hosts could draw "
                "orders over different corpus snapshots. Restart training "
                "to absorb appended shards on multi-host runs."
            )
        self.refresh_at_epoch = refresh_at_epoch
        # Task-mixture sampling (docs/data.md "Task-mixture sampling"):
        # with weights, each epoch's order is a weighted draw WITH
        # replacement over the corpus windows (p_i ∝ weight of window i's
        # task), still a pure function of (seed, epoch, corpus, weights) —
        # the weights fold into the shuffle rng key, so two feeders with
        # the same tuple emit byte-identical streams and weights=None is
        # the exact pre-task permutation path.
        self.task_weights = parse_task_weights(task_weights)
        if self.task_weights is not None and not shuffle:
            raise ValueError(
                "task_weights requires shuffle=True (a weighted epoch is "
                "a sampled mixture, not a deterministic corpus walk)"
            )
        self._weights_key = (
            zlib.crc32(
                repr(sorted(self.task_weights.items())).encode("utf-8")
            )
            if self.task_weights is not None
            else 0
        )
        # Per-task telemetry: emit a (batch,) int32 `task_id` member the
        # jitted step's one-hot segment reduction consumes. The id table
        # is frozen at construction (sorted unique corpus tasks + one
        # trailing OTHER_TASK overflow bucket), so the health-pack layout
        # is static even while the flywheel grows the corpus mid-run. A
        # corpus that already carries a literal "other" task shares that
        # bucket with post-append novel tasks (no duplicate pack entry).
        self.emit_task_ids = emit_task_ids
        self._task_index = {
            name: i for i, name in enumerate(sorted(set(cache.tasks)))
        }
        names = tuple(sorted(self._task_index))
        if OTHER_TASK not in self._task_index:
            names = names + (OTHER_TASK,)
        self.health_task_names: Tuple[str, ...] = (
            names if emit_task_ids else ()
        )

        # Per-epoch corpus snapshots: each entry pins the window count and
        # shuffle order one epoch's batches are drawn from, so a flywheel
        # append only ever changes epochs whose order has not been drawn
        # yet. `_firsts[e]` = the first global ticket of epoch e (epochs
        # have different batch counts once the corpus grows).
        self._order_lock = threading.Lock()
        self._epochs: List[Dict] = []
        self._firsts: List[int] = []
        self._materialize_next_epoch_locked_unsafe()
        self.batches_per_epoch = self._epochs[0]["batches"]
        if self.batches_per_epoch == 0:
            raise ValueError(
                f"global batch ({batch_size} per host x "
                f"{self.process_count} processes) exceeds the corpus's "
                f"{len(self._epochs[0]['order'])} windows"
            )
        # Static corpora keep the exact pre-flywheel exhaustion arithmetic;
        # a refreshing feeder's end is located per-epoch (counts can grow).
        self.total_batches = (
            self.batches_per_epoch * num_epochs
            if num_epochs is not None and not refresh_at_epoch
            else None
        )

        meta0 = cache.meta(0)
        self._embed_dim = int(meta0["instruction"].shape[1])
        self._action_dim = int(meta0["action"].shape[1])

        self._error: Optional[BaseException] = None
        self._stop = threading.Event()
        self._queues = [
            queue.Queue(maxsize=self.depth) for _ in range(self.num_threads)
        ]
        self._threads = [
            threading.Thread(
                target=self._worker, args=(k,), daemon=True,
                name=f"rt1-feeder-{k}",
            )
            for k in range(self.num_threads)
        ]
        self._next_ticket = 0
        self._started = False
        # Per-worker observability counters (rt1_tpu/obs): index-assigned
        # list writes are GIL-atomic, so workers update lock-free and
        # `stats()` reads a consistent-enough snapshot for gauges.
        self._assembled = [0] * self.num_threads
        self._assembly_s = [0.0] * self.num_threads
        if start:
            self.start()

    # ------------------------------------------------------------ schedule

    def _compute_order(self, epoch: int, n_windows: int) -> np.ndarray:
        """The GLOBAL window order for `epoch` over an `n_windows` corpus —
        a pure function of (seed, epoch, n_windows[, weights]) that the
        process identity never enters: every host of a multi-process run
        draws this same order and takes its block of each global batch
        (`_host_indices`), so the global stream is exactly the
        single-host stream no matter how many hosts split it.

        task_weights=None keeps the EXACT pre-task permutation draw (same
        rng key, same shuffle — bit-identical, pinned in tests). With
        weights, the epoch becomes a weighted draw with replacement
        (p_window ∝ weight of its episode's task), the weights digest
        folded into the rng key so different mixtures give different —
        but individually reproducible — streams.
        """
        if self.task_weights is not None:
            w = self._window_weights(n_windows)
            total = w.sum()
            if total <= 0:
                raise ValueError(
                    f"task_weights {self.task_weights} give zero total "
                    f"weight over this corpus (tasks: "
                    f"{sorted(set(self.cache.tasks[:]))})"
                )
            rng = np.random.default_rng(
                [self.seed, epoch, self._weights_key]
            )
            return rng.choice(
                n_windows, size=n_windows, replace=True, p=w / total
            )
        order = np.arange(n_windows)
        if self.shuffle:
            np.random.default_rng([self.seed, epoch]).shuffle(order)
        return order

    @property
    def global_batch_size(self) -> int:
        """Windows per GLOBAL batch (all hosts' shards together)."""
        return self.batch_size * self.process_count

    def _host_indices(self, order: np.ndarray, b: int) -> np.ndarray:
        """This host's `batch_size` window indices of global batch `b`:
        rows [p·B, (p+1)·B) of the order's b-th global-batch block. Hosts'
        slices concatenate (in process order) to the exact single-host
        batch — the contract `jax.make_array_from_process_local_data`
        needs for a batch dim sharded over a host-major mesh."""
        base = b * self.global_batch_size + self.process_index * self.batch_size
        return order[base : base + self.batch_size]

    def host_order(self, epoch: int) -> np.ndarray:
        """This host's window sequence for `epoch` (batched prefix only:
        the order's tail that fills no complete global batch is dropped on
        every host alike). Observability/test accessor — assembly reads
        `_host_indices` per batch."""
        order = self._order_for(epoch)
        nb = len(order) // self.global_batch_size
        if self.process_count == 1:
            return order[: nb * self.global_batch_size]
        return (
            order[: nb * self.global_batch_size]
            .reshape(nb, self.process_count, self.batch_size)[
                :, self.process_index
            ]
            .reshape(-1)
        )

    def _window_weights(self, n_windows: int) -> np.ndarray:
        """(n_windows,) float64 sampling weight per window: the window's
        episode task looked up in `task_weights` (missing tasks fall back
        to the ``"*"`` wildcard weight, default 0 = excluded). Windows are
        laid out episode-by-episode in `cache.index`, so the first
        `n_windows` entries are an episode prefix and one np.repeat
        covers them."""
        default = self.task_weights.get("*", 0.0)
        ep_weights, ep_steps, covered = [], [], 0
        for entry in self.cache.episodes:
            if covered >= n_windows:
                break
            steps = min(int(entry["steps"]), n_windows - covered)
            task = entry.get("task") or UNKNOWN_TASK
            ep_weights.append(self.task_weights.get(task, default))
            ep_steps.append(steps)
            covered += steps
        return np.repeat(
            np.asarray(ep_weights, np.float64), np.asarray(ep_steps, np.int64)
        )

    def _materialize_next_epoch_locked_unsafe(self) -> None:
        """Append the next epoch's snapshot; caller holds `_order_lock`
        (or is the constructor). Refresh happens HERE — at the boundary,
        exactly once per epoch, under the lock — so the whole epoch is
        drawn from one corpus snapshot."""
        e = len(self._epochs)
        with obs_trace.span("feeder/epoch", epoch=e):
            if e > 0 and self.refresh_at_epoch:
                try:
                    self.cache.refresh()
                except Exception:  # noqa: BLE001 - keep feeding the old view
                    pass
            n_windows = len(self.cache.index)
            order = self._compute_order(e, n_windows)
        first = (
            0
            if e == 0
            else self._firsts[-1] + self._epochs[-1]["batches"]
        )
        self._epochs.append(
            {
                "first": first,
                # Batch counts are GLOBAL-batch counts: identical on every
                # host by construction, so multi-process epochs end in
                # lockstep (a per-host count could differ when the corpus
                # is not process-divisible — a collective deadlock).
                "batches": len(order) // self.global_batch_size,
                "order": order,
                "windows": n_windows,
            }
        )
        self._firsts.append(first)
        # Workers straddle at most a couple of epochs (bounded by queue
        # depth); drop older order arrays to bound memory — they are
        # recomputable from the pinned window count if ever needed.
        for old in self._epochs[: max(0, e - 2)]:
            old["order"] = None

    def _locate(self, ticket: int) -> Tuple[int, int]:
        """Global ticket -> (epoch, batch-in-epoch), materializing epoch
        snapshots (and boundary refreshes) as the schedule reaches them."""
        with self._order_lock:
            while (
                ticket
                >= self._firsts[-1] + self._epochs[-1]["batches"]
            ):
                self._materialize_next_epoch_locked_unsafe()
            e = bisect.bisect_right(self._firsts, ticket) - 1
            return e, ticket - self._firsts[e]

    def _order_for(self, epoch: int) -> np.ndarray:
        with self._order_lock:
            while len(self._epochs) <= epoch:
                self._materialize_next_epoch_locked_unsafe()
            entry = self._epochs[epoch]
            if entry["order"] is None:
                entry["order"] = self._compute_order(
                    epoch, entry["windows"]
                )
            return entry["order"]

    def _epoch_order(self, epoch: int) -> np.ndarray:
        """This process's window order for `epoch` (thread-count-free)."""
        return self.host_order(epoch)

    def _past_end(self, ticket: int) -> bool:
        if self.num_epochs is None:
            return False
        if self.total_batches is not None:
            return ticket >= self.total_batches
        epoch, _ = self._locate(ticket)
        return epoch >= self.num_epochs

    def _batch_rng(self, epoch: int, b: int) -> np.random.Generator:
        # Philox keyed directly on (seed, epoch, batch-in-epoch):
        # counter-based, so construction is ~10us vs ~500us for
        # default_rng's SeedSequence entropy pooling — this runs once per
        # batch on the hot path. Keying on the epoch-local coordinates
        # (not the flat ticket) makes each epoch's draws independent of
        # how many batches earlier epochs had — the property that lets a
        # flywheel feeder that grew mid-run match one built after the
        # append. The 0x5EED word keeps the stream disjoint from the
        # shuffle rng.
        key = (self.seed & 0xFFFFFFFFFFFFFFFF) ^ (0x5EED << 48)
        counter = (np.uint64(epoch) << np.uint64(32)) | np.uint64(b)
        return np.random.Generator(
            np.random.Philox(key=np.array([key, counter], np.uint64))
        )

    # ------------------------------------------------------------ workers

    def _assemble(self, ticket: int) -> Dict:
        epoch, b = self._locate(ticket)
        order = self._order_for(epoch)
        indices = self._host_indices(order, b)
        rng = self._batch_rng(epoch, b)
        n, w = len(indices), self.cache.window
        h, wd = self.cache.height, self.cache.width
        images = np.empty((n, w, h, wd, 3), np.uint8)
        embeds = np.empty((n, w, self._embed_dim), np.float32)
        terms = np.empty((n, w), np.int32)
        actions = np.empty((n, w, self._action_dim), np.float32)
        offsets = None
        if self.process_count > 1:
            # Multi-host crop parity: the crop rng is keyed on the GLOBAL
            # (epoch, batch) coordinates, so every host must consume it
            # identically — draw the full global batch's offsets and keep
            # this host's rows. One extra (global_batch·window, 2) integer
            # draw per batch; the frame gather stays per-host-sized.
            all_offsets = self.cache.draw_packed_offsets(
                rng, self.global_batch_size * w
            )
            lo = self.process_index * self.batch_size * w
            offsets = all_offsets[lo : lo + n * w]
        self.cache.fill_batch(
            indices, rng, images, embeds, terms, actions, offsets=offsets
        )
        observations = {
            "image": images,
            "natural_language_embedding": embeds,
        }
        if self.emit_task_ids:
            # (batch,) int32 ids into `health_task_names`; tasks unseen at
            # construction (post-append workloads) ride the OTHER_TASK
            # bucket so the step's one-hot layout never shifts.
            other = self._task_index.get(OTHER_TASK, len(self._task_index))
            tid = np.empty((n,), np.int32)
            for j, idx in enumerate(indices):
                entry = self.cache.episodes[self.cache.index[int(idx)][0]]
                tid[j] = self._task_index.get(
                    entry.get("task") or UNKNOWN_TASK, other
                )
            observations[TASK_ID_KEY] = tid
        if self.cache._clip_tokenizer is not None:
            tokens = np.stack(
                [
                    self.cache._episode_clip_tokens(self.cache.index[int(i)][0])
                    for i in indices
                ]
            )
            observations["instruction_tokenized_clip"] = np.tile(
                tokens[:, None, :], (1, w, 1)
            )
        return {
            "observations": observations,
            "actions": {"terminate_episode": terms, "action": actions},
        }

    def _worker(self, k: int) -> None:
        ticket = k
        q = self._queues[k]
        try:
            while not self._stop.is_set():
                if self._past_end(ticket):
                    return
                # resilience: deterministic fault sites (one global read
                # when no plan is installed). feeder_hang dies silently —
                # the simulated deadlock the consumer-side stall timeout
                # exists to diagnose; feeder_kill exercises the loud path.
                plan = faults.active()
                if plan is not None:
                    if plan.should_fire("feeder_hang", index=ticket):
                        return
                    if plan.should_fire("feeder_kill", index=ticket):
                        raise RuntimeError(
                            f"injected fault [feeder_kill]: worker {k} "
                            f"at ticket {ticket}"
                        )
                # obs: the span puts this worker's assembly on its own line
                # of a profile (`rt1/feeder/assemble`) and in the host ring;
                # the ticket is the batch index the consumer asks for.
                t0 = time.perf_counter()
                with obs_trace.span("feeder/assemble", ticket=ticket):
                    batch = self._assemble(ticket)
                self._assembly_s[k] += time.perf_counter() - t0
                self._assembled[k] += 1
                try:
                    q.put_nowait(batch)
                except queue.Full:
                    # The feeder is ahead. Bounded put that stays responsive
                    # to close(): a plain q.put would deadlock a full queue
                    # against a consumer gone.
                    with obs_trace.span("feeder/put_wait", ticket=ticket):
                        while not self._stop.is_set():
                            try:
                                q.put(batch, timeout=0.1)
                                break
                            except queue.Full:
                                continue
                if obs_trace.enabled():
                    obs_trace.counter(
                        "feeder_queue_depth",
                        sum(qq.qsize() for qq in self._queues),
                    )
                ticket += self.num_threads
        except BaseException as e:  # noqa: BLE001 - re-raised in __next__
            # A dying worker must not strand the consumer in q.get():
            # stash the error, flip the stop flag, and let __next__
            # re-raise it on the train loop's thread (a truncated
            # frames.bin, a bad clip tokenizer — all surface loudly
            # instead of hanging training).
            self._error = e
            self._stop.set()

    # ---------------------------------------------------------- observability

    def stats(self) -> Dict[str, float]:
        """Flat numeric gauges for the obs layer (train-side Prometheus
        listener, flight-recorder step records): ready-queue fill and
        per-worker assembly counters. Lock-free reads of GIL-atomic
        counters — safe to call from any thread at any rate."""
        depth = sum(q.qsize() for q in self._queues)
        out = {
            "queue_depth": depth,
            "queue_capacity": self.num_threads * self.depth,
            "next_ticket": self._next_ticket,
            "workers_alive": sum(t.is_alive() for t in self._threads),
            "corpus_windows": len(self.cache.index),
            "epochs_started": len(self._epochs),
        }
        for k in range(self.num_threads):
            n = self._assembled[k]
            out[f"assembled_w{k}"] = n
            out[f"assembly_ms_mean_w{k}"] = (
                self._assembly_s[k] / n * 1e3 if n else 0.0
            )
        return out

    def flywheel_stats(self) -> Dict[str, float]:
        """Corpus-growth gauges for the train loop's `flywheel/*` scalars
        and the `rt1_flywheel_*` Prometheus families: shard count,
        freshness epoch, corpus size, appended-episode count, and how
        stale the feeder's view of the manifest is. Lock-free reads."""
        c = self.cache
        now = time.time()
        return {
            "shards": float(getattr(c, "num_shards", 1)),
            "freshness_epoch": float(getattr(c, "freshness_epoch", 0)),
            "corpus_windows": float(len(c.index)),
            "corpus_steps": float(getattr(c, "total_steps", 0)),
            "corpus_episodes": float(len(c.episodes)),
            "corpus_tasks": float(len(set(c.tasks))),
            "appended_episodes": float(getattr(c, "appended_episodes", 0)),
            "refreshes": float(getattr(c, "refreshes", 0)),
            "staleness_s": max(
                0.0, now - getattr(c, "last_refresh_unix", now)
            ),
            "epochs_started": float(len(self._epochs)),
        }

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "SampleAheadFeeder":
        if not self._started:
            self._started = True
            for t in self._threads:
                t.start()
        return self

    def close(self) -> None:
        """Stop workers and join them; the iterator is exhausted after."""
        self._stop.set()
        for q in self._queues:
            # Drain so a worker blocked in put() sees the stop event.
            try:
                while True:
                    q.get_nowait()
            except queue.Empty:
                pass
        for t in self._threads:
            if t.is_alive():
                t.join(timeout=5.0)

    def __enter__(self) -> "SampleAheadFeeder":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass

    # ------------------------------------------------------------ iteration

    def __iter__(self) -> Iterator[Dict]:
        return self

    def __next__(self) -> Dict:
        if not self._started:
            self.start()
        if self._stop.is_set():
            self._raise_or_stop()
        t = self._next_ticket
        if self._past_end(t):
            raise StopIteration
        # obs: where the loop waits for its input. `ready` is how far ahead
        # the workers were when the loop asked: batches already assembled,
        # over all queues (capacity num_threads x depth).
        ready = sum(qq.qsize() for qq in self._queues)
        with obs_trace.span("feeder/next", ticket=t, ready=ready):
            batch = self._take(t)
        self._next_ticket = t + 1
        return batch

    def _take(self, t: int) -> Dict:
        """Block until ticket `t`'s batch is in its queue."""
        q = self._queues[t % self.num_threads]
        waited = 0.0
        while True:
            try:
                return q.get(timeout=0.1)
            except queue.Empty:
                if self._stop.is_set():
                    self._raise_or_stop()
                waited += 0.1
                if (
                    self.stall_timeout_s is not None
                    and waited >= self.stall_timeout_s
                ):
                    raise self._stalled_error(t, waited)
                if not any(th.is_alive() for th in self._threads) and q.empty():
                    # Every worker died without raising (so no stashed
                    # error) and nothing is queued: no batch can ever
                    # arrive. Diagnose immediately instead of waiting out
                    # the timeout — or forever, when none is configured.
                    raise self._stalled_error(t, waited)

    def _stalled_error(self, ticket: int, waited: float) -> "FeederStalledError":
        alive = [th.name for th in self._threads if th.is_alive()]
        dead = [th.name for th in self._threads if not th.is_alive()]
        depths = [qq.qsize() for qq in self._queues]
        return FeederStalledError(
            f"feeder stalled: waited {waited:.1f}s for ticket {ticket} "
            f"(queue {ticket % self.num_threads}). Worker threads alive: "
            f"{alive or 'NONE'}; dead: {dead or 'none'}; queue depths: "
            f"{depths} (capacity {self.depth} each). A dead worker with no "
            f"stashed error means it deadlocked or was killed without "
            f"unwinding — check the flight-recorder dump and the host "
            f"trace for its last feeder/assemble span."
        )

    def _raise_or_stop(self) -> None:
        """Re-raise a worker's stashed error on the consumer thread, or end
        the stream cleanly when the stop came from close()."""
        if self._error is not None:
            raise RuntimeError(
                "sample-ahead feeder worker failed"
            ) from self._error
        raise StopIteration

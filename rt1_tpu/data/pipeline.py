"""Sliding-window dataset + loaders.

Reproduces the reference's sample distribution exactly (SURVEY.md §7.4/§7.7,
`load_np_dataset.py:49-116`): each episode is front-padded by repeating the first
step `window-1` times (padding copies get ``is_first=False``), every length-
`window` window is one sample, each frame is independently random-cropped at
`crop_factor` and bilinear-resized to (height, width), labels are
``terminate_episode`` (is_terminal as int) and ``action``.

Improvements over the reference, same distribution:
* episodes are read once into an LRU cache of stacked arrays, not re-unpickled
  per `__getitem__` (the reference's I/O hot spot, `load_np_dataset.py:79-83`);
* loading/augment runs under tf.data with parallel map + prefetch instead of 15
  fork-per-batch DataLoader workers (`distribute_train.py:200`);
* per-host sharding for multi-host SPMD feeding (each host loads 1/N of the
  windows, `jax.process_index` style), then `device_feeder` lays batches out on
  the mesh as sharded `jax.Array`s.
"""

from __future__ import annotations

import collections
import functools
from typing import Callable, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from rt1_tpu.data import episodes as ep_lib
from rt1_tpu.obs import startup
from rt1_tpu.obs import trace as obs_trace


class WindowedEpisodeDataset:
    """Index of all (episode, start) windows over a set of episode files."""

    def __init__(
        self,
        paths: Sequence[str],
        window: int = 6,
        crop_factor: Optional[float] = 0.95,
        height: int = 256,
        width: int = 456,
        reader: Callable[[str], ep_lib.Episode] = ep_lib.load_episode,
        cache_episodes: int = 64,
        image_dtype: str = "uint8",
        clip_tokenizer=None,
    ):
        if image_dtype not in ("uint8", "float32"):
            raise ValueError(f"image_dtype must be uint8|float32, got {image_dtype}")
        self.paths = list(paths)
        self.window = window
        self.crop_factor = crop_factor
        self.height = height
        self.width = width
        # uint8 (default) ships 4x fewer H2D bytes than float32 — the model
        # converts on device (`ops/image.py::convert_dtype`), and the
        # reference stores/augments uint8 rgb anyway (VERDICT r1 weak #2).
        self.image_dtype = image_dtype
        # Optional ClipBPETokenizer: windows gain an
        # "instruction_tokenized_clip" (window, context) observation, fed to
        # LAVA's in-graph CLIP text tower (reference tokenizes in the input
        # pipeline, `input_pipeline_rlds.py` + clip_tokenizer.py).
        self._clip_tokenizer = clip_tokenizer
        self._clip_token_cache: Dict[int, np.ndarray] = {}
        self._reader = reader
        self._cache: "collections.OrderedDict[int, ep_lib.Episode]" = collections.OrderedDict()
        self._cache_size = cache_episodes
        # tf.data's parallel map calls get_window from multiple threads; the
        # LRU mutations must be atomic.
        import threading

        self._cache_lock = threading.Lock()
        # Index construction mirrors `_create_samples` (load_np_dataset.py:65-74):
        # padded length T + window - 1 → exactly T windows per episode.
        self.index: List[Tuple[int, int]] = []
        for i, p in enumerate(self.paths):
            t = self._episode_len(i)
            self.index.extend((i, s) for s in range(t))

    def _episode_len(self, i: int) -> int:
        # Read only the length, not the payload: npz members are lazy, so
        # loading one small member avoids pulling the rgb arrays of every
        # episode at startup. Falls back to a full read for .npy episodes.
        path = self.paths[i]
        if path.endswith(".npz"):
            with np.load(path) as z:
                return int(z["is_first"].shape[0])
        return self._episode(i)["rgb"].shape[0]

    def _episode(self, i: int) -> ep_lib.Episode:
        with self._cache_lock:
            ep = self._cache.get(i)
            if ep is not None:
                self._cache.move_to_end(i)
                return ep
        ep = self._reader(self.paths[i])
        with self._cache_lock:
            self._cache[i] = ep
            if len(self._cache) > self._cache_size:
                self._cache.popitem(last=False)
        return ep

    def __len__(self) -> int:
        return len(self.index)

    # ------------------------------------------------------------------ samples

    def _padded_step(self, ep: ep_lib.Episode, j: int, key: str):
        """Step j of the padded episode: j < window-1 reads the first step."""
        pad = self.window - 1
        src = 0 if j < pad else j - pad
        return ep[key][src]

    def get_window(
        self, idx: int, rng: Optional[np.random.Generator] = None
    ) -> Dict[str, Dict[str, np.ndarray]]:
        ep_i, start = self.index[idx]
        ep = self._episode(ep_i)
        rng = rng or np.random.default_rng()

        frames, embeds, actions, terms = [], [], [], []
        boxes = []
        for j in range(start, start + self.window):
            rgb = self._padded_step(ep, j, "rgb")
            frames.append(rgb)
            boxes.append(
                _crop_box(rgb.shape[0], rgb.shape[1], self.crop_factor, rng)
            )
            embeds.append(self._padded_step(ep, j, "instruction"))
            actions.append(self._padded_step(ep, j, "action"))
            terms.append(np.int32(bool(self._padded_step(ep, j, "is_terminal"))))
        images = self._crop_resize_frames(frames, boxes)

        observations = {
            "image": images,
            "natural_language_embedding": np.stack(embeds).astype(np.float32),
        }
        if self._clip_tokenizer is not None:
            tokens = self._episode_clip_tokens(ep_i)
            observations["instruction_tokenized_clip"] = np.tile(
                tokens, (self.window, 1)
            )
        return {
            "observations": observations,
            "actions": {
                "terminate_episode": np.asarray(terms, np.int32),
                "action": np.stack(actions).astype(np.float32),
            },
        }

    def _crop_resize_frames(self, frames, boxes) -> np.ndarray:
        """(window,) frames + crop boxes -> (window, H, W, 3) in image_dtype."""
        out = crop_resize_frames(frames, boxes, self.height, self.width)
        if self.image_dtype == "float32":
            return out.astype(np.float32) / 255.0
        return out

    def _episode_clip_tokens(self, ep_i: int) -> np.ndarray:
        """(context,) int32 CLIP BPE frame for the episode's instruction."""
        tokens = self._clip_token_cache.get(ep_i)
        if tokens is None:
            ep = self._episode(ep_i)
            if "instruction_text" not in ep:
                raise KeyError(
                    f"{self.paths[ep_i]} has no 'instruction_text' member; "
                    "re-collect with a current rt1_tpu.data.collect to use "
                    "clip_tokenizer"
                )
            text = ep_lib.decode_instruction_text(ep["instruction_text"])
            tokens = self._clip_tokenizer.tokenize_text(text)[0].astype(np.int32)
            self._clip_token_cache[ep_i] = tokens
        return tokens

    # ------------------------------------------------------------------ loaders

    def numpy_batches(
        self,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        num_epochs: Optional[int] = None,
        process_index: int = 0,
        process_count: int = 1,
        drop_remainder: bool = True,
    ) -> Iterator[Dict]:
        """Dependency-free batch iterator (tests, debugging, tiny runs)."""
        rng = np.random.default_rng(seed)
        epoch = 0
        while num_epochs is None or epoch < num_epochs:
            order = np.arange(len(self.index))
            if shuffle:
                rng.shuffle(order)
            order = order[process_index::process_count]
            for i in range(0, len(order) - (batch_size - 1 if drop_remainder else 0), batch_size):
                chunk = order[i : i + batch_size]
                samples = [self.get_window(int(j), rng) for j in chunk]
                yield _stack_tree(samples)
            epoch += 1

    def as_tf_dataset(
        self,
        batch_size: int,
        shuffle: bool = True,
        seed: int = 0,
        num_parallel_calls: int = 16,
        shuffle_buffer: int = 2048,
        process_index: int = 0,
        process_count: int = 1,
        repeat: bool = True,
    ):
        """tf.data pipeline: parallel window assembly + augment, shuffle, batch,
        prefetch. Replaces the reference's DataLoader(num_workers=15) path."""
        import tensorflow as tf

        tf.config.set_visible_devices([], "GPU")

        n = len(self.index)
        ds = tf.data.Dataset.range(n)
        ds = ds.shard(process_count, process_index)
        if repeat:
            ds = ds.repeat()
        if shuffle:
            ds = ds.shuffle(min(n, shuffle_buffer), seed=seed, reshuffle_each_iteration=True)

        with_tokens = self._clip_tokenizer is not None

        def _load(idx):
            def _py(i):
                s = self.get_window(int(i))
                out = [
                    s["observations"]["image"],
                    s["observations"]["natural_language_embedding"],
                    s["actions"]["terminate_episode"],
                    s["actions"]["action"],
                ]
                if with_tokens:
                    out.append(s["observations"]["instruction_tokenized_clip"])
                return tuple(out)

            img_tf_dtype = (
                tf.uint8 if self.image_dtype == "uint8" else tf.float32
            )
            dtypes = [img_tf_dtype, tf.float32, tf.int32, tf.float32]
            if with_tokens:
                dtypes.append(tf.int32)
            tensors = tf.numpy_function(_py, [idx], dtypes)
            img, emb, term, act = tensors[:4]
            w = self.window
            img.set_shape((w, self.height, self.width, 3))
            emb.set_shape((w, None))
            term.set_shape((w,))
            act.set_shape((w, None))
            observations = {
                "image": img, "natural_language_embedding": emb,
            }
            if with_tokens:
                tokens = tensors[4]
                tokens.set_shape((w, self._clip_tokenizer.context_length))
                observations["instruction_tokenized_clip"] = tokens
            return {
                "observations": observations,
                "actions": {"terminate_episode": term, "action": act},
            }

        ds = ds.map(_load, num_parallel_calls=num_parallel_calls, deterministic=False)
        ds = ds.batch(batch_size, drop_remainder=True)
        return ds.prefetch(tf.data.AUTOTUNE)


def _crop_box(
    h: int, w: int, crop_factor: Optional[float], rng: np.random.Generator
) -> Tuple[int, int, int, int]:
    """(top, left, crop_h, crop_w) — `DecodeAndRandomResizedCrop` parity
    (load_np_dataset.py:8-39): a `crop_factor` box at a uniform random
    offset (the full frame when crop_factor is None)."""
    if crop_factor is None:
        return 0, 0, h, w
    ch, cw = int(h * crop_factor), int(w * crop_factor)
    top = int(rng.integers(0, h - ch + 1))
    left = int(rng.integers(0, w - cw + 1))
    return top, left, ch, cw


def crop_resize_frames(frames, boxes, height: int, width: int) -> np.ndarray:
    """Crop + bilinear-resize a batch of frames -> (n, height, width, 3).

    The one augmentation backend every loader shares (tf.data window
    assembly, the packed-cache packer, and the sample-ahead feeder's general
    path all call this), so their pixel semantics agree by construction:
    cv2 (SIMD bilinear, GIL-released) when importable; otherwise the native
    C++ sampler (native/window_sampler.cc) keeps the pipeline
    dependency-free. Both follow cv2.INTER_LINEAR half-pixel-center
    semantics, so the sample distribution matches to +/-1 LSB.
    Set RT1_TPU_FORCE_NATIVE_SAMPLER=1 to force the native path.
    """
    import os

    use_native = bool(os.environ.get("RT1_TPU_FORCE_NATIVE_SAMPLER"))
    if use_native and frames[0].dtype != np.uint8:
        raise RuntimeError(
            "RT1_TPU_FORCE_NATIVE_SAMPLER: the native sampler only "
            f"handles uint8 frames, got {frames[0].dtype}"
        )
    if not use_native:
        try:
            import cv2  # noqa: F401
        except ImportError:
            if frames[0].dtype != np.uint8:
                raise RuntimeError(
                    "cv2 is unavailable and the native sampler only "
                    f"handles uint8 frames, got {frames[0].dtype}; "
                    "install opencv-python"
                ) from None
            use_native = True
    if use_native:
        from rt1_tpu.data import native

        if not native.sampler_available():
            raise RuntimeError(
                "Neither cv2 nor the native window sampler is available "
                "(build native/ with `make` or install opencv-python)"
            )
        # Threads=1: tf.data's parallel map / feeder workers already fan out
        # across windows; the call releases the GIL so those threads
        # genuinely run in parallel.
        return native.crop_resize_batch(frames, boxes, height, width, threads=1)
    return np.stack(
        [_cv2_crop_resize(rgb, box, height, width) for rgb, box in zip(frames, boxes)]
    )


def _cv2_crop_resize(rgb: np.ndarray, box, height: int, width: int) -> np.ndarray:
    """Single-frame crop + cv2.INTER_LINEAR resize (`DecodeAndRandomResizedCrop`
    parity, load_np_dataset.py:8-39); dtype preserved (uint8 in, uint8 out)."""
    import cv2

    top, left, ch, cw = box
    crop = rgb[top : top + ch, left : left + cw]
    return cv2.resize(crop, (width, height), interpolation=cv2.INTER_LINEAR)


def _stack_tree(samples: List[Dict]) -> Dict:
    """collate_fn parity (load_np_dataset.py:131-146): stack nested dicts."""
    out = {}
    for k, v in samples[0].items():
        if isinstance(v, dict):
            out[k] = {kk: np.stack([s[k][kk] for s in samples]) for kk in v}
        else:
            out[k] = np.stack([s[k] for s in samples])
    return out


def put_global(batch, sharding):
    """Lay one host batch out per `sharding` — multi-process aware.

    Single process: one async `jax.device_put` (the fast path, unchanged).
    Multi-process: each host holds only ITS rows of the global batch (the
    feeder's per-host block slice), so the global array is assembled with
    `jax.make_array_from_process_local_data` — every leaf's global leading
    dim is local_rows × process_count, matching a batch dim sharded over
    the host-major (data, fsdp) mesh axes where each host's devices own
    exactly its contiguous row block. No cross-host data moves: the
    "assembly" is metadata + local H2D.
    """
    import jax

    if jax.process_count() == 1:
        return jax.device_put(batch, sharding)
    nproc = jax.process_count()

    def put(x):
        x = np.asarray(x)
        global_shape = (x.shape[0] * nproc,) + x.shape[1:]
        return jax.make_array_from_process_local_data(
            sharding, x, global_shape
        )

    return jax.tree.map(put, batch)


def prefetch_to_device(iterator, sharding, depth: int = 2) -> Iterator:
    """Double-buffered H2D: keep `depth` batches resident on device.

    `jax.device_put` is asynchronous, so enqueueing batch N+1 before the
    consumer blocks on batch N overlaps its host->device copy with the
    device compute of step N (VERDICT r1 weak #3 — the single-buffered loop
    serialized H2D into the step). Equivalent of
    `flax.jax_utils.prefetch_to_device`, but laying batches out with an
    explicit (mesh) sharding instead of pmap's leading device axis. On
    multi-process runs each host feeds its shard of the global batch
    (`put_global`).
    """
    import jax

    queue = collections.deque()
    for ticket, batch in enumerate(iterator):
        # obs: the copy's host side (enqueue; the transfer itself is
        # asynchronous). The n-th put carries the feeder's n-th batch.
        nbytes = sum(getattr(x, "nbytes", 0) for x in jax.tree.leaves(batch))
        with obs_trace.span("h2d/put", ticket=ticket, bytes=nbytes):
            queue.append(put_global(batch, sharding))
        if len(queue) >= max(depth, 1):
            yield queue.popleft()
    while queue:
        yield queue.popleft()


def to_obs_actions(batch):
    """Loader batch dict -> the (observations, actions) tuple steps consume.

    tf.data yields dicts whose leaves are EagerTensors; numpy loaders yield
    dicts of ndarrays. Normalize leaves, not the container.
    """
    import jax

    b = jax.tree.map(
        lambda x: x.numpy() if hasattr(x, "numpy") else np.asarray(x),
        batch,
    )
    return b["observations"], b["actions"]


def device_feeder(iterator, batch_sharding, depth: int = 1) -> Iterator:
    """Lay host batches out on the mesh as (observations, actions) tuples of
    sharded jax.Arrays. On a multi-process run each host's iterator yields
    its block of the global batch and `put_global` assembles the global
    `jax.Array` via `jax.make_array_from_process_local_data`; single-process
    keeps the plain async `device_put`. `depth=2` double-buffers (see
    `prefetch_to_device`). The first batch (the host feed's first pulls and
    the first copies to the device) is the start-up log's `first_batch`."""
    batches = prefetch_to_device(
        map(to_obs_actions, iterator), batch_sharding, depth=depth
    )
    with startup.phase("first_batch"):
        first = next(batches, None)
    if first is not None:
        yield first
        yield from batches

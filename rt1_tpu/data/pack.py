"""Packed mmap frame cache: decode-once episodes at augmentation headroom.

The tf.data path pays the full augmentation bill per *sample*: every window
re-reads decoded 256x456-class frames and random-resize-crops each one
(~42 ms/batch on the single-core bench host against an 8 ms device step —
the 78% input stall in docs/performance.md). The fix is to move every
per-pixel operation that does not depend on the random crop offset to an
offline pass:

* `pack_episodes` decodes each episode ONCE and stores its frames resized to
  the *packed* resolution — the smallest frame from which every random crop
  of the training distribution can be cut as a pure slice — appended into a
  corpus-wide uint8 frames file (mmap-able, no headers), with the small
  step-aligned members (action/instruction/flags) concatenated into raw
  `meta_<member>.npy` files and a JSON manifest carrying geometry,
  per-episode frame offsets, and source fingerprints. One file per array,
  not per episode: a 7800-episode corpus costs a handful of open fds and
  zero per-window parsing (per-episode `.npz` sidecars measured 3.2 ms/load
  — reintroducing the exact per-sample I/O tax this cache removes).
* `PackedEpisodeCache` maps the frames files once and assembles a training
  window as h x w uint8 slices out of the mmaps — no decode, no resize, no
  float math, no handle churn.

Sharded pack format v2 (the data flywheel, docs/data.md): the corpus is a
list of **shards** — `frames.bin` plus zero or more `frames_<k>.bin` — each
with its own meta sidecars and fingerprints, listed in the manifest with a
monotonically increasing `freshness_epoch`. `append_shard` adds newly
collected/captured episodes as a NEW shard and atomically rewrites the
manifest (shard files land fully before the manifest rename, so readers
see either the old corpus or the whole new shard — never a torn append),
and `PackedEpisodeCache.refresh()` picks new shards up in a live process.
Pre-shard manifests (format_version 2, one `frames.bin`) load unchanged as
a single-shard corpus — same files, same bytes, same samples.

Crop-distribution parity (tested in tests/test_packed_cache.py): the random
box is still drawn by `pipeline._crop_box` in SOURCE-frame coordinates —
bit-identical draws to the tf.data path for the same rng — then mapped into
packed coordinates, where it is exactly (height, width) by construction:

    source (H0, W0) -- crop (ch0, cw0) = (int(H0*cf), int(W0*cf)) -> (h, w)
    packed (ph, pw) = (round(H0*h/ch0), round(W0*w/cw0))

so a ch0-tall source crop spans h packed rows, and the gather is
`frames[t, top_p:top_p+h, left_p:left_p+w]`. The only pixel-semantics
difference vs the tf.data path is resize-once-then-slice instead of
slice-then-resize (the same interpolation family, applied once offline).
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from rt1_tpu.data import episodes as ep_lib
from rt1_tpu.data.pipeline import _crop_box, crop_resize_frames
from rt1_tpu.obs import startup
from rt1_tpu.resilience import faults

MANIFEST_NAME = "pack_manifest.json"
FRAMES_NAME = "frames.bin"
# Sharded manifests. Format 2 (one frames.bin, no shard list) is the
# pre-flywheel layout; it loads as a single-shard corpus with no byte
# rewritten on disk.
FORMAT_VERSION = 3
LEGACY_FORMAT_VERSION = 2
# Step-aligned members consolidated into meta_<name><suffix>.npy
# (concatenated over episodes along axis 0, raw .npy so the cache opens
# them mmap_mode="r").
META_MEMBERS = ("action", "instruction", "is_first", "is_terminal")
TEXT_MEMBER = "instruction_text"
TEXT_NAME = "meta_instruction_text.npy"
#: Task id reported for episodes whose manifest entry carries no `task`
#: meta (legacy format-2 packs, pre-task corpora). THE definition of the
#: slug — pack.py is numpy+stdlib only, so every consumer (collect's
#: stamping path, the feeder's mixture weights, the eval matrix) imports
#: this one spelling.
UNKNOWN_TASK = "unknown"


def shard_suffix(k: int) -> str:
    """File-name suffix of shard `k`: shard 0 keeps the pre-shard names
    (`frames.bin`, `meta_action.npy`) so a fresh pack stays byte-identical
    to the format-2 layout; appended shards are `frames_00001.bin`, ..."""
    return "" if k == 0 else f"_{k:05d}"


def shard_frames_name(suffix: str) -> str:
    return f"frames{suffix}.bin" if suffix else FRAMES_NAME


def shard_meta_name(member: str, suffix: str) -> str:
    return f"meta_{member}{suffix}.npy"


# --------------------------------------------------------------------- geometry


def crop_size(dim: int, crop_factor: Optional[float]) -> int:
    """Source-coordinate crop size along one dim (`_crop_box` parity)."""
    return dim if crop_factor is None else int(dim * crop_factor)


def packed_dims(
    src_h: int,
    src_w: int,
    height: int,
    width: int,
    crop_factor: Optional[float],
) -> Tuple[int, int]:
    """Packed (ph, pw): a `crop_factor` source crop spans exactly (h, w).

    crop_factor None degenerates to (height, width) — the gather is then the
    whole packed frame.
    """
    ch0 = crop_size(src_h, crop_factor)
    cw0 = crop_size(src_w, crop_factor)
    ph = int(round(src_h * height / ch0))
    pw = int(round(src_w * width / cw0))
    # round() cannot undershoot the slice size by construction (ch0 <= src_h
    # implies src_h*h/ch0 >= h) except through the 0.5-rounding edge; clamp
    # so the (h, w) gather slice always fits.
    return max(ph, height), max(pw, width)


def map_box_to_packed(
    box: Tuple[int, int, int, int],
    src_h: int,
    src_w: int,
    ph: int,
    pw: int,
    height: int,
    width: int,
) -> Tuple[int, int]:
    """Source-coordinate crop box -> (top, left) of its (h, w) packed slice."""
    top, left, ch, cw = box
    top_p = int(round(top * height / max(ch, 1)))
    left_p = int(round(left * width / max(cw, 1)))
    return min(max(top_p, 0), ph - height), min(max(left_p, 0), pw - width)


# --------------------------------------------------------------------- packer


def _fingerprint(path: str) -> Dict[str, object]:
    st = os.stat(path)
    return {"name": os.path.basename(path), "bytes": st.st_size,
            "mtime": round(st.st_mtime, 3)}


def _fingerprint_key(fp: Dict[str, object]) -> Tuple:
    return (fp.get("name"), fp.get("bytes"), fp.get("mtime"))


def _resize_episode_frames(rgb: np.ndarray, ph: int, pw: int) -> np.ndarray:
    """(T, H0, W0, 3) uint8 -> (T, ph, pw, 3) uint8, full-frame resize."""
    t, h0, w0, _ = rgb.shape
    if (h0, w0) == (ph, pw):
        return np.ascontiguousarray(rgb)
    boxes = np.tile(np.array([[0, 0, h0, w0]], np.int32), (t, 1))
    return crop_resize_frames(list(rgb), boxes, ph, pw)


def _write_shard(
    out_dir: str,
    paths: Sequence[str],
    suffix: str,
    src_h: Optional[int],
    src_w: Optional[int],
    ph: Optional[int],
    pw: Optional[int],
    height: int,
    width: int,
    crop_factor: Optional[float],
    frame_base: int,
    shard_index: int,
) -> Tuple[List[Dict[str, object]], Dict[str, object], int, int, int]:
    """Decode `paths` once into one shard's frames + meta files.

    Returns (episode_entries, shard_entry, steps, src_h, src_w). Frame
    offsets in the episode entries are GLOBAL (frame_base + local); text
    offsets are LOCAL to this shard's text file. `src_h`/`src_w` None means
    "infer from the first episode" (fresh pack); a fixed value enforces the
    corpus-wide geometry on append.
    """
    os.makedirs(out_dir, exist_ok=True)
    episodes: List[Dict[str, object]] = []
    meta_parts: Dict[str, List[np.ndarray]] = {k: [] for k in META_MEMBERS}
    text_parts: List[np.ndarray] = []
    have_text = True
    frame_offset = frame_base
    text_offset = 0
    frames_name = shard_frames_name(suffix)
    frames_tmp = os.path.join(out_dir, frames_name + ".tmp")
    with open(frames_tmp, "wb") as frames_f:
        for path in paths:
            ep = ep_lib.load_episode(path)
            ep_lib.validate_episode(ep)
            rgb = np.asarray(ep["rgb"], np.uint8)
            t, h0, w0, _ = rgb.shape
            if src_h is None:
                src_h, src_w = h0, w0
                ph, pw = packed_dims(src_h, src_w, height, width, crop_factor)
            elif (h0, w0) != (src_h, src_w):
                raise ValueError(
                    f"{path}: source frames {h0}x{w0} differ from corpus "
                    f"{src_h}x{src_w}; the packed geometry is corpus-wide"
                )
            _resize_episode_frames(rgb, ph, pw).tofile(frames_f)
            for k in META_MEMBERS:
                meta_parts[k].append(np.asarray(ep[k]))
            entry = {
                "steps": int(t),
                "frame_offset": int(frame_offset),
                "shard": int(shard_index),
                "source": _fingerprint(path),
            }
            # The per-episode task id (reward family / capture workload tag)
            # rides the manifest so task-mixture sampling can weight windows
            # without reopening any episode file.
            if "task" in ep:
                entry["task"] = ep_lib.decode_instruction_text(ep["task"])
            if have_text and "instruction_text" in ep:
                text = np.asarray(ep["instruction_text"], np.uint8)
                text_parts.append(text)
                entry["text_offset"] = int(text_offset)
                entry["text_len"] = int(text.shape[0])
                text_offset += int(text.shape[0])
            else:
                # All-or-nothing per shard: a shard with only some
                # instruction_text members packs without any (mirrors the tf
                # path, which KeyErrors per missing episode at clip-token
                # time).
                have_text = False
            episodes.append(entry)
            frame_offset += t
    os.replace(frames_tmp, os.path.join(out_dir, frames_name))
    for k in META_MEMBERS:
        _atomic_save_npy(
            os.path.join(out_dir, shard_meta_name(k, suffix)),
            np.concatenate(meta_parts[k], axis=0),
        )
    has_text = bool(have_text and text_parts)
    if has_text:
        _atomic_save_npy(
            os.path.join(out_dir, shard_meta_name(TEXT_MEMBER, suffix)),
            np.concatenate(text_parts),
        )
    else:
        for e in episodes:
            e.pop("text_offset", None)
            e.pop("text_len", None)
    steps = frame_offset - frame_base
    shard_entry = {
        "suffix": suffix,
        "frames": frames_name,
        "steps": int(steps),
        "frame_base": int(frame_base),
        "episodes": len(episodes),
        "bytes": int(steps) * int(ph) * int(pw) * 3,
        "has_text": has_text,
    }
    return episodes, shard_entry, steps, int(src_h), int(src_w)


def _write_manifest(out_dir: str, manifest: Dict[str, object]) -> None:
    tmp = os.path.join(out_dir, MANIFEST_NAME + ".tmp")
    with open(tmp, "w") as f:
        json.dump(manifest, f, indent=2, sort_keys=True)
    os.replace(tmp, os.path.join(out_dir, MANIFEST_NAME))


def pack_episodes(
    paths: Sequence[str],
    out_dir: str,
    height: int,
    width: int,
    crop_factor: Optional[float],
    force: bool = False,
) -> Dict[str, object]:
    """Decode each episode once, write packed frames + sidecars + manifest.

    Returns the manifest dict. Skips work when `pack_is_fresh` already holds
    (unless `force`). Source frames must share one (H0, W0) across the
    corpus — the packed geometry is corpus-wide. The result is a one-shard
    sharded manifest whose shard-0 files keep the pre-shard names, so the
    on-disk frame/meta bytes are identical to a format-2 pack.
    """
    paths = sorted(paths)
    if not paths:
        raise ValueError("pack_episodes: no episode paths given")
    if not force and pack_is_fresh(out_dir, paths, height, width, crop_factor):
        return load_manifest(out_dir)

    # Geometry is inferred inside _write_shard from the first episode.
    episodes, shard_entry, steps, src_h, src_w = _write_shard(
        out_dir, paths, shard_suffix(0), None, None, None, None,
        height, width, crop_factor, frame_base=0, shard_index=0,
    )
    ph, pw = packed_dims(src_h, src_w, height, width, crop_factor)
    manifest = {
        "format_version": FORMAT_VERSION,
        "freshness_epoch": 0,
        "source": {"height": src_h, "width": src_w},
        "train": {
            "height": int(height),
            "width": int(width),
            "crop_factor": crop_factor,
        },
        "packed": {"height": int(ph), "width": int(pw)},
        "total_steps": int(steps),
        "has_instruction_text": bool(shard_entry["has_text"]),
        "shards": [shard_entry],
        "episodes": episodes,
    }
    _write_manifest(out_dir, manifest)
    return manifest


def append_shard(
    pack_dir: str, paths: Sequence[str]
) -> Dict[str, object]:
    """Append newly collected episodes to an existing pack as a NEW shard.

    The data-flywheel write path: episodes already present (matched by
    source fingerprint) are skipped, the remainder are decoded once into
    `frames_<k>.bin` + meta sidecars, and the manifest is atomically
    rewritten with the new shard, extended episode list, and a bumped
    `freshness_epoch`. Shard files are fully on disk BEFORE the manifest
    rename, so a crash mid-append (chaos site `pack_append@N`) leaves at
    worst orphaned shard files next to a valid old manifest — readers never
    observe a torn corpus. Returns the (possibly unchanged) manifest.
    """
    manifest = load_manifest(pack_dir)
    known = {
        _fingerprint_key(e.get("source", {}))
        for e in manifest["episodes"]
    }
    new_paths = [
        p for p in sorted(paths)
        if _fingerprint_key(_fingerprint(p)) not in known
    ]
    if not new_paths:
        return manifest
    k = len(manifest["shards"])
    train = manifest["train"]
    episodes, shard_entry, steps, _, _ = _write_shard(
        pack_dir,
        new_paths,
        shard_suffix(k),
        int(manifest["source"]["height"]),
        int(manifest["source"]["width"]),
        int(manifest["packed"]["height"]),
        int(manifest["packed"]["width"]),
        int(train["height"]),
        int(train["width"]),
        train["crop_factor"],
        frame_base=int(manifest["total_steps"]),
        shard_index=k,
    )
    shard_entry["appended"] = True
    # Chaos site: shard files are written, the manifest rename has not
    # happened — the torn-append window readers must be immune to.
    faults.maybe_fail(
        "pack_append",
        index=int(manifest["freshness_epoch"]) + 1,
        what=f"shard {shard_entry['frames']} in {pack_dir}",
    )
    manifest["episodes"] = list(manifest["episodes"]) + episodes
    manifest["shards"] = list(manifest["shards"]) + [shard_entry]
    manifest["total_steps"] = int(manifest["total_steps"]) + int(steps)
    manifest["freshness_epoch"] = int(manifest["freshness_epoch"]) + 1
    manifest["has_instruction_text"] = bool(
        manifest["has_instruction_text"] and shard_entry["has_text"]
    )
    manifest["format_version"] = FORMAT_VERSION
    _write_manifest(pack_dir, manifest)
    return manifest


def _atomic_save_npy(path: str, arr: np.ndarray) -> None:
    tmp = path + ".tmp.npy"  # .npy suffix keeps np.save from appending one
    np.save(tmp, arr)
    os.replace(tmp, path)


# ----------------------------------------------------------------- manifests


def load_manifest(pack_dir: str) -> Dict[str, object]:
    """Read + normalize a pack manifest to the sharded (v3) shape.

    A legacy format-2 manifest (one `frames.bin`, no shard list) is
    presented as a single-shard corpus: `shards` synthesized, every episode
    stamped `shard: 0`, `freshness_epoch` 0. Nothing is rewritten on disk —
    old packs keep loading byte-identically. Raises ValueError for unknown
    versions.
    """
    with open(os.path.join(pack_dir, MANIFEST_NAME)) as f:
        manifest = json.load(f)
    version = manifest.get("format_version")
    if version == FORMAT_VERSION:
        return manifest
    if version != LEGACY_FORMAT_VERSION:
        raise ValueError(
            f"{pack_dir}: pack format {version} is not "
            f"{LEGACY_FORMAT_VERSION} or {FORMAT_VERSION} — re-pack with "
            "scripts/pack_dataset.py"
        )
    total = int(manifest.get("total_steps", 0))
    ph = int(manifest["packed"]["height"])
    pw = int(manifest["packed"]["width"])
    manifest["freshness_epoch"] = 0
    manifest["shards"] = [
        {
            "suffix": "",
            "frames": FRAMES_NAME,
            "steps": total,
            "frame_base": 0,
            "episodes": len(manifest.get("episodes", [])),
            "bytes": total * ph * pw * 3,
            "has_text": bool(manifest.get("has_instruction_text")),
        }
    ]
    for e in manifest.get("episodes", []):
        e.setdefault("shard", 0)
    return manifest


def verify_shards(
    pack_dir: str, manifest: Dict[str, object]
) -> List[str]:
    """Validate EVERY shard's files; returns problem strings naming the
    missing/corrupt shard (empty = intact). Checked on cache open, on
    `refresh`, and by the staleness gate — a pack with a torn or deleted
    shard must fail loudly with the shard's name, not stream garbage."""
    problems: List[str] = []
    for shard in manifest.get("shards", []):
        suffix = shard.get("suffix", "")
        frames = os.path.join(pack_dir, shard_frames_name(suffix))
        expected = int(shard.get("bytes", 0))
        try:
            size = os.path.getsize(frames)
        except OSError:
            problems.append(f"shard {shard_frames_name(suffix)!r}: missing")
            continue
        if size != expected:
            problems.append(
                f"shard {shard_frames_name(suffix)!r}: {size} bytes on "
                f"disk, manifest expects {expected}"
            )
        for member in META_MEMBERS:
            meta = os.path.join(pack_dir, shard_meta_name(member, suffix))
            if not os.path.exists(meta):
                problems.append(
                    f"shard {shard_frames_name(suffix)!r}: sidecar "
                    f"{shard_meta_name(member, suffix)!r} missing"
                )
        if shard.get("has_text") and not os.path.exists(
            os.path.join(pack_dir, shard_meta_name(TEXT_MEMBER, suffix))
        ):
            problems.append(
                f"shard {shard_frames_name(suffix)!r}: sidecar "
                f"{shard_meta_name(TEXT_MEMBER, suffix)!r} missing"
            )
    return problems


def pack_status(
    pack_dir: str,
    paths: Sequence[str],
    height: int,
    width: int,
    crop_factor: Optional[float],
) -> Tuple[bool, str]:
    """(fresh, reason) for `pack_dir` against base episode set `paths`.

    Fresh = same train geometry, shard 0 built from exactly `paths` (same
    basenames in order, unchanged size/mtime fingerprints), and EVERY shard
    — including flywheel-appended ones, which are not part of the base set
    — present and intact on disk. The reason string names what failed
    (which shard, which episode) so the fallback log is actionable.
    """
    try:
        manifest = load_manifest(pack_dir)
    except (OSError, ValueError) as exc:
        return False, f"manifest unreadable: {exc}"
    train = manifest.get("train", {})
    if (
        train.get("height") != height
        or train.get("width") != width
        or train.get("crop_factor") != crop_factor
    ):
        return False, (
            f"train geometry {train.get('height')}x{train.get('width')}"
            f"@{train.get('crop_factor')} != requested "
            f"{height}x{width}@{crop_factor}"
        )
    base = [e for e in manifest.get("episodes", []) if e.get("shard") == 0]
    paths = sorted(paths)
    if len(base) != len(paths):
        return False, (
            f"base shard has {len(base)} episodes, source dir has "
            f"{len(paths)}"
        )
    for entry, path in zip(base, paths):
        try:
            fp = _fingerprint(path)
        except OSError:
            return False, f"source episode {path!r} unreadable"
        if entry.get("source") != fp:
            return False, (
                f"source episode {os.path.basename(path)!r} changed since "
                "packing"
            )
    problems = verify_shards(pack_dir, manifest)
    if problems:
        return False, "; ".join(problems)
    return True, "fresh"


def pack_is_fresh(
    pack_dir: str,
    paths: Sequence[str],
    height: int,
    width: int,
    crop_factor: Optional[float],
) -> bool:
    """True when `pack_dir` holds a current pack of exactly `paths` (plus
    any intact appended shards); see `pack_status` for the reason string."""
    return pack_status(pack_dir, paths, height, width, crop_factor)[0]


# --------------------------------------------------------------------- cache


class _OpenShard:
    """One shard's open mmaps: frames + step-aligned meta (+ text)."""

    __slots__ = ("frames", "meta", "text", "base", "steps")

    def __init__(self, frames, meta, text, base, steps):
        self.frames = frames
        self.meta = meta
        self.text = text
        self.base = base
        self.steps = steps


class PackedEpisodeCache:
    """Window sampler over a packed cache: mmap slices, not decodes.

    Mirrors `WindowedEpisodeDataset`'s sample distribution exactly (same
    (episode, start) index, same front-padding, `_crop_box` draws in source
    coordinates) but a window's frames are (h, w) uint8 slices out of the
    per-shard frame mmaps. `get_window` returns the same nested dict the
    tf.data path produces; `fill_batch` writes a whole batch straight into
    caller-provided buffers (the feeder's arrays). Total open handles: one
    frames mmap + one mmap per meta member PER SHARD, regardless of corpus
    size — there is no per-episode state to cache or evict.

    Flywheel semantics: `refresh()` re-reads the manifest and opens any
    newly appended shards in place — existing episode indices, window
    index entries, and open mmaps are never disturbed, so concurrent
    readers (feeder workers mid-batch) are safe; the feeder calls it at
    epoch boundaries only, keeping every epoch's stream a pure function of
    (seed, epoch, corpus-at-epoch-start).
    """

    @startup.phased("open_feed")
    def __init__(self, pack_dir: str, window: int = 6, clip_tokenizer=None):
        self.pack_dir = pack_dir
        self.manifest = load_manifest(pack_dir)
        problems = verify_shards(pack_dir, self.manifest)
        if problems:
            raise ValueError(
                f"{pack_dir}: packed cache is torn — " + "; ".join(problems)
            )
        self.window = window
        self.height = int(self.manifest["train"]["height"])
        self.width = int(self.manifest["train"]["width"])
        self.crop_factor = self.manifest["train"]["crop_factor"]
        self.src_h = int(self.manifest["source"]["height"])
        self.src_w = int(self.manifest["source"]["width"])
        self.packed_h = int(self.manifest["packed"]["height"])
        self.packed_w = int(self.manifest["packed"]["width"])
        self.episodes = list(self.manifest["episodes"])
        self.total_steps = int(self.manifest["total_steps"])
        self.freshness_epoch = int(self.manifest.get("freshness_epoch", 0))
        self.refreshes = 0  # successful mid-run shard pickups
        self.last_refresh_unix = time.time()
        self._clip_tokenizer = clip_tokenizer
        self._clip_token_cache: Dict[int, np.ndarray] = {}
        self._lock = threading.Lock()
        self._shards: List[_OpenShard] = [
            self._open_shard(s) for s in self.manifest["shards"]
        ]
        self._shard_bases = np.array(
            [s.base for s in self._shards], np.int64
        )
        self._frame_offsets = np.array(
            [int(e["frame_offset"]) for e in self.episodes], np.int64
        )
        self.index: List[Tuple[int, int]] = []
        for i, entry in enumerate(self.episodes):
            self.index.extend((i, s) for s in range(int(entry["steps"])))

    def _open_shard(self, shard: Dict[str, object]) -> _OpenShard:
        suffix = shard.get("suffix", "")
        steps = int(shard["steps"])
        # One mapping for every frame in the shard; the kernel pages in
        # only what gets sliced.
        frames = np.memmap(
            os.path.join(self.pack_dir, shard_frames_name(suffix)),
            dtype=np.uint8,
            mode="r",
            shape=(steps, self.packed_h, self.packed_w, 3),
        )
        # Raw .npy metas opened mmap_mode="r": header parsed once here,
        # window access is a page-cached fancy-index (the per-episode
        # .npz sidecars this replaces cost 3.2 ms of zipfile parsing per
        # load — a per-sample tax at corpus scale).
        meta = {
            k: np.load(
                os.path.join(self.pack_dir, shard_meta_name(k, suffix)),
                mmap_mode="r",
            )
            for k in META_MEMBERS
        }
        text = None
        if shard.get("has_text"):
            text = np.load(
                os.path.join(
                    self.pack_dir, shard_meta_name(TEXT_MEMBER, suffix)
                ),
                mmap_mode="r",
            )
        return _OpenShard(
            frames, meta, text, base=int(shard["frame_base"]), steps=steps
        )

    def __len__(self) -> int:
        return len(self.index)

    # ------------------------------------------------------------ flywheel

    @property
    def num_shards(self) -> int:
        return len(self._shards)

    @property
    def appended_episodes(self) -> int:
        """Episodes living in flywheel-appended shards (shard > 0)."""
        return sum(
            int(s.get("episodes", 0))
            for s in self.manifest["shards"]
            if s.get("appended")
        )

    def episode_task(self, ep_i: int) -> str:
        """The per-episode task id carried through capture/pack metas
        (reward family, capture workload tag) — the hook task-mixture
        sampling weights against. Episodes packed before task stamping
        existed (legacy format-2 manifests, untagged corpora) report the
        stable ``UNKNOWN_TASK`` slug instead of None/raising, so mixture
        weights and per-task telemetry always see a string id."""
        return self.episodes[ep_i].get("task") or UNKNOWN_TASK

    @property
    def tasks(self) -> List[str]:
        """Per-episode task ids, index-aligned with `episodes` (untagged
        episodes report ``UNKNOWN_TASK``)."""
        return [e.get("task") or UNKNOWN_TASK for e in self.episodes]

    def refresh(self) -> bool:
        """Pick up shards appended since open; True when the corpus grew.

        Re-reads the manifest; on a bumped `freshness_epoch` the new
        shards are validated (a torn append is skipped loudly, the old
        view keeps serving) and opened, and `episodes`/`index`/offset
        tables are EXTENDED in place — entries already handed to readers
        never move. Geometry is append-invariant by construction
        (`append_shard` enforces it)."""
        with self._lock:
            try:
                manifest = load_manifest(self.pack_dir)
            except (OSError, ValueError):
                return False  # mid-rewrite or gone; keep the current view
            self.last_refresh_unix = time.time()
            fresh_epoch = int(manifest.get("freshness_epoch", 0))
            if (
                fresh_epoch <= self.freshness_epoch
                or len(manifest["episodes"]) < len(self.episodes)
                or len(manifest["shards"]) <= len(self._shards)
            ):
                return False
            problems = verify_shards(self.pack_dir, manifest)
            if problems:
                import logging

                logging.getLogger(__name__).warning(
                    "packed cache refresh skipped — %s", "; ".join(problems)
                )
                return False
            self.manifest = manifest
            for shard in manifest["shards"][len(self._shards):]:
                self._shards.append(self._open_shard(shard))
            new_eps = manifest["episodes"][len(self.episodes):]
            base_i = len(self.episodes)
            self.episodes.extend(new_eps)
            self._shard_bases = np.array(
                [s.base for s in self._shards], np.int64
            )
            self._frame_offsets = np.array(
                [int(e["frame_offset"]) for e in self.episodes], np.int64
            )
            for i, entry in enumerate(new_eps, start=base_i):
                self.index.extend(
                    (i, s) for s in range(int(entry["steps"]))
                )
            self.total_steps = int(manifest["total_steps"])
            self.freshness_epoch = fresh_epoch
            self.refreshes += 1
            return True

    # ------------------------------------------------------------ file access

    def _episode_shard(self, ep_i: int) -> Tuple[_OpenShard, int]:
        """(shard, local frame offset) for episode `ep_i` — episodes never
        span shards."""
        entry = self.episodes[ep_i]
        shard = self._shards[int(entry.get("shard", 0))]
        return shard, int(entry["frame_offset"]) - shard.base

    def frames(self, ep_i: int) -> np.ndarray:
        """(T, ph, pw, 3) uint8 view of episode `ep_i`'s packed frames."""
        shard, off = self._episode_shard(ep_i)
        return shard.frames[off : off + int(self.episodes[ep_i]["steps"])]

    def meta(self, ep_i: int) -> Dict[str, np.ndarray]:
        """Step-aligned member views for episode `ep_i` (zero copies)."""
        shard, off = self._episode_shard(ep_i)
        end = off + int(self.episodes[ep_i]["steps"])
        return {k: v[off:end] for k, v in shard.meta.items()}

    # ------------------------------------------------------------ sampling

    def draw_box(self, rng: np.random.Generator) -> Tuple[int, int, int, int]:
        """One source-coordinate crop box — the tf.data path's distribution,
        drawn by the same `_crop_box` (bit-identical for the same rng)."""
        return _crop_box(self.src_h, self.src_w, self.crop_factor, rng)

    def draw_packed_offsets(
        self, rng: np.random.Generator, n: int
    ) -> np.ndarray:
        """(n, 2) packed-coordinate (top, left) offsets, drawn vectorized.

        Identical distribution to mapping `draw_box` results one by one
        (uniform integers over the same source ranges, the same
        round-and-clip into packed coordinates) but one rng call per axis
        for the whole batch — the feeder's hot path. Not the same *stream*
        as per-frame `_crop_box` draws; the byte-parity contract with the
        tf.data path lives on `get_window`/`gather_frames`, which keep the
        sequential draw order.
        """
        h, w = self.height, self.width
        ph, pw = self.packed_h, self.packed_w
        if self.crop_factor is None:
            return np.zeros((n, 2), np.int32)
        ch0 = int(self.src_h * self.crop_factor)
        cw0 = int(self.src_w * self.crop_factor)
        tops = rng.integers(0, self.src_h - ch0 + 1, size=n)
        lefts = rng.integers(0, self.src_w - cw0 + 1, size=n)
        out = np.empty((n, 2), np.int32)
        # np.rint is round-half-even, matching map_box_to_packed's
        # int(round(.)) on the scalar path.
        out[:, 0] = np.clip(np.rint(tops * (h / ch0)), 0, ph - h)
        out[:, 1] = np.clip(np.rint(lefts * (w / cw0)), 0, pw - w)
        return out

    def _padded_src(self, start: int, j: int) -> int:
        """Index into the unpadded episode for step j of the padded window."""
        pad = self.window - 1
        k = start + j
        return 0 if k < pad else k - pad

    def _padded_src_indices(self, start: int) -> np.ndarray:
        """(window,) int64 unpadded source steps for the whole window."""
        k = np.arange(start, start + self.window, dtype=np.int64)
        return np.maximum(k - (self.window - 1), 0)

    def gather_frames(
        self,
        ep_i: int,
        start: int,
        rng: Optional[np.random.Generator] = None,
        out: Optional[np.ndarray] = None,
    ) -> np.ndarray:
        """(window, h, w, 3) uint8 for window `start` of episode `ep_i`.

        Each frame is an independent random crop; boxes are drawn
        per-frame in source coordinates with the tf.data path's exact rng
        consumption order (the byte-parity path — `fill_batch` is the
        vectorized fast path). `out` lets callers fill a buffer in place.
        """
        mm = self.frames(ep_i)
        h, w = self.height, self.width
        if out is None:
            out = np.empty((self.window, h, w, 3), np.uint8)
        rng = rng or np.random.default_rng()
        boxes = [self.draw_box(rng) for _ in range(self.window)]
        use_native = _native_gather_available()
        if use_native:
            from rt1_tpu.data import native

            src = np.empty((self.window,), np.int64)
            pboxes = np.empty((self.window, 4), np.int32)
            for j in range(self.window):
                src[j] = self._padded_src(start, j)
                top_p, left_p = map_box_to_packed(
                    boxes[j], self.src_h, self.src_w,
                    self.packed_h, self.packed_w, h, w,
                )
                pboxes[j] = (top_p, left_p, h, w)
            native.packed_gather(mm, src, pboxes, out, threads=1)
            return out
        for j in range(self.window):
            frame = mm[self._padded_src(start, j)]
            top_p, left_p = map_box_to_packed(
                boxes[j], self.src_h, self.src_w,
                self.packed_h, self.packed_w, h, w,
            )
            out[j] = frame[top_p : top_p + h, left_p : left_p + w]
        return out

    def get_window(
        self, idx: int, rng: Optional[np.random.Generator] = None
    ) -> Dict[str, Dict[str, np.ndarray]]:
        """Same nested sample dict as `WindowedEpisodeDataset.get_window`."""
        ep_i, start = self.index[idx]
        meta = self.meta(ep_i)
        images = self.gather_frames(ep_i, start, rng)
        embeds, actions, terms = [], [], []
        for j in range(self.window):
            src = self._padded_src(start, j)
            embeds.append(meta["instruction"][src])
            actions.append(meta["action"][src])
            terms.append(np.int32(bool(meta["is_terminal"][src])))
        observations = {
            "image": images,
            "natural_language_embedding": np.stack(embeds).astype(np.float32),
        }
        if self._clip_tokenizer is not None:
            observations["instruction_tokenized_clip"] = np.tile(
                self._episode_clip_tokens(ep_i), (self.window, 1)
            )
        return {
            "observations": observations,
            "actions": {
                "terminate_episode": np.asarray(terms, np.int32),
                "action": np.stack(actions).astype(np.float32),
            },
        }

    def fill_window(
        self,
        idx: int,
        rng: np.random.Generator,
        image_out: np.ndarray,
        embed_out: np.ndarray,
        term_out: np.ndarray,
        action_out: np.ndarray,
    ) -> None:
        """Assemble window `idx` straight into batch-row buffers (no stack)."""
        ep_i, start = self.index[idx]
        meta = self.meta(ep_i)
        self.gather_frames(ep_i, start, rng, out=image_out)
        for j in range(self.window):
            src = self._padded_src(start, j)
            embed_out[j] = meta["instruction"][src]
            action_out[j] = meta["action"][src]
            term_out[j] = int(bool(meta["is_terminal"][src]))

    def _gather_meta(self, member: str, gidx: np.ndarray) -> np.ndarray:
        """Fancy-index a step-aligned member by GLOBAL frame index across
        shards; single-shard corpora stay the one-mmap fast path."""
        if len(self._shards) == 1:
            return self._shards[0].meta[member][gidx]
        flat = gidx.reshape(-1)
        shard_ids = (
            np.searchsorted(self._shard_bases, flat, side="right") - 1
        )
        first = self._shards[0].meta[member]
        out = np.empty((flat.shape[0],) + first.shape[1:], first.dtype)
        for k in np.unique(shard_ids):
            rows = np.nonzero(shard_ids == k)[0]
            shard = self._shards[int(k)]
            out[rows] = shard.meta[member][flat[rows] - shard.base]
        return out.reshape(gidx.shape + first.shape[1:])

    def fill_batch(
        self,
        indices: np.ndarray,
        rng: np.random.Generator,
        images: np.ndarray,
        embeds: np.ndarray,
        terms: np.ndarray,
        actions: np.ndarray,
        threads: int = 1,
        offsets: Optional[np.ndarray] = None,
    ) -> None:
        """Assemble a whole batch into preallocated buffers, vectorized.

        The feeder's hot path: one vectorized crop-offset draw, one global
        frame-index computation, and ONE native gather call per shard
        touched (or a numpy slice loop) for the entire batch against the
        shard mmaps; meta members fill via one fancy-index each. Crop
        distribution matches the per-window path (`draw_packed_offsets`);
        byte-level stream parity with `get_window` is not a goal here —
        determinism is the feeder's (seed, epoch, batch) contract.

        ``offsets`` ((n·window, 2) int32 packed crop offsets) substitutes
        for the rng draw — the multi-host path: each host of a
        process-sharded feeder draws the GLOBAL batch's offsets from the
        shared (seed, epoch, batch) rng and passes only its rows here, so
        per-host shards concatenate to the exact single-host batch,
        augmentation included (rt1_tpu/data/feeder.py `_assemble`).
        """
        n = len(indices)
        w = self.window
        h, wd = self.height, self.width
        if offsets is None:
            offsets = self.draw_packed_offsets(rng, n * w)
        # Global frame indices: episode frame offset + padded source step.
        gidx = np.empty((n, w), np.int64)
        for i, idx in enumerate(indices):
            ep_i, start = self.index[int(idx)]
            gidx[i] = self._frame_offsets[ep_i] + self._padded_src_indices(start)
        flat_idx = gidx.reshape(-1)
        boxes = np.empty((n * w, 4), np.int32)
        boxes[:, :2] = offsets
        boxes[:, 2] = h
        boxes[:, 3] = wd
        flat_img = images.reshape(n * w, h, wd, 3)
        use_native = _native_gather_available()
        if len(self._shards) == 1:
            self._gather_shard(
                self._shards[0], flat_idx, boxes, flat_img, threads,
                use_native,
            )
        else:
            shard_ids = (
                np.searchsorted(self._shard_bases, flat_idx, side="right")
                - 1
            )
            for k in np.unique(shard_ids):
                rows = np.nonzero(shard_ids == k)[0]
                shard = self._shards[int(k)]
                sub = np.empty((len(rows), h, wd, 3), np.uint8)
                self._gather_shard(
                    shard, flat_idx[rows] - shard.base, boxes[rows], sub,
                    threads, use_native,
                )
                flat_img[rows] = sub
        embeds[:] = self._gather_meta("instruction", gidx)
        actions[:] = self._gather_meta("action", gidx)
        terms[:] = self._gather_meta("is_terminal", gidx)

    @staticmethod
    def _gather_shard(
        shard: _OpenShard,
        local_idx: np.ndarray,
        boxes: np.ndarray,
        out: np.ndarray,
        threads: int,
        use_native: bool,
    ) -> None:
        if use_native:
            from rt1_tpu.data import native

            native.packed_gather(
                shard.frames, local_idx, boxes, out, threads=threads
            )
            return
        h, wd = out.shape[1], out.shape[2]
        for j in range(len(local_idx)):
            top, left = boxes[j, 0], boxes[j, 1]
            out[j] = shard.frames[
                local_idx[j], top : top + h, left : left + wd
            ]

    def _episode_clip_tokens(self, ep_i: int) -> np.ndarray:
        with self._lock:
            tokens = self._clip_token_cache.get(ep_i)
        if tokens is None:
            entry = self.episodes[ep_i]
            shard = self._shards[int(entry.get("shard", 0))]
            if shard.text is None or "text_offset" not in entry:
                raise KeyError(
                    f"episode {ep_i} in {self.pack_dir} has no "
                    "'instruction_text'; re-pack from a corpus collected "
                    "with a current rt1_tpu.data.collect to use clip_tokens"
                )
            off, ln = int(entry["text_offset"]), int(entry["text_len"])
            text = ep_lib.decode_instruction_text(shard.text[off : off + ln])
            tokens = self._clip_tokenizer.tokenize_text(text)[0].astype(np.int32)
            with self._lock:
                self._clip_token_cache[ep_i] = tokens
        return tokens


def _native_gather_available() -> bool:
    if os.environ.get("RT1_TPU_NO_NATIVE"):
        return False
    try:
        from rt1_tpu.data import native

        return native.packed_gather_available()
    except Exception:
        return False


def default_pack_dir(data_dir: str, split: str) -> str:
    """Convention: the packed cache lives next to its split's episodes."""
    return os.path.join(data_dir, f"{split}_packed")

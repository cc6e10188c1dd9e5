"""Multi-session RT-1 policy engine: one batched, AOT-compiled control step.

`RT1Policy.infer_step` keeps a rolling per-stream window (context image
tokens, action tokens, seq_idx) whose roll-vs-insert decision depends on
that stream's `seq_idx` — a scalar in the model's state pytree, so a naive
batched call would force every stream to the same phase. The engine instead
`vmap`s a single-stream step over a fixed number of **slots**: every leaf of
the engine state carries a leading slot axis (`seq_idx` becomes `(N,)`),
each session owns one slot, and sessions at different points of their
episode coexist in one device batch.

Fixed shapes, pinned compiles: the engine compiles a small set of
**batch-size buckets** (config-driven, default just `[max_sessions]`) and
every batch rides the smallest bucket that fits, so light traffic stops
paying the full-batch step cost. Each bucket executable gathers its lanes'
rows out of the full `(max_sessions, ...)` state tree by slot index, steps
them, and scatters the (active-gated) results back — padding lanes ride
distinct unused slots and write their old value back, so no batch
composition can corrupt a neighbour. Every bucket is lowered and compiled
**ahead of time** (`jax.jit(...).lower(...).compile()`), `compile_count`
is pinned at exactly `len(buckets)` for the engine lifetime, and a later
shape mismatch is a hard error, not a silent recompile. The state argument
is donated: the rolling window updates in place on device, no per-step copy.

The hot path is split into `dispatch_batch` (host work + async device
dispatch, under the lock) and `collect_batch` (the blocking device→host
fetch, outside the lock), so a serving frontend can **double-buffer**:
prepare and dispatch batch N+1 while batch N still executes — XLA orders
the two steps through the donated state dependency, and sessions riding an
in-flight step are protected from LRU eviction until their results land.
`act_batch` remains the dispatch-then-collect composition.

The model parameters are an **argument** of the compiled step, not a
closure capture — a captured array would be baked into the executable as a
constant, making a checkpoint reload a recompile. Because they are an
input (undonated, so they survive every call), `swap_variables` can
hot-swap a newly restored checkpoint between two batches: validate the new
tree in a standby host buffer (structure, shapes, dtypes, finiteness),
transfer it to the device off the request path, then atomically repoint
the engine under the lock. In-flight batches finish on the old params, the
next batch runs on the new ones, and the pinned-compile invariant
(`compile_count == len(buckets)`) holds across any number of reloads.

Host-side the engine adds the serving conveniences the eval policy never
needed: session→slot assignment with LRU reclaim, per-slot reset, action
de-normalization/clipping, and an LRU instruction-embedding cache keyed by
`ClipBPETokenizer` output so textual variants of one instruction ("Push the
red moon" / "push  the red moon") hit one cache line and skip the text
tower / embedder entirely.
"""

from __future__ import annotations

import collections
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from rt1_tpu.obs import startup
from rt1_tpu.obs import trace as obs_trace

EPS = np.finfo(np.float32).eps
EMBEDDING_DIM = 512


class SessionError(RuntimeError):
    """Invalid session usage (duplicate id in one batch, unknown release)."""


class SlotContentionError(SessionError):
    """No slot can be reclaimed for a new session right now — every slot
    belongs to this batch or to a step still in flight. Transient under
    double-buffered oversubscription; the HTTP layer maps it to a
    retryable 503 (busy), never a hard failure."""


def pow2_buckets(max_sessions: int) -> List[int]:
    """The default AOT bucket ladder: powers of two up to (and always
    including) `max_sessions` — e.g. 8 -> [1, 2, 4, 8], 6 -> [1, 2, 4, 6]."""
    out = []
    b = 1
    while b < max_sessions:
        out.append(b)
        b *= 2
    out.append(max_sessions)
    return out


def normalize_buckets(buckets, max_sessions: int) -> Tuple[int, ...]:
    """Validate/canonicalize a bucket list: sorted, unique, within
    [1, max_sessions], and always topped by `max_sessions` so every legal
    batch has a bucket to ride."""
    if buckets is None:
        return (max_sessions,)
    out = sorted({int(b) for b in buckets})
    if not out or out[0] < 1 or out[-1] > max_sessions:
        raise ValueError(
            f"buckets {list(buckets)} must be within [1, {max_sessions}]"
        )
    if out[-1] != max_sessions:
        out.append(max_sessions)
    return tuple(out)


class StepHandle:
    """One in-flight batched step: everything `collect_batch` needs to
    turn the (possibly still executing) device output into per-item
    results. Created by `dispatch_batch`; single-use."""

    __slots__ = (
        "items", "errors", "slots_by_sid", "lane_by_sid", "fresh",
        "bucket", "active_count", "out", "collected",
    )

    def __init__(self, items):
        self.items = list(items)
        self.errors: List[Optional[Exception]] = [None] * len(self.items)
        self.slots_by_sid: Dict[str, int] = {}
        self.lane_by_sid: Dict[str, int] = {}
        self.fresh: set = set()
        self.bucket: Optional[int] = None  # None: nothing was dispatched
        self.active_count = 0
        self.out = None
        self.collected = False


class PolicyEngine:
    """Holds N session slots of rolling network state in one device batch."""

    def __init__(
        self,
        model,
        variables,
        *,
        max_sessions: int = 8,
        action_mean: float = 0.0,
        action_std: float = 1.0,
        action_minimum: float = -0.03,
        action_maximum: float = 0.03,
        embedder: Optional[Callable[[str], np.ndarray]] = None,
        embed_cache_size: int = 256,
        tokenizer=None,
        plan=None,
        buckets: Optional[Sequence[int]] = None,
        inference_dtype: str = "f32",
        prepare_variables: Optional[Callable[[Any], Any]] = None,
        master_variables=None,
        cached_inference: bool = False,
    ):
        import jax
        import jax.numpy as jnp

        if max_sessions < 1:
            raise ValueError(f"max_sessions must be >= 1, got {max_sessions}")
        # AOT batch-size buckets: a batch of k active items rides the
        # smallest bucket >= k. Default is the single full-size bucket —
        # the pre-bucket padding semantics, one compile.
        self.buckets = normalize_buckets(buckets, max_sessions)
        self._jax = jax
        self._model = model
        self._plan = plan
        # Low-precision serving (rt1_tpu/models/quant.py): `variables` is
        # the SERVING tree (already cast/quantized by the restore path);
        # `prepare_variables` re-derives it from an f32 master checkpoint,
        # so `swap_variables` can requantize every standby reload; the
        # master spec (paths/shapes/dtypes of the PRE-quantization tree,
        # from `master_variables` when given) is what standby buffers are
        # validated against — a hot-swap always receives masters, never a
        # pre-quantized tree.
        self.inference_dtype = inference_dtype
        self._prepare = prepare_variables
        spec_src = (
            master_variables if master_variables is not None else variables
        )
        from jax import tree_util as _tree_util

        self._master_spec = [
            (
                _tree_util.keystr(path),
                tuple(leaf.shape),
                np.dtype(leaf.dtype),
            )
            for path, leaf in _tree_util.tree_flatten_with_path(spec_src)[0]
        ]
        # Device-resident params, passed to the compiled step as an
        # argument (see swap_variables). With a `plan`
        # (rt1_tpu/parallel/plan.py — the same declarative layout train
        # resolves from config.parallel) each leaf lands per its plan rule
        # on the plan's mesh, so a tensor-parallel serve mesh is a config
        # switch; without one, device_put is a no-op for arrays already on
        # device. Either way `swap_variables` re-places a new checkpoint
        # with each leaf's CURRENT sharding, keeping layout stable across
        # reloads.
        if plan is not None:
            self._variables = plan.place_variables(variables)
        else:
            self._variables = jax.device_put(variables)
        self.max_sessions = max_sessions
        self.action_mean = action_mean
        self.action_std = action_std
        self.action_minimum = action_minimum
        self.action_maximum = action_maximum
        self._embedder = embedder
        self._embed_cache_size = embed_cache_size
        self._embed_cache: collections.OrderedDict = collections.OrderedDict()
        self._tokenizer = tokenizer
        self.embed_calls = 0  # embedder invocations (cache misses)

        # Incremental inference (docs/serving.md "Incremental inference"):
        # with cached_inference the slot state additionally holds per-layer
        # transformer K/V caches, the compiled step is infer_step_cached
        # (one frame's tokens attend the cached prefix instead of a full-
        # window transformer pass), and every invalidation event (params
        # swap) rebuilds caches via an AOT `rebuild` program. Off (the
        # default) the state schema and the compiled program are the
        # pre-cache ones, byte for byte.
        self.cached_inference = bool(cached_inference)
        self._rebuild = None  # AOT cache-rebuild executable (cached only)
        # Invalidation bookkeeping: reset/evict zero the slot (cache gone
        # with the window); swap rebuilds every cache from the retained
        # image tokens under the new params.
        self.cache_invalidations = {"swap": 0, "reset": 0, "evict": 0}
        self.cache_cached_steps = 0   # lanes stepped through the cached program
        self.cache_rebuild_steps = 0  # per-slot full-window cache rebuilds

        # Engine state: per-slot leaves stacked on a leading slot axis. The
        # model's initial_state(batch_size=1) provides per-leaf shapes/dtypes;
        # seq_idx is its only unbatched (scalar) leaf.
        single = model.initial_state(batch_size=1, cached=self.cached_inference) \
            if self.cached_inference else model.initial_state(batch_size=1)
        self._state = jax.tree.map(
            lambda x: jnp.zeros(
                (max_sessions,) + (x.shape[1:] if x.ndim else ()), x.dtype
            ),
            single,
        )
        if plan is not None:
            # Slot state rides the same mesh as the params (replicated —
            # slots are sessions, not data shards); mixing a mesh-placed
            # param tree with default-device state would fail at dispatch.
            self._state = jax.device_put(
                self._state,
                jax.tree.map(lambda _: plan.replicated(), self._state),
            )

        # Session bookkeeping. OrderedDict doubles as the LRU order:
        # move_to_end on every act, popitem(last=False) to reclaim.
        self._lock = threading.RLock()
        self._embed_lock = threading.Lock()
        self._sessions: collections.OrderedDict = collections.OrderedDict()
        self._free: List[int] = list(range(max_sessions))
        self.evictions = 0  # LRU slot reclaims (oversubscription signal)
        # Sessions riding a dispatched-but-uncollected step: protected
        # from LRU eviction so a double-buffered frontend can never zero
        # a slot whose result is still on the wire.
        self._inflight_sessions: collections.Counter = collections.Counter()
        self.batches_in_flight = 0  # dispatched, not yet collected

        # AOT compilation of EVERY bucket happens lazily at the first act
        # (or explicit warmup()) because only then are H, W and the
        # embedding dim known. compile_count is pinned at len(buckets).
        self._compiled: Dict[int, Any] = {}
        self._compiled_obs_shapes: Optional[Dict[str, Tuple]] = None
        self.compile_count = 0
        self.reloads = 0  # successful swap_variables hot-swaps

    # ------------------------------------------------------------ embedding

    def _embed_instruction(self, text: str) -> np.ndarray:
        """Instruction text -> embedding, LRU-cached on the BPE token ids.

        Keying on `ClipBPETokenizer` output (not the raw string) folds
        case/whitespace/punctuation variants that tokenize identically into
        one entry, so a fleet of clients phrasing the same command slightly
        differently still skips the embedder after the first hit.
        """
        if self._embedder is None:
            raise SessionError(
                "request carried an 'instruction' string but the engine has "
                "no embedder; pass embedder= (rt1_tpu.eval.embedding."
                "get_embedder) or send 'natural_language_embedding' directly"
            )
        if self._tokenizer is None:
            from rt1_tpu.text.clip_bpe import default_tokenizer

            self._tokenizer = default_tokenizer()
        try:
            key = self._tokenizer.tokenize_text(text).tobytes()
        except ValueError:  # longer than the 77-token CLIP context
            key = b"raw\x00" + text.encode("utf-8")
        with self._embed_lock:
            cached = self._embed_cache.get(key)
            if cached is not None:
                self._embed_cache.move_to_end(key)
                return cached
        vec = np.asarray(self._embedder(text), np.float32)
        with self._embed_lock:
            self.embed_calls += 1
            self._embed_cache[key] = vec
            while len(self._embed_cache) > self._embed_cache_size:
                self._embed_cache.popitem(last=False)
        return vec

    def _embed_key(self, text: str) -> bytes:
        """The embed-cache key for `text` (BPE token ids, raw-bytes
        fallback past the CLIP context) — shared by the hit path and the
        migration seed/peek helpers so they can never disagree."""
        if self._tokenizer is None:
            from rt1_tpu.text.clip_bpe import default_tokenizer

            self._tokenizer = default_tokenizer()
        try:
            return self._tokenizer.tokenize_text(text).tobytes()
        except ValueError:  # longer than the 77-token CLIP context
            return b"raw\x00" + text.encode("utf-8")

    def cached_embedding(self, text: str) -> Optional[np.ndarray]:
        """The LRU-cached embedding for `text`, or None on a miss. Pure
        read for the session exporter: no embedder call, no LRU refresh —
        exporting a session must not change what gets evicted next."""
        if self._embedder is None:
            return None
        key = self._embed_key(text)
        with self._embed_lock:
            cached = self._embed_cache.get(key)
        return None if cached is None else np.asarray(cached, np.float32)

    def seed_embedding(self, text: str, vec) -> None:
        """Warm the embed LRU with a migrated (instruction, embedding)
        pair, so the imported session's next text-bearing /act skips the
        embedder exactly as it would have on its old replica. Does not
        bump `embed_calls` — nothing was computed here."""
        if self._embedder is None:
            return
        key = self._embed_key(text)
        value = np.asarray(vec, np.float32)
        with self._embed_lock:
            if key not in self._embed_cache:
                self._embed_cache[key] = value
                while len(self._embed_cache) > self._embed_cache_size:
                    self._embed_cache.popitem(last=False)

    # ------------------------------------------------------------ compile

    def bucket_for(self, active: int) -> int:
        """Deterministic bucket selection: the smallest configured bucket
        that fits `active` items (monotone in `active`)."""
        if active < 1 or active > self.max_sessions:
            raise ValueError(
                f"active={active} outside [1, {self.max_sessions}]"
            )
        for b in self.buckets:
            if b >= active:
                return b
        return self.buckets[-1]  # unreachable: buckets top at max_sessions

    @startup.phased("compile_buckets")
    def _build_step(self, obs_shapes: Dict[str, Tuple[int, ...]]):
        """Lower + compile the batched step for EVERY bucket at fixed
        per-item obs shapes — compile_count lands at len(buckets) and
        never moves again. A set-up phase of the start-up log: one
        function compiled once a bucket is set-up's work, not a recompile."""
        import jax
        import jax.numpy as jnp

        model = self._model
        step_method = (
            model.infer_step_cached if self.cached_inference else model.infer_step
        )

        def single_step(variables, obs, state):
            # One lane == one batch-1 infer step; vmap gives each lane its
            # own scalar seq_idx (per-slot roll phase), which the batched
            # state pytree cannot express directly. State members are
            # threaded by key so the cached path's kv_cache leaf rides the
            # same (donated) chain without per-member plumbing; seq_idx is
            # the one unbatched scalar.
            obs_b = {k: v[None] for k, v in obs.items()}
            state_b = {
                k: (v if k == "seq_idx" else v[None]) for k, v in state.items()
            }
            out, new_state = model.apply(
                variables, obs_b, state_b, method=step_method
            )
            out = jax.tree.map(lambda x: x[0], out)
            new_state = {
                k: (v if k == "seq_idx" else v[0]) for k, v in new_state.items()
            }
            return out, new_state

        def bucket_step(variables, obs, slots, active, state):
            # Params are an argument (broadcast over lanes, NOT donated) so
            # swap_variables can hand the same executable a new checkpoint.
            # `slots` are host-guaranteed DISTINCT rows of the full state
            # tree (padding lanes ride unused slots), so gather → step →
            # scatter is race-free and the donated full state updates in
            # place.
            lanes = jax.tree.map(lambda x: x[slots], state)
            out, stepped = jax.vmap(single_step, in_axes=(None, 0, 0))(
                variables, obs, lanes
            )

            def gate(new, old):
                mask = active.reshape(
                    active.shape + (1,) * (new.ndim - 1)
                )
                return jnp.where(mask, new, old)

            # Padding lanes ran on garbage; gate their old row back before
            # the scatter so their slots' rolling state does not advance.
            gated = jax.tree.map(gate, stepped, lanes)
            new_state = jax.tree.map(
                lambda full, rows: full.at[slots].set(rows), state, gated
            )
            return out, new_state

        # With a plan the lowered step carries each argument's mesh
        # placement, so XLA partitions the batched step (GSPMD) instead of
        # assuming one default device; without one the specs are placement-
        # free, exactly as before.
        repl = self._plan.replicated() if self._plan is not None else None

        def spec_of(x):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=getattr(x, "sharding", None)
                if self._plan is not None else None,
            )

        var_spec = jax.tree.map(spec_of, self._variables)
        state_spec = jax.tree.map(spec_of, self._state)
        for b in self.buckets:
            obs_spec = {
                k: jax.ShapeDtypeStruct(
                    (b,) + tuple(shape), np.float32, sharding=repl
                )
                for k, shape in obs_shapes.items()
            }
            slots_spec = jax.ShapeDtypeStruct((b,), np.int32, sharding=repl)
            active_spec = jax.ShapeDtypeStruct((b,), np.bool_, sharding=repl)
            lowered = jax.jit(bucket_step, donate_argnums=(4,)).lower(
                var_spec, obs_spec, slots_spec, active_spec, state_spec
            )
            self._compiled[b] = lowered.compile()
            self.compile_count += 1
        self._compiled_obs_shapes = dict(obs_shapes)

        if self.cached_inference:
            # The cache invalidation primitive, AOT-compiled alongside the
            # ladder: recompute every slot's K/V rows from its retained
            # per-frame image tokens (model.rebuild_cache — one full-window
            # transformer pass per slot, no tokenizer work). One fixed
            # shape (the whole slot batch), donated state, compiled once at
            # the same moment as the buckets — `compile_count` stays pinned
            # at len(buckets) and no swap ever pays an XLA compile.

            def single_rebuild(variables, state):
                state_b = {
                    k: (v if k == "seq_idx" else v[None])
                    for k, v in state.items()
                }
                new_state = model.apply(
                    variables, state_b, method=model.rebuild_cache
                )
                return {
                    k: (v if k == "seq_idx" else v[0])
                    for k, v in new_state.items()
                }

            def rebuild_all(variables, state):
                return jax.vmap(single_rebuild, in_axes=(None, 0))(
                    variables, state
                )

            self._rebuild = jax.jit(rebuild_all, donate_argnums=(1,)).lower(
                var_spec, state_spec
            ).compile()

    def warmup(
        self,
        image_shape: Sequence[int],
        embed_dim: int = EMBEDDING_DIM,
    ) -> None:
        """AOT-compile every configured bucket before traffic arrives —
        no live request ever pays an XLA compile.

        `image_shape` is the per-item (H, W, 3); pair with
        `compilation_cache.enable_persistent_cache()` at process startup so
        even the pinned compiles are served from disk on restarts.
        """
        with self._lock:
            self._ensure_compiled(
                {
                    "image": tuple(image_shape),
                    "natural_language_embedding": (embed_dim,),
                }
            )

    def _ensure_compiled(self, obs_shapes: Dict[str, Tuple[int, ...]]):
        if not self._compiled:
            self._build_step(obs_shapes)
        elif self._compiled_obs_shapes != obs_shapes:
            raise ValueError(
                f"observation shapes {obs_shapes} do not match the compiled "
                f"step {self._compiled_obs_shapes}; the engine serves one "
                "fixed shape per process (pad/resize client-side)"
            )

    # ------------------------------------------------------------ hot-swap

    @property
    def model(self):
        """The served RT1 module (read-only — parity gates and tooling need
        its window length / token geometry, never its apply state)."""
        return self._model

    @property
    def serving_param_bytes(self) -> int:
        """Device-resident serving-tree bytes (int8 kernels + scales count
        at their quantized size — THE memory win the quant bench records)."""
        jax = self._jax
        return int(
            sum(leaf.nbytes for leaf in jax.tree.leaves(self._variables))
        )

    @property
    def cache_bytes_per_slot(self) -> int:
        """Device bytes of ONE session's K/V cache rows (0 with caching
        off) — the per-slot memory price of incremental inference that the
        `rt1_serve_cache_slot_bytes` gauge exports."""
        if not self.cached_inference:
            return 0
        kv = self._state.get("kv_cache")
        if kv is None:
            return 0
        return int(kv.nbytes // self.max_sessions)

    @property
    def master_param_bytes(self) -> int:
        """Bytes of the f32 master tree this engine restores/reloads from
        (= the serving bytes of an f32 engine of the same model)."""
        return int(
            sum(
                int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
                for _, shape, dtype in self._master_spec
            )
        )

    def swap_variables(self, new_variables) -> Dict[str, Any]:
        """Zero-downtime checkpoint hot-swap: validate `new_variables` in a
        standby host buffer, move them to the device, then atomically
        repoint the compiled step's param argument between batches.

        The expensive phases (host validation, quantization, H2D transfer)
        run OUTSIDE the engine lock, so in-flight `act_batch` calls are
        never stalled; only the final pointer swap takes the lock. Because
        the params are an undonated input of the AOT-compiled executable —
        identical shapes/dtypes are enforced here — no recompile can
        occur: the pinned-compile invariant survives any number of
        reloads. Raises ValueError (engine untouched, old params keep
        serving) on a structure/shape/dtype mismatch or a non-finite leaf.

        Validation is against the MASTER spec, not the serving tree's
        dtypes: a standby always arrives as the f32 master checkpoint
        (eval/restore.load_standby_variables contract) — under bf16/int8
        serving the engine re-runs the same deterministic
        `prepare_variables` transform quantize-at-restore used, landing on
        the exact dtypes the step was compiled for. A standby pre-cast to
        a compute/serving dtype is rejected rather than silently
        recompiled or served.
        """
        import numpy as np
        from jax import tree_util

        jax = self._jax
        standby = [
            (tree_util.keystr(path), np.asarray(leaf))
            for path, leaf in tree_util.tree_flatten_with_path(
                new_variables
            )[0]
        ]
        if [p for p, _ in standby] != [p for p, _, _ in self._master_spec]:
            raise ValueError(
                "swap_variables: parameter tree structure differs from the "
                f"master tree ({len(standby)} vs {len(self._master_spec)} "
                "leaves); hot-swap requires a checkpoint of the same model"
            )
        for (path, new), (_, shape, dtype) in zip(standby, self._master_spec):
            if tuple(new.shape) != shape or new.dtype != dtype:
                raise ValueError(
                    f"swap_variables: leaf {path!r} is "
                    f"{new.shape}/{new.dtype}, master spec "
                    f"{shape}/{dtype} — hot-swap expects the f32 master "
                    "checkpoint (a shape/dtype drift would force a "
                    "recompile); rejected"
                )
        bad = [
            path
            for path, leaf in standby
            if np.issubdtype(leaf.dtype, np.floating)
            and not np.isfinite(leaf).all()
        ]
        if bad:
            raise ValueError(
                f"swap_variables: non-finite values in {bad[:4]} "
                f"({len(bad)} leaves) — refusing to serve a corrupt "
                "checkpoint; old params stay live"
            )
        # Re-derive the serving tree from the validated masters (cast /
        # per-channel int8 quantization — deterministic, so the result's
        # dtypes match the compiled step exactly), still off the lock.
        if self._prepare is not None:
            serving = self._prepare(new_variables)
        else:
            serving = new_variables
        serving_flat = [
            (tree_util.keystr(path), leaf)
            for path, leaf in tree_util.tree_flatten_with_path(serving)[0]
        ]
        current = [
            (tree_util.keystr(path), leaf)
            for path, leaf in tree_util.tree_flatten_with_path(
                self._variables
            )[0]
        ]
        # Final no-recompile gate on the SERVING tree: the prepared tree
        # must be leaf-for-leaf compatible with what the step compiled
        # against (catches a quant-rule edit racing a live engine).
        if [p for p, _ in serving_flat] != [p for p, _ in current]:
            raise ValueError(
                "swap_variables: prepared serving tree structure differs "
                "from the compiled serving tree — quant rules changed "
                "under a live engine?"
            )
        for (path, new), (_, old) in zip(serving_flat, current):
            if tuple(new.shape) != tuple(old.shape) or new.dtype != old.dtype:
                raise ValueError(
                    f"swap_variables: prepared serving leaf {path!r} is "
                    f"{tuple(new.shape)}/{new.dtype}, compiled "
                    f"{tuple(old.shape)}/{old.dtype} — rejected to keep "
                    "the pinned-compile invariant"
                )
        # Rebuild on the SERVING treedef (a restored checkpoint may arrive
        # as plain dicts while the engine was built from a FrozenDict —
        # the AOT executable matches treedefs exactly, not just key paths)
        # and re-place each leaf with its CURRENT sharding: under a plan
        # the swapped-in checkpoint keeps the exact mesh layout the step
        # was compiled for, so the no-recompile guarantee holds for
        # sharded serving too.
        treedef = jax.tree.structure(self._variables)
        device = jax.device_put(
            jax.tree.unflatten(treedef, [leaf for _, leaf in serving_flat]),
            jax.tree.map(lambda x: x.sharding, self._variables),
        )
        jax.block_until_ready(device)  # pay the H2D cost off the swap
        caches_rebuilt = 0
        with self._lock:
            self._variables = device
            self.reloads += 1
            # A params swap makes every cached K/V row stale (it was
            # computed by the OLD transformer). Rebuild all slots' caches
            # from their retained image tokens under the new params — the
            # same full-window math infer_step would do — instead of
            # serving poisoned caches. Under the lock: the rebuild must
            # order against dispatches on the donated state chain.
            if self.cached_inference and self._rebuild is not None:
                self._state = self._rebuild(self._variables, self._state)
                self.cache_invalidations["swap"] += 1
                caches_rebuilt = len(self._sessions)
                self.cache_rebuild_steps += caches_rebuilt
        result = {
            "params_swapped": len(serving_flat),
            "param_bytes": int(
                sum(np.asarray(leaf).nbytes for _, leaf in serving_flat)
            ),
            "inference_dtype": self.inference_dtype,
        }
        if self.cached_inference:
            # Only the cached engine reports rebuilds — the windowed swap
            # response stays byte-identical to the pre-cache engine's.
            result["caches_rebuilt"] = caches_rebuilt
        return result

    # ------------------------------------------------------------ sessions

    def _slot_for(
        self, session_id: str, create: bool = True, protected: frozenset = frozenset()
    ) -> int:
        slot = self._sessions.get(session_id)
        if slot is not None:
            self._sessions.move_to_end(session_id)
            return slot
        if not create:
            raise SessionError(f"unknown session {session_id!r}")
        if self._free:
            slot = self._free.pop()
        else:
            # Reclaim the least-recently-used session's slot. The evicted
            # session is forgotten; if it comes back it starts a fresh
            # window (clients idle past the slot budget should /reset).
            # `protected` holds the current batch's session ids plus every
            # session riding a still-in-flight step — a session being
            # stepped right now must never be the eviction victim.
            victim = next(
                (s for s in self._sessions if s not in protected), None
            )
            if victim is None:
                raise SlotContentionError(
                    f"no reclaimable slot for session {session_id!r}: all "
                    f"{self.max_sessions} slots belong to this batch or an "
                    "in-flight step; retry after the step completes"
                )
            slot = self._sessions.pop(victim)
            self.evictions += 1
            if self.cached_inference:
                # The victim's K/V rows die with its window (_zero_slot
                # below) — booked as a cache invalidation so the scrape
                # plane can tell churn-driven cache loss from swaps.
                self.cache_invalidations["evict"] += 1
        self._sessions[session_id] = slot
        self._zero_slot(slot)
        return slot

    def _zero_slot(self, slot: int) -> None:
        self._state = self._jax.tree.map(
            lambda x: x.at[slot].set(0), self._state
        )

    def reset(self, session_id: str) -> int:
        """Zero a session's rolling window (allocating a slot if new).
        A new session's slot claim honors the same in-flight protection
        as /act: it must not evict a session riding a dispatched-but-
        uncollected step (retryable SlotContentionError instead)."""
        with self._lock:
            known = session_id in self._sessions
            slot = self._slot_for(
                session_id, protected=frozenset(self._inflight_sessions)
            )
            self._zero_slot(slot)
            if self.cached_inference and known:
                self.cache_invalidations["reset"] += 1
            return slot

    def release(self, session_id: str) -> None:
        """Forget a session and return its slot to the free list."""
        with self._lock:
            slot = self._sessions.pop(session_id, None)
            if slot is None:
                raise SessionError(f"unknown session {session_id!r}")
            self._free.append(slot)

    @property
    def active_sessions(self) -> int:
        with self._lock:
            return len(self._sessions)

    def session_ids(self) -> List[str]:
        with self._lock:
            return list(self._sessions)

    def session_state(self, session_id: str) -> Dict[str, np.ndarray]:
        """One session's unbatched state pytree, pulled to host (debug/tests).
        Pure read: does NOT refresh the session's LRU recency — inspecting
        a session must not change which one gets evicted next."""
        with self._lock:
            slot = self._sessions.get(session_id)
            if slot is None:
                raise SessionError(f"unknown session {session_id!r}")
            return self._jax.tree.map(
                lambda x: np.asarray(x[slot]), self._state
            )

    # ------------------------------------------------------- state migration

    @property
    def window(self) -> int:
        """The rolling context window length (model time_sequence_length)
        — part of the session-snapshot compatibility contract: a snapshot
        exported under one window length must not land in another."""
        return int(getattr(self._model, "time_sequence_length", 0))

    def state_schema(self) -> List[Tuple[str, Tuple[int, ...], str]]:
        """The per-slot network-state contract: (leaf name, per-slot shape,
        dtype) triples, sorted by name. With cached_inference this includes
        the `kv_cache` leaf — the cache defines the session state schema,
        which is exactly why the migration seam lands with it."""
        return sorted(
            (k, tuple(v.shape[1:]), str(np.dtype(v.dtype)))
            for k, v in self._state.items()
        )

    def export_session(self, session_id: str) -> Dict[str, Any]:
        """Migration seam (ROADMAP item 3): gather one slot's full rolling
        network_state — window tokens, action tokens, seq_idx, and (when
        cached) the K/V cache rows — to host, with the schema header
        `import_session` validates against. Pure read (no LRU refresh);
        the snapshot is self-describing so a peer replica can refuse a
        mismatched model before touching device memory."""
        return {
            "session_id": session_id,
            "cached_inference": self.cached_inference,
            "schema": self.state_schema(),
            "state": self.session_state(session_id),
        }

    def import_session(self, snapshot: Dict[str, Any], session_id: Optional[str] = None) -> int:
        """Restore an exported session into a slot of THIS engine.

        Validation mirrors `swap_variables`' master-spec discipline, but
        against the engine's state schema: leaf names, per-slot shapes and
        dtypes must match exactly (so a windowed snapshot cannot land in a
        cached engine and vice versa), and float leaves must be finite.
        Raises ValueError with the first mismatch (engine untouched);
        returns the slot on success. Caches travel verbatim — the intended
        use is migrating a session between replicas serving the SAME
        checkpoint (scale-down drain, re-home); after a cross-checkpoint
        move, hot-swap semantics apply and the importer should reset or
        rely on its own swap-time rebuild.
        """
        sid = session_id or snapshot.get("session_id")
        if not sid:
            raise SessionError("import_session: no session id in snapshot or argument")
        state = snapshot.get("state")
        if not isinstance(state, dict):
            raise ValueError("import_session: snapshot has no 'state' pytree")
        expected = self.state_schema()
        got = sorted(
            (k, tuple(np.asarray(v).shape), str(np.asarray(v).dtype))
            for k, v in state.items()
        )
        if [k for k, _, _ in got] != [k for k, _, _ in expected]:
            raise ValueError(
                f"import_session: state leaves {[k for k, _, _ in got]} do "
                f"not match this engine's schema "
                f"{[k for k, _, _ in expected]} — cached_inference or model "
                "mismatch between exporter and importer"
            )
        for (k, shape, dtype), (_, eshape, edtype) in zip(got, expected):
            if shape != eshape or dtype != edtype:
                raise ValueError(
                    f"import_session: leaf {k!r} is {shape}/{dtype}, this "
                    f"engine expects {eshape}/{edtype} — refusing a "
                    "mismatched session snapshot"
                )
        bad = [
            k
            for k, v in state.items()
            if np.issubdtype(np.asarray(v).dtype, np.floating)
            and not np.isfinite(np.asarray(v)).all()
        ]
        if bad:
            raise ValueError(
                f"import_session: non-finite values in {bad} — refusing a "
                "corrupt session snapshot"
            )
        with self._lock:
            slot = self._slot_for(
                sid, protected=frozenset(self._inflight_sessions)
            )
            for k, v in state.items():
                self._state[k] = self._state[k].at[slot].set(
                    np.asarray(v)
                )
            return slot

    # ------------------------------------------------------------ stepping

    def _resolve_obs(self, obs: Dict[str, Any]) -> Dict[str, np.ndarray]:
        image = np.asarray(obs["image"], np.float32)
        if "natural_language_embedding" in obs:
            embedding = np.asarray(
                obs["natural_language_embedding"], np.float32
            )
        else:
            embedding = self._embed_instruction(obs["instruction"])
        return {"image": image, "natural_language_embedding": embedding}

    def dispatch_batch(
        self, items: Sequence[Tuple[str, Dict[str, Any]]]
    ) -> StepHandle:
        """Phase 1 of a batched control step: resolve observations, assign
        slots, and **asynchronously dispatch** the smallest bucket that
        fits. Returns a `StepHandle` the caller hands to `collect_batch`.

        The device may still be executing when this returns — that is the
        point: a double-buffering caller dispatches batch N+1 while batch
        N's collect blocks, and XLA serializes the two steps through the
        donated state dependency. Sessions riding this handle are
        protected from LRU eviction until collected.
        """
        handle = StepHandle(items)
        if not handle.items:
            return handle
        if len(handle.items) > self.max_sessions:
            raise SessionError(
                f"batch of {len(handle.items)} exceeds max_sessions="
                f"{self.max_sessions}"
            )
        ids = [sid for sid, _ in handle.items]
        if len(set(ids)) != len(ids):
            raise SessionError(
                f"duplicate session ids in one batch: {ids} — a "
                "session's rolling state must step one obs at a time"
            )

        # Resolve (and possibly embed) OUTSIDE the lock: an embedder cache
        # miss may be an expensive text-tower forward, and gauge readers
        # (/healthz, /metrics) must not stall behind it. Per-item failures
        # become per-item error results, not a poisoned batch.
        resolved: List[Optional[Dict[str, np.ndarray]]] = []
        # obs: an embedder cache miss (full text-tower forward) shows up
        # as engine_resolve dwarfing engine_dispatch, instead of being
        # booked as device time.
        with obs_trace.span("engine_resolve", batch=len(handle.items)):
            for i, (sid, obs) in enumerate(handle.items):
                try:
                    resolved.append(self._resolve_obs(obs))
                except Exception as exc:  # noqa: BLE001 - isolated per item
                    resolved.append(None)
                    handle.errors[i] = exc

        good = [
            (i, sid, obs)
            for i, ((sid, _), obs) in enumerate(zip(handle.items, resolved))
            if obs is not None
        ]
        if not good:
            return handle
        with self._lock:
            # First use compiles every bucket (shapes come from the first
            # item); afterwards mismatches are handled per item below.
            if not self._compiled:
                self._build_step({k: v.shape for k, v in good[0][2].items()})

            # Per-item shape check BEFORE any slot is assigned: a
            # mismatched item becomes its own error result instead of
            # poisoning the batch (and allocates no slot).
            kept = []
            for i, sid, obs in good:
                bad_key = next(
                    (
                        k
                        for k, v in obs.items()
                        if v.shape != self._compiled_obs_shapes[k]
                    ),
                    None,
                )
                if bad_key is not None:
                    handle.errors[i] = ValueError(
                        f"session {sid!r} obs {bad_key!r} shape "
                        f"{obs[bad_key].shape} != compiled "
                        f"{self._compiled_obs_shapes[bad_key]}"
                    )
                else:
                    kept.append((i, sid, obs))

            # Slot assignment in one pass; eviction safety comes from the
            # `protected` set (every batchmate's id plus every session
            # riding a still-in-flight step), NOT from assignment order —
            # a newcomer's LRU reclaim skips protected sessions and fails
            # with a retryable SlotContentionError when none is left.
            # `fresh` marks sessions starting a new (zeroed) window this
            # step — surfaced in the result so a client whose session was
            # LRU-evicted can detect the silent context reset instead of
            # acting on it unaware.
            handle.fresh.update(
                sid for _, sid, _ in kept if sid not in self._sessions
            )
            batch_ids = frozenset(sid for _, sid, _ in kept)
            protected = batch_ids | frozenset(self._inflight_sessions)
            for idx, sid, _ in list(kept):
                try:
                    if sid in self._sessions:
                        handle.slots_by_sid[sid] = self._slot_for(sid)
                    else:
                        handle.slots_by_sid[sid] = self._slot_for(
                            sid, protected=protected
                        )
                except SlotContentionError as exc:
                    # Transient: every slot is riding this batch or an
                    # in-flight step. Fail THIS item retryably (503 busy
                    # upstream); its batchmates still step.
                    handle.errors[idx] = exc
                    handle.fresh.discard(sid)
                    kept = [k for k in kept if k[1] != sid]

            if not kept:
                return handle
            bucket = self.bucket_for(len(kept))
            batch_obs = {
                k: np.zeros((bucket,) + tuple(shape), np.float32)
                for k, shape in self._compiled_obs_shapes.items()
            }
            active = np.zeros((bucket,), np.bool_)
            slots = np.zeros((bucket,), np.int32)
            for lane, (_, sid, obs) in enumerate(kept):
                handle.lane_by_sid[sid] = lane
                slots[lane] = handle.slots_by_sid[sid]
                for k, v in obs.items():
                    batch_obs[k][lane] = v
                active[lane] = True
            # Padding lanes ride DISTINCT unused slots (there are always
            # enough: bucket <= max_sessions) and scatter their old row
            # back — a no-op write, so duplicate-index scatter hazards
            # cannot exist by construction.
            used = set(int(s) for s in slots[: len(kept)])
            pads = [s for s in range(self.max_sessions) if s not in used]
            for lane in range(len(kept), bucket):
                slots[lane] = pads[lane - len(kept)]

            # obs: async dispatch only — the blocking device→host fetch
            # lands in collect_batch's engine_fetch span, making the
            # double-buffer overlap visible on the trace timeline.
            with obs_trace.span(
                "engine_dispatch", active=len(kept), bucket=bucket
            ):
                handle.out, self._state = self._compiled[bucket](
                    self._variables, batch_obs, slots, active, self._state
                )
            handle.bucket = bucket
            handle.active_count = len(kept)
            if self.cached_inference:
                self.cache_cached_steps += len(kept)
            for _, sid, _ in kept:
                self._inflight_sessions[sid] += 1
            self.batches_in_flight += 1
        return handle

    def collect_batch(self, handle: StepHandle) -> List[Dict[str, Any]]:
        """Phase 2: block on the handle's device step (outside the lock)
        and build one result dict per item — the de-normalized, clipped
        `action` and the raw `action_tokens`, or `{"error": ...}` for an
        item whose observation failed to resolve/validate (a bad request
        must not poison its batchmates; its session state does not
        advance)."""
        if handle.collected:
            raise RuntimeError("StepHandle already collected")
        handle.collected = True
        actions = tokens = terminate = None
        if handle.out is not None:
            try:
                # obs: the blocking fetch — under double-buffering this
                # span overlaps the NEXT batch's engine_dispatch.
                with obs_trace.span(
                    "engine_fetch", active=handle.active_count,
                    bucket=handle.bucket,
                ):
                    actions = np.asarray(handle.out["action"])
                    tokens = np.asarray(handle.out["action_tokens"])
                    terminate = (
                        np.asarray(handle.out["terminate_episode"])
                        if "terminate_episode" in handle.out
                        else None
                    )
            finally:
                # ALWAYS release the eviction protection, even when the
                # fetch itself fails (device fault mid-step): a leaked
                # in-flight count would permanently pin its sessions'
                # slots and starve every future newcomer.
                with self._lock:
                    for sid in handle.lane_by_sid:
                        self._inflight_sessions[sid] -= 1
                        if self._inflight_sessions[sid] <= 0:
                            del self._inflight_sessions[sid]
                    self.batches_in_flight -= 1

        results: List[Dict[str, Any]] = []
        for (sid, _), error in zip(handle.items, handle.errors):
            if error is not None:
                results.append({"error": error})
                continue
            lane = handle.lane_by_sid[sid]
            action = actions[lane] * max(self.action_std, EPS) + self.action_mean
            action = np.clip(action, self.action_minimum, self.action_maximum)
            result = {
                "action": action.astype(np.float32),
                "action_tokens": tokens[lane],
                "session_started": sid in handle.fresh,
            }
            if terminate is not None:
                result["terminate_episode"] = int(terminate[lane])
            results.append(result)
        return results

    def act_batch(
        self, items: Sequence[Tuple[str, Dict[str, Any]]]
    ) -> List[Dict[str, Any]]:
        """Run one batched control step for `items` = [(session_id, obs)]:
        `dispatch_batch` then `collect_batch`, back to back. Each obs
        carries `image` (H, W, 3) float32 in [0, 1] plus either
        `natural_language_embedding` (D,) or `instruction` (str). Session
        ids must be unique within one batch (the batcher's `batch_key`
        guarantees it in the serving path)."""
        return self.collect_batch(self.dispatch_batch(items))

    def act(self, session_id: str, obs: Dict[str, Any]) -> Dict[str, Any]:
        """Single-session convenience wrapper over `act_batch`; re-raises
        the item's error (act_batch's markers exist for batchmates)."""
        result = self.act_batch([(session_id, obs)])[0]
        if "error" in result:
            raise result["error"]
        return result

"""The full SPMD training loop + absl CLI.

Replaces `distribute_train.py:192-247` (Lightning Trainer.fit over DDP) and
`language_table/train/train.py:60-218` (pmap loop) with one mesh-wide jitted
step driven by a host loop: restore-or-initialize, per-step trace annotation,
periodic metrics/checkpoint/eval, throughput accounting, and — via
`config.resilience` (rt1_tpu/resilience/, docs/resilience.md) — NaN
guardrails with checkpoint rollback, preemption-safe save-and-exit, and
retried I/O.

Run:
  python -m rt1_tpu.train.train --config rt1_tpu/train/configs/tiny.py \
      --workdir /tmp/rt1
"""

from __future__ import annotations

import functools
import json
import os
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from rt1_tpu.obs import startup
from rt1_tpu.specs import language_table_action_space, sample_space
from rt1_tpu.train.families import family_of
from rt1_tpu.trainer import (
    create_train_state,
    make_optimizer,
    make_train_step_fns,
)
from rt1_tpu.trainer.checkpoints import CheckpointConfig, CheckpointManager
from rt1_tpu.trainer.metrics import (
    ThroughputMeter,
    create_writer,
    log_parameter_overview,
    scalars_from_metrics,
    step_trace,
    write_hparams,
)


def build_model(model_config, mesh=None):
    """Construct the RT-1 policy from `config.model`.

    `mesh` enables the one mesh-coupled feature: a >1 "stage" axis
    pipelines the decoder (GPipe, parallel/pipeline.py). Eval/restore
    callers may omit it — parameter layout does not depend on the mesh.

    Configs written before the Switch MoE FFN and ring attention were
    removed may still carry their keys: the values that select the paths
    that stayed are accepted (and stale `num_experts` / `moe_*` keys
    ignored), the removed ones are refused here.
    """
    from rt1_tpu.models.rt1 import RT1Policy

    if model_config.get("ffn_impl", "dense") != "dense":
        raise ValueError(
            f"model.ffn_impl={model_config.ffn_impl!r}: the RT-1 decoder's "
            "Switch MoE FFN was removed; routed experts live in the decoder "
            'LM family (model.family="lfm2_moe", rt1_tpu/models/lm)'
        )
    attention_impl = model_config.get("attention_impl", "dense")
    if attention_impl not in ("dense", "pallas"):
        raise ValueError(
            f"model.attention_impl={attention_impl!r}: expected 'dense' or "
            "'pallas' (ring attention was removed and has no replacement: "
            "no RT-1 input is longer than 66 tokens)"
        )

    tokenizer_def = None
    if model_config.image_tokenizer == "tiny":
        from rt1_tpu.models.tiny_tokenizer import TinyImageTokenizer

        tokenizer_def = TinyImageTokenizer(
            num_tokens=model_config.num_image_tokens,
            emb=model_config.token_embedding_size,
            dtype=jnp.bfloat16
            if model_config.dtype == "bfloat16"
            else jnp.float32,
        )
    elif model_config.image_tokenizer == "efficientnet_small":
        # Same FiLM-EfficientNet + TokenLearner family at ~0.35/0.35 scaling:
        # spatially faithful but CPU-trainable (the flagship B3 needs a TPU).
        from rt1_tpu.models.image_tokenizer import RT1ImageTokenizer

        tokenizer_def = RT1ImageTokenizer(
            embedding_output_dim=model_config.token_embedding_size,
            use_token_learner=model_config.use_token_learner,
            num_tokens=model_config.num_image_tokens,
            width_coefficient=0.35,
            depth_coefficient=0.35,
            dtype=jnp.bfloat16
            if model_config.dtype == "bfloat16"
            else jnp.float32,
        )
    return RT1Policy(
        action_space=language_table_action_space(),
        vocab_size=model_config.vocab_size,
        token_embedding_size=model_config.token_embedding_size,
        num_layers=model_config.num_layers,
        layer_size=model_config.layer_size,
        num_heads=model_config.num_heads,
        feed_forward_size=model_config.feed_forward_size,
        dropout_rate=model_config.dropout_rate,
        time_sequence_length=model_config.time_sequence_length,
        use_token_learner=model_config.use_token_learner,
        num_image_tokens=model_config.num_image_tokens,
        image_tokenizer_def=tokenizer_def,
        photometric_augmentation=model_config.get(
            "photometric_augmentation", False
        ),
        focal_gamma=model_config.get("focal_gamma", 0.0),
        aux_mse_weight=model_config.get("aux_mse_weight", 0.0),
        action_decode=model_config.get("action_decode", "argmax"),
        remat=model_config.get("remat", False),
        attention_impl=attention_impl,
        mesh=mesh,
        pipeline_microbatches=model_config.get("pipeline_microbatches", 4),
        dtype=jnp.bfloat16
        if model_config.dtype == "bfloat16"
        else jnp.float32,
    )


@startup.phased("build_model")
def build_family(model_config, mesh=None):
    """(model, init_fn, loss_fn) for ``config.model.family``, from the
    family's record (rt1_tpu/train/families.py).

    The reference trains its two model families from separate stacks
    (Stack A `distribute_train.py` for RT-1, Stack B
    `language_table/train/train.py:105-116` for LAVA/BC); here one train
    loop serves every family — the record only selects the model
    constructor, the init signature, the loss closure plugged into the
    jitted SPMD step, and the host feed. The decoder language models built
    from a block description (rt1_tpu/models/lm, docs/lm_family.md) are
    entries that share one builder; their batches are token ids
    (rt1_tpu/data/tokens.py).
    """
    family = family_of(model_config)
    if (
        not family.pipelined
        and mesh is not None
        and getattr(mesh, "shape", {}).get("stage", 1) > 1
    ):
        raise ValueError(
            f"mesh.stage > 1 (pipeline parallelism) is only supported for "
            f"the 'rt1' family; family={model_config.family!r} would "
            f"silently replicate all compute across the stage axis"
        )
    return family.build(model_config, mesh)


def _make_clip_tokenizer(config):
    """Tokenizer matching the text tower's config, validated at the seam.

    `data.clip_bpe_path` loads the real CLIP merges (vocab 49408);
    unset uses the byte-level fallback (vocab 514). Context length and
    vocab must agree with `model.lava.text_context` / `text_vocab`, or the
    Embed gather clamps out-of-range ids / the posemb slice shape-fails —
    deep inside the traced step instead of here.
    """
    from rt1_tpu.text.clip_bpe import ClipBPETokenizer, default_tokenizer

    lv = config.model.lava
    context = lv.get("text_context", 77)
    path = config.data.get("clip_bpe_path")
    if path:
        tokenizer = ClipBPETokenizer.from_bpe_file(path, context_length=context)
    else:
        tokenizer = default_tokenizer(context_length=context)
    vocab = len(tokenizer.encoder)
    if vocab != lv.get("text_vocab", 514):
        raise ValueError(
            f"model.lava.text_vocab={lv.get('text_vocab')} but the "
            f"tokenizer ({'merges file' if path else 'byte-level default'}) "
            f"has vocab {vocab}; set text_vocab={vocab}"
        )
    return tokenizer


def _check_clip_token_config(config):
    """Fail at the config seam, not steps later inside a traced forward:
    the LAVA "clip" encoder consumes `instruction_tokenized_clip`, which
    only `data.clip_tokens=True` produces — and producing it for any other
    encoder ships a dead (window, 77) tensor to the device every step."""
    clip_tokens = config.data.get("clip_tokens", False)
    lava_clip = (
        config.model.get("family", "rt1") == "lava"
        and config.model.lava.lang_encoder == "clip"
    )
    if lava_clip and not clip_tokens:
        raise ValueError(
            "model.lava.lang_encoder='clip' requires data.clip_tokens=True "
            "(the pipeline must emit instruction_tokenized_clip)"
        )
    if clip_tokens and not lava_clip:
        raise ValueError(
            "data.clip_tokens=True but no model consumes "
            "instruction_tokenized_clip (set model.lava.lang_encoder='clip')"
        )


def _packed_batches(
    config, split, paths, clip_tokenizer, seed=None
) -> Optional[Iterator]:
    """Packed-cache feed for `split`, or None to fall back to tf.data.

    The cache must exist and be fresh (same episodes, same geometry —
    build it with scripts/pack_dataset.py); anything else logs a warning
    and returns None so training proceeds on the tf.data path rather than
    training on stale pixels or dying at startup.

    With `config.resilience.io_retry` the manifest/mmap open and the feeder
    construction are retried with backoff — a transient filesystem error on
    a network mount degrades to a warning instead of killing startup (or a
    guard rollback's feeder rebuild mid-run).
    """
    from absl import logging

    from rt1_tpu import resilience
    from rt1_tpu.data import pack as pack_lib

    pack_dir = config.data.get("packed_cache_dir") or pack_lib.default_pack_dir(
        config.data.data_dir, split
    )
    fresh, reason = pack_lib.pack_status(
        pack_dir,
        paths,
        config.data.height,
        config.data.width,
        config.data.crop_factor,
    )
    if not fresh:
        logging.warning(
            "data.packed_cache=True but %s is missing or stale (%s) — "
            "falling back to the '%s' loader. Build it with: python "
            "scripts/pack_dataset.py --data_dir %s --split %s --height %d "
            "--width %d --crop_factor %s",
            pack_dir,
            reason,
            config.data.loader,
            config.data.data_dir,
            split,
            config.data.height,
            config.data.width,
            config.data.crop_factor,
        )
        return None
    from rt1_tpu.data.feeder import SampleAheadFeeder

    retry_opts = resilience.ResilienceOptions.from_config(config).retry_options()

    def _build(fn, *args, name, **kwargs):
        if retry_opts is None:
            return fn(*args, **kwargs)
        return resilience.retry_call(
            fn, *args, options=retry_opts, name=name, **kwargs
        )

    cache = _build(
        pack_lib.PackedEpisodeCache,
        pack_dir,
        window=config.model.time_sequence_length,
        clip_tokenizer=clip_tokenizer,
        name="packed_cache_open",
    )
    logging.info(
        "packed cache: feeding %s from %s (%d windows, %dx%d packed frames)",
        split, pack_dir, len(cache), cache.packed_h, cache.packed_w,
    )
    # Task-mixture sampling + per-task telemetry (train split only — eval
    # streams stay the unweighted pinned corpus walk): weights come from
    # `config.data.task_weights` ("task:weight,..." string, docs/data.md);
    # task-id emission arms exactly when the step's health pack will
    # consume it (model_health on, a family whose pack reads them), so
    # health-off runs keep a byte-identical batch stream.
    task_weights = None
    emit_task_ids = False
    if split == "train":
        from rt1_tpu import obs as obs_lib
        from rt1_tpu.data.feeder import parse_task_weights

        task_weights = parse_task_weights(config.data.get("task_weights"))
        emit_task_ids = (
            obs_lib.ObsOptions.from_config(config).model_health
            and family_of(config.model).task_ids
        )
    return _build(
        SampleAheadFeeder,
        cache,
        config.per_host_batch_size,
        seed=config.seed if seed is None else seed,
        shuffle=split == "train",
        num_threads=config.data.get("feeder_threads", 2),
        depth=config.data.get("feeder_depth", 2),
        process_index=jax.process_index(),
        process_count=jax.process_count(),
        stall_timeout_s=config.data.get("feeder_stall_timeout_s"),
        # Data flywheel: re-read the pack manifest at epoch boundaries and
        # pick up appended shards mid-run (train split only — eval streams
        # should stay pinned to one corpus). Single-process only: a
        # per-host refresh has no cross-host barrier, so multi-host runs
        # keep the corpus pinned for the whole run (the feeder raises on
        # the combination; restart to absorb appended shards).
        refresh_at_epoch=(
            split == "train"
            and config.data.get("packed_refresh", False)
            and jax.process_count() == 1
        ),
        task_weights=task_weights,
        emit_task_ids=emit_task_ids,
        name="feeder_construct",
    )


def dataset_batches(config, split="train", seed=None) -> Iterator:
    """Real data: windowed episode dataset, per-host sharded.

    `seed` overrides `config.seed` for the stream's shuffle/crop draws —
    the guard's rollback path rebuilds the iterator with a fresh seed so
    the restored run does not re-walk the exact batch sequence that
    produced the divergence.
    """
    import glob

    from rt1_tpu.data.pipeline import WindowedEpisodeDataset

    stream_seed = config.seed if seed is None else seed
    paths = sorted(
        glob.glob(os.path.join(config.data.data_dir, split, "episode_*.np*"))
    )
    if not paths:
        raise FileNotFoundError(
            f"No episodes under {config.data.data_dir}/{split}"
        )
    if config.data.get("clip_tokens", False) and config.data.loader == "rlds_tf":
        raise ValueError(
            "clip_tokens requires the windowed loaders ('tf' or 'numpy'); "
            "the rlds_tf graph pipeline does not tokenize instructions"
        )
    if config.data.loader == "rlds_tf":
        if config.data.get("packed_cache", False):
            raise ValueError(
                "data.packed_cache=True is incompatible with loader="
                "'rlds_tf' (the pure-TF graph cannot read the packed mmap "
                "store); use loader='tf' or 'numpy'"
            )
        # Pure-TF windowing pipeline: episodes stream lazily from the npz
        # store (one read per generator pull, bounded host memory) into the
        # same window/crop graph the direct-RLDS path uses
        # (rt1_tpu/data/rlds_pipeline.py). tf.data service with this loader
        # is limited to in-process/colocated workers (generator source);
        # use create_rlds_datasets + InGraphTableEmbedder for remote ones.
        from rt1_tpu.data.rlds_pipeline import (
            RldsPipelineConfig,
            make_episode_dataset_from_paths,
            windowed_rlds_dataset,
        )

        host_paths = paths[jax.process_index() :: jax.process_count()]
        cfg = RldsPipelineConfig(
            window=config.model.time_sequence_length,
            crop_factor=config.data.crop_factor,
            height=config.data.height,
            width=config.data.width,
            batch_size=config.per_host_batch_size,
            shuffle_buffer=config.data.shuffle_buffer,
            seed=stream_seed,
            data_service_address=config.data.get("data_service_address"),
        )
        tfds = windowed_rlds_dataset(
            make_episode_dataset_from_paths(host_paths), cfg,
            training=split == "train",
        )
        return iter(tfds.as_numpy_iterator())

    clip_tokenizer = None
    if config.data.get("clip_tokens", False):
        clip_tokenizer = _make_clip_tokenizer(config)

    if config.data.get("packed_cache", False):
        packed_iter = _packed_batches(
            config, split, paths, clip_tokenizer, seed=seed
        )
        if packed_iter is not None:
            return packed_iter
        # else: fall through to the tf.data/numpy path (warned inside).

    ds = WindowedEpisodeDataset(
        paths,
        window=config.model.time_sequence_length,
        crop_factor=config.data.crop_factor,
        height=config.data.height,
        width=config.data.width,
        clip_tokenizer=clip_tokenizer,
    )
    if config.data.loader == "tf":
        tfds = ds.as_tf_dataset(
            batch_size=config.per_host_batch_size,
            seed=stream_seed,
            shuffle_buffer=config.data.shuffle_buffer,
            process_index=jax.process_index(),
            process_count=jax.process_count(),
        )
        return iter(tfds.as_numpy_iterator())
    return ds.numpy_batches(
        batch_size=config.per_host_batch_size,
        seed=stream_seed,
        process_index=jax.process_index(),
        process_count=jax.process_count(),
    )


def _state_for_save(state):
    """The tree handed to Orbax at save time.

    Single process keeps the historical `jax.device_get` (host numpy —
    saves never hold device buffers while serializing). Multi-process
    hands over the sharded `jax.Array`s untouched: device_get of a
    dp/fsdp-sharded leaf would raise (this host cannot address the other
    hosts' shards), and Orbax's multihost path wants the global arrays —
    each host then writes exactly its own shard bytes.
    """
    if jax.process_count() == 1:
        return jax.device_get(state)
    return state


def train_and_evaluate(config, workdir: str):
    """Run the training loop; returns the final TrainState.

    Self-healing behavior (`config.resilience`, docs/resilience.md): with
    the guard on, non-finite updates are skipped on device and persistent
    divergence escalates to a checkpoint rollback with a fresh data-stream
    seed (bounded by a rollback budget, then GuardAbortError); with
    `preempt_save`, SIGTERM/SIGINT force-saves a checkpoint at the current
    step, drains the feeder, and returns normally (exit 0) so the next
    launch resumes exactly; with `io_retry`, checkpoint and packed-cache
    I/O retries with backoff before giving up. All of it is off by default
    for configs without a `resilience` block.
    """
    from rt1_tpu import obs, resilience

    # Multi-process rendezvous FIRST — before any device access (the plan
    # resolves against the global device set, and a post-backend-init
    # rendezvous is too late). No-op unless `config.parallel.distributed`
    # is enabled; idempotent across runs in one process.
    from rt1_tpu.parallel import describe_devices, initialize_from_config

    initialize_from_config(config)

    from absl import logging

    device = describe_devices()
    logging.info(
        "devices: platform=%s device_kind=%s count=%d",
        device["platform"], device["device_kind"], device["device_count"],
    )

    # Observability first: the tracer must be live before dataset_batches
    # spawns feeder workers, or their assembly spans are lost.
    obs_opts = obs.ObsOptions.from_config(config, workdir)
    if obs_opts.trace:
        obs.trace.enable(obs_opts.trace_path, obs_opts.trace_max_events)

    # Run-level goodput ledger (obs/goodput.py): everything from here to
    # the first loop step accrues to its "init" bucket (checkpoint restore
    # time is carved out into "ckpt_restore" via the manager's on_io hook).
    ledger = None
    if obs_opts.goodput:
        ledger = obs.GoodputLedger()
        ledger.open_phase("init")

    res_opts = resilience.ResilienceOptions.from_config(config)
    retry_opts = res_opts.retry_options()
    # Deterministic fault schedule (config string + RT1_FAULTS env) — the
    # chaos-run channel; None on production runs.
    fault_plan = resilience.faults.install_from(res_opts.faults)
    if fault_plan is not None:
        from absl import logging

        logging.warning(
            "resilience: fault plan armed: %s",
            sorted(fault_plan.fired_counts()),
        )
    step_guard = (
        resilience.StepGuard(res_opts.guard_options()) if res_opts.guard
        else None
    )

    writer = create_writer(workdir)

    _check_clip_token_config(config)
    # ONE plan resolution: mesh shape (dp × fsdp × tp × pp, or auto by
    # device count) + the declarative param layout, from `config.parallel`
    # (legacy `config.mesh` configs fall back transparently). The same
    # resolution runs in eval/restore.py and serve, so dense/fsdp/tp/pp are
    # config-only switches with no per-callsite spec plumbing.
    from rt1_tpu.parallel import ShardingPlan, mixed_precision_from_config

    sharding_plan = ShardingPlan.from_config(config)
    mesh = sharding_plan.mesh
    mixed_precision = mixed_precision_from_config(config)
    if mixed_precision and config.model.dtype != "bfloat16":
        from absl import logging

        # True mixed precision = bf16 compute against f32 masters; the
        # compute dtype must be bf16 for the step's cast to take effect
        # (masters, optimizer state, and checkpoints stay f32 regardless).
        logging.info(
            "parallel.mixed_precision: forcing model compute dtype "
            "bfloat16 (was %s); master params/opt state stay float32",
            config.model.dtype,
        )
        with config.unlocked():
            config.model.dtype = "bfloat16"
    # Recorded AFTER the mixed-precision dtype mutation so the hparams
    # describe the program that actually runs (model.dtype=bfloat16 under
    # parallel.mixed_precision, not the pre-mutation value).
    write_hparams(
        writer, dict(config.to_dict()) if hasattr(config, "to_dict") else {}
    )
    family = family_of(config.model)
    model, init_fn, loss_fn = build_family(config.model, mesh=mesh)
    data_size = sharding_plan.data_parallel_size
    # The batch the jitted step sees is GLOBAL: per-host rows × processes
    # (each host feeds its block, data/pipeline.py `put_global`). The
    # mesh's batch ways must divide it, and on a host-major mesh each
    # host's rows must map onto its own devices — per-host divisibility by
    # the per-host share of the batch axes.
    nproc = jax.process_count()
    global_batch = config.per_host_batch_size * nproc
    if nproc > 1 and data_size % nproc != 0:
        # Each host feeds only its own rows, so a batch shard must never
        # span hosts (and a batch-REPLICATED mesh, data_size < nproc,
        # cannot be fed per-host rows at all). Reject at the config seam
        # rather than deep inside the first prefetch's
        # make_array_from_process_local_data.
        raise ValueError(
            f"multi-process run: the mesh batch axes (data x fsdp = "
            f"{data_size} ways) must divide evenly across "
            f"{nproc} processes — give dp (or fsdp) a multiple of the "
            f"process count"
        )
    per_host_ways = data_size // nproc if nproc > 1 else data_size
    if config.per_host_batch_size % per_host_ways != 0:
        raise ValueError(
            f"per_host_batch_size={config.per_host_batch_size} must be "
            f"divisible by this host's share of the mesh batch axes "
            f"({per_host_ways} of data x fsdp = {data_size} ways)"
        )
    if mesh.shape["stage"] > 1:
        accum = max(int(config.get("accum_steps", 1)), 1)
        # Each accumulation microstep forwards batch/accum rows, sharded
        # over data — that is the batch pipeline_apply actually sees.
        shard_batch = global_batch // data_size // accum
        micro = config.model.get("pipeline_microbatches", 4)
        if shard_batch == 0 or shard_batch % micro != 0:
            raise ValueError(
                f"pipeline parallelism: per-data-shard per-accum-step batch "
                f"{shard_batch} (= global batch {global_batch} / "
                f"{data_size} data shards / {accum} accum steps) must be a "
                f"positive multiple of pipeline_microbatches={micro}"
            )

    if config.data.data_dir:
        train_iter = dataset_batches(config, "train")
        # Stamp the dataset's provenance (instruction embedder, env config)
        # next to the checkpoints, so eval can refuse a policy/embedder
        # mismatch (the embedding is the task specification).
        from rt1_tpu.data.collect import read_manifest

        manifest = read_manifest(config.data.data_dir)
        if manifest is not None and jax.process_index() == 0:
            os.makedirs(workdir, exist_ok=True)
            with open(os.path.join(workdir, "data_manifest.json"), "w") as f:
                json.dump(manifest, f, indent=2, sort_keys=True)
    else:
        train_iter = family.host_feed(config, config.seed)

    first = next(train_iter)
    # Model init must not see the feeder's per-task telemetry member — the
    # observation contract is the model's; the task ids exist only for the
    # jitted step's one-hot reduction (stripped there before the forward).
    example = (
        {
            k: v
            for k, v in first["observations"].items()
            if k != obs.health.TASK_ID_KEY
        },
        first["actions"],
    )

    tx = make_optimizer(
        learning_rate=config.learning_rate,
        milestones=config.lr_milestones,
        gamma=config.lr_gamma,
        steps_per_epoch=config.steps_per_epoch,
        grad_clip_norm=config.grad_clip_norm or None,
    )
    rng = jax.random.PRNGKey(config.seed)
    state = create_train_state(model, rng, example, tx, init_fn=init_fn)
    pretrained_encoder = config.model.get("pretrained_encoder")
    if pretrained_encoder:
        from rt1_tpu.trainer.checkpoints import latest_step

        if latest_step(os.path.join(workdir, "checkpoints")) is not None:
            # Resumed runs (incl. every DAgger extension) restore their
            # checkpoint immediately below — grafting first would be wasted
            # work and, worse, a false "grafted" provenance line in the log.
            pretrained_encoder = None
    if pretrained_encoder:
        # Hermetic substitute for the reference's ImageNet-pretrained tower
        # (film_efficientnet_encoder.py:376-425): graft a state-regression-
        # pretrained encoder (train/pretrain_vision.py) into the tokenizer
        # BEFORE restore — a resumed run's checkpoint still wins.
        from absl import logging

        from rt1_tpu.train.pretrain_vision import (
            graft_encoder_into_policy,
            load_encoder,
        )

        variables = {"params": state.params}
        if state.batch_stats:
            variables["batch_stats"] = state.batch_stats
        grafted = graft_encoder_into_policy(
            variables, load_encoder(pretrained_encoder)
        )
        state = state.replace(
            params=grafted["params"],
            batch_stats=grafted.get("batch_stats", state.batch_stats),
        )
        logging.info("grafted pretrained encoder from %s", pretrained_encoder)
    if jax.process_index() == 0:
        log_parameter_overview(
            state.params, os.path.join(workdir, "parameters.txt")
        )

    ckpt = CheckpointManager(
        CheckpointConfig(
            directory=os.path.join(os.path.abspath(workdir), "checkpoints"),
            # `or None` coerces legacy 0-means-keep-all configs; the config
            # itself now uses a placeholder (None = keep all) explicitly.
            max_to_keep=config.max_to_keep or None,
            save_interval_steps=config.checkpoint_every_steps,
            keep_period=config.keep_period,
            retry=retry_opts,
            on_io=ledger.note_io if ledger is not None else None,
        )
    )
    # Plan-migrating restore (parallel/reshard.py): the template carries
    # the CURRENT plan's target shardings, so a checkpoint saved under a
    # different mesh/plan (a bigger slice, dense vs fsdp) resumes directly
    # in this run's layout instead of relying on a layout coincidence.
    with startup.phase("restore"):
        state, initial_step = ckpt.restore_or_initialize(
            state, plan=sharding_plan
        )

    fns = make_train_step_fns(
        model, mesh, state, accum_steps=config.accum_steps, loss_fn=loss_fn,
        guard_nonfinite=res_opts.guard,
        guard_grad_norm_max=res_opts.guard_grad_norm_max,
        model_health=obs_opts.model_health,
        health_group_depth=obs_opts.health_group_depth,
        # Per-task telemetry: the feeder publishes its frozen task-id
        # table when it emits task ids (packed multi-task corpora with
        # model_health on); other sources leave the pack task-free.
        health_task_names=tuple(
            getattr(train_iter, "health_task_names", ()) or ()
        ),
        plan=sharding_plan,
        mixed_precision=mixed_precision,
        check_coverage=family.planned,
    )
    state = fns.shard_state(state)
    # What the runtime placed, read off the shards (not the plan): under
    # fsdp/tp every device holds less than the replicated total.
    from rt1_tpu.parallel.sharding import per_device_bytes

    placed = (state.params, state.opt_state)
    logging.info(
        "state placement: params+opt_state total_bytes=%d per_device_bytes=%s",
        sum(leaf.nbytes for leaf in jax.tree.leaves(placed)),
        json.dumps(per_device_bytes(placed), sort_keys=True),
    )

    if ledger is not None and obs_opts.goodput_mfu:
        # Arm the live MFU gauge: FLOPs per step from XLA cost analysis of
        # the LOWERED step program — avals only, so no second compile and
        # no extra device transfer. The gauge stays disarmed when the
        # analysis reports no FLOPs or the device has no known peak.
        with obs.trace.span("goodput_flops_estimate"):
            batch_tpl = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (first["observations"], first["actions"]),
            )
            rng_tpl = jax.ShapeDtypeStruct((2,), jnp.uint32)
            if fns.guarded:
                skips_tpl = jax.ShapeDtypeStruct((), jnp.int32)
                flops = obs.flops.train_step_flops(
                    fns.train_step, state, skips_tpl, batch_tpl, rng_tpl
                )
            else:
                flops = obs.flops.train_step_flops(
                    fns.train_step, state, batch_tpl, rng_tpl
                )
            ledger.set_flops_per_step(
                flops,
                peak_flops=obs.flops.peak_flops(device["device_kind"]),
                n_chips=jax.device_count(),
            )

    eval_iter = None
    if config.eval_every_steps:
        if config.data.data_dir:
            try:
                eval_iter = dataset_batches(config, "val")
            except FileNotFoundError:
                eval_iter = None
        else:
            eval_iter = family.host_feed(config, config.seed + 1)

    meter = ThroughputMeter(
        config.per_host_batch_size * jax.process_count(),
        initial_step=initial_step,
    )
    # Step wall-time attribution (wait_data/h2d/device_step/host + rolling
    # stall_pct) — always on: a handful of perf_counter reads per step.
    timeline = obs.StepTimeline(
        window=obs_opts.stall_window, sync=obs_opts.sync_timing
    )
    # Feeder-side gauges when the packed sample-ahead feeder is the source.
    feeder_stats = getattr(train_iter, "stats", None)
    # Flywheel corpus gauges (shards / freshness epoch / corpus size /
    # staleness): the feeder exposes them when it feeds from the packed
    # cache; rendered as rt1_flywheel_* on the scrape and flywheel/* in TB.
    flywheel_stats = getattr(train_iter, "flywheel_stats", None)

    recorder = None
    if obs_opts.flight_recorder:
        recorder = obs.FlightRecorder(
            obs_opts.flight_recorder_size, path=obs_opts.flight_recorder_path
        )
    coordinator = None
    if res_opts.preempt_save:
        # Preemption-safe shutdown: the first SIGTERM/SIGINT runs the dump
        # callbacks (the flight record survives preemption too) and sets a
        # flag the loop polls — the LOOP then force-saves, drains, and
        # returns (exit 0). The recorder's own die-with-dump handler is NOT
        # installed in this mode; a second signal restores the previous
        # handlers and re-raises, so a wedged drain still dies honestly.
        callbacks = []
        if recorder is not None:
            callbacks.append(lambda: recorder.dump(reason="preempt"))
        coordinator = resilience.PreemptionCoordinator(callbacks=callbacks)
        coordinator.install()
    elif recorder is not None:
        # SIGTERM chains to SIG_DFL (process dies there) — the host trace
        # must dump inside the handler or a terminated traced run loses it.
        recorder.install_sigterm(
            extra=obs.trace.dump if obs_opts.trace else None
        )

    # Opt-in Prometheus scrape target for the train process: renders the
    # latest written scalars + rolling timing/feeder gauges on demand —
    # scrape cost lands on the scraper's thread, not the step.
    latest_scalars: dict = {}
    metrics_server = None
    if obs_opts.prometheus_port >= 0 and jax.process_index() == 0:
        from absl import logging

        def _render_prometheus():
            scalars = dict(latest_scalars)
            scalars.update(timeline.scalars())
            if feeder_stats is not None:
                scalars.update(
                    {f"feeder/{k}": v for k, v in feeder_stats().items()}
                )
            # rt1_train_guard_* / rt1_train_retry_* / rt1_train_preempt_*:
            # live on every scrape, not only after a log step wrote them.
            if step_guard is not None:
                scalars.update(step_guard.counters())
            scalars.update(resilience.retry.counters())
            if coordinator is not None:
                scalars.update(coordinator.counters())
            if fault_plan is not None:
                scalars.update(fault_plan.counters())
            # rt1_train_goodput_*: live run-level wall-time partition +
            # MFU on every scrape (rt1_train_health_* ride in via
            # latest_scalars from the last log step).
            if ledger is not None:
                scalars.update(ledger.scalars())
            # rt1_train_compile_*: what the start-up log has measured so
            # far; a recompile in mid-run shows on the next scrape.
            scalars.update(startup.scalars())
            body = obs.prometheus.render_scalar_gauges(scalars)
            # rt1_flywheel_*: live corpus-growth gauges — a scrape during
            # an epoch shows the shard pickup the moment the feeder takes
            # it, independent of the log-step cadence.
            if flywheel_stats is not None:
                body += obs.prometheus.render_scalar_gauges(
                    flywheel_stats(), prefix="rt1_flywheel_"
                )
            return body

        metrics_server = obs.MetricsServer(
            _render_prometheus,
            host=obs_opts.prometheus_host,
            port=obs_opts.prometheus_port,
        )
        logging.info("obs: train metrics listener at %s", metrics_server.url)

    # Double-buffered device feed: H2D for step N+1 overlaps compute of
    # step N (uint8 images by default — 4x fewer bytes than float32).
    # `timeline.timed` charges time blocked on the host iterator to the
    # wait_data bucket; the rest of next(dev_iter) is the h2d bucket.
    import contextlib
    import itertools

    from rt1_tpu.data.pipeline import device_feeder

    def _host_stream(iterator, initial=()):
        """Wrap a host batch iterator for the device feed: fault injection
        (nan_batch site, indexed by batch ordinal within this stream) under
        the timeline's wait_data accounting. The model-init example batch
        is extracted BEFORE this wrapper, so a poisoned batch 0 can never
        leak NaNs into parameter initialization."""
        stream = itertools.chain(initial, iterator)
        plan = resilience.faults.active()
        if plan is not None:
            def _with_faults(inner):
                from absl import logging

                for i, b in enumerate(inner):
                    if plan.should_fire("nan_batch", index=i):
                        logging.warning(
                            "resilience: injected nan_batch at host batch "
                            "%d", i,
                        )
                        b = resilience.faults.poison_batch(b)
                    yield b

            stream = _with_faults(stream)
        return timeline.timed(stream)

    dev_iter = device_feeder(
        _host_stream(train_iter, initial=[first]),
        fns.batch_sharding,
        depth=2,
    )
    def _obs_teardown():
        # Runs on success AND on a loop exception (after the flight dump):
        # leaking any of these poisons the next run in this process — a
        # bound scrape port, a SIGTERM handler referencing a dead recorder,
        # a stale process-wide tracer swallowing the next enable().
        if metrics_server is not None:
            metrics_server.close()
        if coordinator is not None:
            coordinator.uninstall()
        if recorder is not None:
            recorder.uninstall_sigterm()
        if obs_opts.trace:
            from absl import logging

            # disable() dumps to obs_opts.trace_path and clears the
            # process-wide recorder, so back-to-back runs (tests, sweeps)
            # don't bleed spans into each other's traces.
            obs.trace.disable()
            logging.info(
                "obs: host trace written to %s", obs_opts.trace_path
            )

    crash_guard = (
        recorder.dump_on_exception()
        if recorder is not None
        else contextlib.nullcontext()
    )
    # The host iterator is rebound on rollback; close whichever is current
    # at exit (drains the sample-ahead feeder's worker threads).
    live_iter = {"host": train_iter}

    def _close_host_iter():
        closer = getattr(live_iter["host"], "close", None)
        if callable(closer):
            closer()

    def _write_goodput():
        # Success, crash, and preempt paths all leave a summary on disk —
        # run_report's post-mortem needs it most when the run died.
        if ledger is None or not obs_opts.goodput_summary_path:
            return
        if jax.process_index() != 0:
            return
        from absl import logging

        try:
            path = ledger.write_summary(
                obs_opts.goodput_summary_path, startup=startup.snapshot()
            )
            s = ledger.summary()
            logging.info(
                "obs: goodput summary at %s (goodput %.1f%%, badput %.1f%%"
                "%s)",
                path, s["goodput_pct"], s["badput_pct"],
                ", mfu %.2f%%" % s["mfu_pct"] if "mfu_pct" in s else "",
            )
        except Exception:  # noqa: BLE001 - accounting must not mask exits
            pass

    guard_skips = fns.init_guard_skips() if fns.guarded else None
    # Steps at or before this mark are post-rollback re-runs — badput the
    # ledger books as rollback_replay, not productive step time.
    replay_until = initial_step
    # The first call of the step traces, lowers and compiles it (or fetches
    # it from the persistent cache): the start-up log's last phase, and the
    # block is logged once it has closed.
    no_phase = contextlib.nullcontext()
    first_call = startup.phase("first_step", step=initial_step)
    cleanup = contextlib.ExitStack()
    cleanup.callback(_obs_teardown)
    cleanup.callback(_close_host_iter)
    if callable(getattr(eval_iter, "close", None)):
        cleanup.callback(eval_iter.close)
    cleanup.callback(_write_goodput)
    if ledger is not None:
        ledger.close_phase()  # init ends where the step loop begins
    with cleanup, crash_guard:
        step = initial_step
        while step < config.num_steps:
            if fault_plan is not None:
                # Self-delivered SIGTERM ("sigterm@<step>"): the chaos-run
                # stand-in for a scheduler preemption, handled exactly like
                # the real one (coordinator flag -> save-and-exit below).
                resilience.faults.maybe_signal("sigterm", index=step)
            timeline.start_step(step)
            # The XPlane step annotation spans the batch pull + the step,
            # as before this loop was instrumented — the device profiler's
            # per-step view must keep including input wait/H2D.
            with step_trace("train", step):
                with timeline.phase("h2d", exclusive_of="wait_data"):
                    batch = next(dev_iter)
                with timeline.phase("device_step"), first_call:
                    step_rng = jax.random.fold_in(rng, step)
                    if fns.guarded:
                        state, guard_skips, metrics = fns.train_step(
                            state, guard_skips, batch, step_rng
                        )
                    else:
                        state, metrics = fns.train_step(
                            state, batch, step_rng
                        )
            step_record = timeline.end_step(sync_on=metrics.get("loss"))
            if ledger is not None:
                ledger.note_step(step_record, replay=step < replay_until)
            if first_call is not no_phase:
                first_call = no_phase
                logging.info("%s", "\n".join(startup.block(startup.snapshot())))

            log_now = (step + 1) % config.log_every_steps == 0
            verdict = resilience.GuardVerdict.OK
            health_scalars = None
            if log_now:
                # The health pack is a vector — pop it before the per-key
                # scalar fetch (a mean over the pack is meaningless) and
                # unpack it against the step builder's name layout.
                health_vec = (
                    metrics.pop(obs.health.PACK_KEY, None)
                    if fns.health_names
                    else None
                )
                scalars = scalars_from_metrics(metrics)
                if health_vec is not None:
                    health_scalars = obs.health.unpack(
                        fns.health_names, health_vec
                    )
                    scalars.update(health_scalars)
                # The guard judges the scalars this loop already fetched —
                # its host-side cost at log steps is arithmetic on floats.
                if step_guard is not None:
                    verdict = step_guard.observe(step + 1, scalars)
                    scalars.update(step_guard.counters())
                scalars.update(meter.update(step + 1))
                scalars.update(timeline.scalars())
                if ledger is not None:
                    scalars.update(ledger.scalars())
                scalars.update(startup.scalars())
                if feeder_stats is not None:
                    scalars.update(
                        {
                            f"feeder/{k}": v
                            for k, v in feeder_stats().items()
                        }
                    )
                if flywheel_stats is not None:
                    scalars.update(
                        {
                            f"flywheel/{k}": v
                            for k, v in flywheel_stats().items()
                        }
                    )
                scalars.update(resilience.retry.counters())
                if coordinator is not None:
                    scalars.update(coordinator.counters())
                if fault_plan is not None:
                    scalars.update(fault_plan.counters())
                writer.write_scalars(step + 1, scalars)
                latest_scalars.update(scalars)
                latest_scalars["step"] = step + 1

            if recorder is not None:
                rec = {
                    k: v for k, v in step_record.items() if k != "step"
                }
                if log_now:
                    rec["loss"] = scalars.get("loss")
                    if health_scalars is not None:
                        rec["health"] = health_scalars
                    if step_guard is not None:
                        rec["guard"] = step_guard.counters()
                    retry_counters = resilience.retry.counters()
                    if retry_counters:
                        rec["retry"] = retry_counters
                if feeder_stats is not None:
                    rec["feeder"] = feeder_stats()
                recorder.record(step + 1, **rec)

            if verdict is resilience.GuardVerdict.ABORT:
                raise resilience.GuardAbortError(
                    f"guard: rollback budget "
                    f"({res_opts.guard_rollback_budget}) exhausted and "
                    f"training is still unhealthy at step {step + 1}: "
                    f"{step_guard.last_reason}"
                )
            if verdict is resilience.GuardVerdict.ROLLBACK:
                from absl import logging

                ckpt.wait_until_finished()
                target = ckpt.latest_step()
                if target is None:
                    raise resilience.GuardAbortError(
                        f"guard: training unhealthy at step {step + 1} "
                        f"({step_guard.last_reason}) with no checkpoint to "
                        f"roll back to (first save at step "
                        f"{config.checkpoint_every_steps})"
                    )
                logging.warning(
                    "resilience: guard ROLLBACK at step %d (%s) — "
                    "restoring checkpoint step %d with a fresh data seed",
                    step + 1, step_guard.last_reason, target,
                )
                state = ckpt.restore(state, step=target, plan=sharding_plan)
                step_guard.notify_rollback(target)
                # Fresh stream offset: re-walking the exact batch sequence
                # would reproduce the divergence deterministically.
                fresh_seed = config.seed + 7919 * step_guard.rollbacks
                _close_host_iter()
                if config.data.data_dir:
                    train_iter = dataset_batches(
                        config, "train", seed=fresh_seed
                    )
                else:
                    train_iter = family.host_feed(config, fresh_seed)
                live_iter["host"] = train_iter
                feeder_stats = getattr(train_iter, "stats", None)
                flywheel_stats = getattr(train_iter, "flywheel_stats", None)
                dev_iter = device_feeder(
                    _host_stream(train_iter), fns.batch_sharding, depth=2
                )
                obs.trace.counter("guard_rollbacks", step_guard.rollbacks)
                if ledger is not None:
                    ledger.mark_rollback()
                # Everything up to the step we just abandoned is now a
                # re-run — the ledger books it as rollback_replay badput.
                replay_until = max(replay_until, step + 1)
                step = target
                continue

            if (
                eval_iter is not None
                and (step + 1) % config.eval_every_steps == 0
            ):
                losses = []
                for _ in range(config.eval_batches):
                    ev = next(eval_iter)
                    ev_metrics = fns.eval_step(
                        state,
                        fns.shard_batch((ev["observations"], ev["actions"])),
                    )
                    losses.append(scalars_from_metrics(ev_metrics)["loss"])
                writer.write_scalars(
                    step + 1, {"eval_loss": float(np.mean(losses))}
                )

            last = step + 1 == config.num_steps
            saved = False
            if last or (step + 1) % config.checkpoint_every_steps == 0:
                # device_get only on save steps: the full-state D2H copy
                # would otherwise sync the host every step and kill the
                # prefetch overlap. Trace-span only, NOT a timeline bucket:
                # this runs between steps, and folding multi-second saves
                # into the next step's host bucket would make its buckets
                # exceed its total. Multi-process: NO device_get — a host
                # cannot materialize other hosts' fsdp/dp shards; Orbax
                # takes the sharded jax.Arrays and each host writes its own
                # shard files.
                with obs.trace.span("checkpoint_save", step=step + 1):
                    saved = ckpt.save(
                        step + 1, _state_for_save(state), force=last
                    )

            if coordinator is not None and coordinator.triggered:
                from absl import logging

                logging.warning(
                    "resilience: preemption signal %s — force-saving step "
                    "%d, draining the feeder, exiting 0",
                    coordinator.signum, step + 1,
                )
                if ledger is not None:
                    ledger.mark_preempted()
                drain_cm = (
                    ledger.phase("preempt_drain")
                    if ledger is not None
                    else contextlib.nullcontext()
                )
                # The force-save inside the drain is carved out into the
                # ckpt_save bucket by note_io's phase steal.
                with drain_cm:
                    if not saved:
                        with obs.trace.span("preempt_save", step=step + 1):
                            ckpt.save(
                                step + 1, _state_for_save(state), force=True
                            )
                    _close_host_iter()
                break

            step += 1

    ckpt.wait_until_finished()
    writer.flush()
    memory = jax.local_devices()[0].memory_stats()
    if memory and "peak_bytes_in_use" in memory:  # None on the CPU backend
        logging.info(
            "device memory: peak_bytes_in_use=%d stats=%s",
            memory["peak_bytes_in_use"], json.dumps(memory, sort_keys=True),
        )
    # Refresh the summary the cleanup stack already wrote: the async final
    # checkpoint's wait and the teardown itself belong in the totals.
    _write_goodput()
    return state


def apply_sweep_trial(config, config_module, trial: int):
    """Apply trial `trial` of the config module's `sweep()` (the open
    equivalent of the reference's `get_hyper` hook,
    `configs/language_table_sim_local.py:84-89`) onto `config` in place."""
    trials = config_module.sweep()
    if not 0 <= trial < len(trials):
        raise ValueError(f"--sweep_trial {trial} out of range [0, {len(trials)})")
    overrides = trials[trial]
    with config.unlocked():
        config.update_from_flattened_dict(overrides)
    return overrides


def main(argv):
    del argv
    import importlib.util

    from absl import flags, logging
    from ml_collections import config_flags

    from rt1_tpu import compilation_cache

    # A cold flagship train-step compile costs minutes on the chip; cached,
    # a relaunch (resume, preemption restart) starts in seconds.
    compilation_cache.enable_persistent_cache()

    FLAGS = flags.FLAGS
    config = FLAGS.config
    if FLAGS.sweep_trial >= 0:
        module_name = config_flags.get_config_filename(FLAGS["config"])
        spec = importlib.util.spec_from_file_location("sweep_cfg", module_name)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        if not hasattr(mod, "sweep"):
            raise ValueError(f"{module_name} defines no sweep()")
        overrides = apply_sweep_trial(config, mod, FLAGS.sweep_trial)
        logging.info("sweep trial %d: %s", FLAGS.sweep_trial, overrides)
    train_and_evaluate(config, FLAGS.workdir)


if __name__ == "__main__":
    from absl import app, flags
    from ml_collections import config_flags

    config_flags.DEFINE_config_file("config", None, "Config file.", lock_config=True)
    flags.DEFINE_string("workdir", "/tmp/rt1_tpu", "Work/output directory.")
    flags.DEFINE_integer(
        "sweep_trial", -1,
        "If >= 0, apply this trial of the config module's sweep() before "
        "training (one process per trial).")
    flags.mark_flags_as_required(["config"])
    app.run(main)

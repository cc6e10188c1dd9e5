"""Flagship config: RT-1 on Language-Table blocktoblock_sim.

Hyperparameters mirror the reference's implied throughput baseline
(`distribute_train.py:269-295` + SURVEY.md §2.1): batch 8/chip, seq_len 6,
256x456 images, lr 5e-4 with MultiStepLR [50, 75, 90] gamma 0.1, 100 epochs
over 7800 train episodes, vocab 256, 8 layers, TokenLearner with 8 tokens.
"""

import ml_collections


def get_config():
    config = ml_collections.ConfigDict()

    # Model (SURVEY.md §2.1 instantiation).
    config.model = ml_collections.ConfigDict()
    config.model.family = "rt1"  # "rt1" | "lava" (Stack A vs Stack B)
    config.model.vocab_size = 256
    config.model.token_embedding_size = 512
    config.model.num_layers = 8
    config.model.layer_size = 128
    config.model.num_heads = 8
    config.model.feed_forward_size = 512
    config.model.dropout_rate = 0.1
    config.model.time_sequence_length = 6
    config.model.use_token_learner = True
    config.model.num_image_tokens = 8
    config.model.image_tokenizer = "efficientnet_b3"
    config.model.dtype = "bfloat16"
    config.model.photometric_augmentation = False
    # Focal CE modulation (models/rt1.py): 0 = reference parity; > 0 fights
    # the BC marginal-collapse ("copycat") failure on smooth oracle demos.
    config.model.focal_gamma = 0.0
    # Soft-argmax MSE auxiliary (models/rt1.py): dense regression gradient
    # that bypasses the token-CE marginal plateau. 0 = reference parity.
    config.model.aux_mse_weight = 0.0
    # Inference decode: "argmax" (reference parity) | "expected" (soft E[a]).
    config.model.action_decode = "argmax"
    # jax.checkpoint the transformer + MBConv blocks: ~1/3 extra FLOPs for
    # O(1) activation memory — turn on when HBM, not compute, caps batch.
    config.model.remat = False
    # Attention implementation: "dense" (reference parity) or "pallas"
    # (fused inference kernel).
    # The pallas kernel is forward-only (no autodiff rule): under "pallas"
    # the train step still runs the dense math and only inference/serving
    # runs the kernel, which needs a TPU (it raises elsewhere).
    config.model.attention_impl = "dense"
    # GPipe microbatches per step when mesh.stage > 1 (parallel/pipeline.py).
    config.model.pipeline_microbatches = 4
    # Path to a state-regression-pretrained encoder (train/pretrain_vision
    # .py::save_encoder) grafted into the tokenizer at initialization — the
    # hermetic stand-in for the reference's ImageNet-pretrained B3 tower
    # (film_efficientnet_encoder.py:376-425). None = train from scratch.
    config.model.pretrained_encoder = ml_collections.config_dict.placeholder(
        str
    )

    # LAVA family fields (used when family == "lava"; defaults mirror the
    # reference's SequenceLAVMSE config, `train/configs/
    # language_table_sim_local.py:27-49`).
    config.model.lava = ml_collections.ConfigDict()
    config.model.lava.action_size = 2
    config.model.lava.d_model = 128
    config.model.lava.num_layers = 2
    config.model.lava.temporal_num_layers = 2
    config.model.lava.num_heads = 2
    config.model.lava.pyramid_fuse_layers = (2, 3, 4)
    config.model.lava.image_encoder = "conv_maxpool"
    config.model.lava.lang_encoder = "embedding_in_obs"
    config.model.lava.dense_resnet_width = 256
    config.model.lava.dense_resnet_num_blocks = 8
    # In-graph CLIP text tower dims (lang_encoder == "clip"). Defaults match
    # the byte-level `clip_bpe.default_tokenizer` vocab (514); for public
    # OpenAI weights use vocab 49408 / width 512 / 12 layers / 8 heads and
    # the real merges file.
    config.model.lava.text_vocab = 514
    config.model.lava.text_context = 77
    config.model.lava.text_width = 512
    config.model.lava.text_layers = 12
    config.model.lava.text_heads = 8
    config.model.lava.text_embed_dim = 512

    # Data.
    config.data = ml_collections.ConfigDict()
    config.data.data_dir = ""  # empty -> synthetic random batches (smoke)
    config.data.height = 256
    config.data.width = 456
    config.data.crop_factor = 0.95
    # "tf": numpy_function-backed local pipeline; "rlds_tf": pure-TF graph
    # (tf.data-service-distributable); "numpy": dependency-free iterator.
    config.data.loader = "tf"
    config.data.shuffle_buffer = 2048
    # Emit "instruction_tokenized_clip" observations (CLIP BPE over the
    # stored instruction text) for the LAVA "clip" language encoder.
    config.data.clip_tokens = False
    # Path to CLIP's bpe_simple_vocab_16e6.txt(.gz) merges. None -> the
    # byte-level fallback tokenizer (model.lava.text_vocab must then be 514;
    # with the real merges use 49408).
    config.data.clip_bpe_path = ml_collections.config_dict.placeholder(str)
    # tf.data service endpoint for distributed preprocessing with the
    # "rlds_tf" loader (reference input_pipeline_rlds.py:307-317); None =
    # process batches locally.
    config.data.data_service_address = ml_collections.config_dict.placeholder(str)
    # Packed mmap frame cache (rt1_tpu/data/pack.py): feed training from
    # pre-decoded frames at augmentation-headroom resolution via the
    # sample-ahead feeder instead of the tf.data decode+crop path. Build
    # the cache offline with scripts/pack_dataset.py; a missing/stale cache
    # falls back to the tf.data path with a warning. Incompatible with
    # loader="rlds_tf".
    config.data.packed_cache = False
    # Override the cache location (default: <data_dir>/<split>_packed).
    config.data.packed_cache_dir = ml_collections.config_dict.placeholder(str)
    # Sample-ahead feeder shape: background assembly threads and the
    # per-thread ready-batch queue depth (total sample-ahead =
    # threads * depth batches).
    config.data.feeder_threads = 2
    config.data.feeder_depth = 2
    # Consumer-side stall diagnosis: if the train loop waits this long for
    # a feeder batch it raises FeederStalledError naming which workers are
    # alive and the queue depths, instead of blocking forever on a worker
    # that deadlocked without raising. None = wait indefinitely.
    config.data.feeder_stall_timeout_s = ml_collections.config_dict.placeholder(
        float
    )
    # Data flywheel (docs/data.md "Sharded pack format v2 & the
    # flywheel"): at every epoch boundary the train feeder re-reads the
    # pack manifest and picks up shards appended by
    # `scripts/pack_dataset.py --append` (serve-captured episodes) without
    # a restart; `flywheel/*` scalars + rt1_flywheel_* gauges track shard
    # count, corpus size, and staleness. Costs one manifest read per data
    # epoch when nothing changed.
    config.data.packed_refresh = True
    # Task-mixture sampling over the packed corpus (docs/data.md "Task
    # mixture & per-task telemetry"): "task:weight,..." per-task sampling
    # weights, e.g. "block2block:3,block1_to_corner:1,*:0.5" ("*" = every
    # task not named; "unknown" matches untagged legacy episodes). Empty =
    # off — the bit-identical pre-task uniform shuffle. Weighted epochs
    # sample windows with replacement (p ∝ weight of the window's task),
    # still a pure function of (seed, epoch, corpus, weights).
    config.data.task_weights = ""

    # Training schedule (reference: 100 epochs x 975 steps at batch 8).
    config.per_host_batch_size = 8
    config.num_steps = 97_500
    config.steps_per_epoch = 975
    config.learning_rate = 5e-4
    config.lr_milestones = (50, 75, 90)  # epochs
    config.lr_gamma = 0.1
    config.grad_clip_norm = 0.0  # 0 disables (reference has none)
    config.accum_steps = 1
    config.seed = 42

    # Parallelism plan (rt1_tpu/parallel/plan.py, docs/parallelism.md): the
    # dp × fsdp × tp × pp mesh shape plus the declarative param layout, all
    # config-only switches — train, eval, and serve resolve this block
    # identically. -1 dp = all remaining local devices. (Replaces the old
    # `config.mesh` block: data→dp, model→tp, stage→pp; legacy
    # configs with a `mesh` block still resolve via the same fallback.)
    config.parallel = ml_collections.ConfigDict()
    config.parallel.dp = -1
    # ZeRO-3 weight sharding: batch shards over dp×fsdp, weight matrices /
    # optimizer masters shard one dim over fsdp.
    config.parallel.fsdp = 1
    # Tensor parallelism (attention heads / FFN columns; the lfm2_moe
    # family's experts).
    config.parallel.tp = 1
    # Pipeline stages (GPipe over the decoder's layer stack); num_layers
    # must be divisible by this.
    config.parallel.pp = 1
    # Pick (dp, fsdp, tp) automatically from the device count
    # (plan.AUTO_MESH_SHAPES); pp still honored as configured.
    config.parallel.auto = False
    # Plan-coverage strictness: True turns the "weight matrix matched no
    # rule" warning into a hard error at step-build time.
    config.parallel.strict = False
    # True mixed precision: f32 master params + optimizer state, one bf16
    # cast of params inside the jitted step for fwd/bwd (forces the model
    # compute dtype to bfloat16; f32 softmax/CE unchanged). Off = the
    # bit-identical pre-change f32 program.
    config.parallel.mixed_precision = False
    # Multi-process (multi-host) scale-out (rt1_tpu/parallel/distributed
    # .py, docs/parallelism.md "Multi-host"): with `enabled`, the train
    # entry runs `jax.distributed.initialize` BEFORE any device access, so
    # the plan resolves against the slice's global devices, per-host
    # feeders slice the global stream, and Orbax coordinates multihost
    # checkpoints. One config serves every host: leave process_id /
    # num_processes at -1 and set RT1_COORDINATOR / RT1_PROCESS_ID /
    # RT1_NUM_PROCESSES per host (or nothing at all on TPU pods — the
    # runtime reads the metadata server).
    config.parallel.distributed = ml_collections.ConfigDict()
    config.parallel.distributed.enabled = False
    config.parallel.distributed.coordinator_address = (
        ml_collections.config_dict.placeholder(str)
    )
    config.parallel.distributed.process_id = -1
    config.parallel.distributed.num_processes = -1

    # Observability (rt1_tpu/obs/, docs/observability.md). Defaults are
    # resolved by obs.ObsOptions.from_config, so configs without this block
    # (pinned proof configs) keep working.
    config.obs = ml_collections.ConfigDict()
    # Host-side Chrome-trace recording (train loop + feeder workers + H2D
    # in one Perfetto timeline); dumped to obs.trace_path at exit.
    config.obs.trace = False
    config.obs.trace_path = ml_collections.config_dict.placeholder(str)
    config.obs.trace_max_events = 200_000
    # Rolling window (steps) for the stall_pct gauge / timing buckets.
    config.obs.stall_window = 50
    # Block on each step's output for exact device_step attribution —
    # diagnosis mode; costs one host sync per step.
    config.obs.sync_timing = False
    # >= 0: serve Prometheus text on http://<host>:<port>/metrics from the
    # train process (0 = ephemeral port, logged at startup). < 0: off.
    config.obs.prometheus_port = -1
    config.obs.prometheus_host = "127.0.0.1"
    # Flight recorder: ring of the last N step records (timing buckets,
    # feeder queue depths, loss at log steps), dumped to JSONL on an
    # unhandled exception or SIGTERM.
    config.obs.flight_recorder = True
    config.obs.flight_recorder_size = 256
    config.obs.flight_recorder_path = ml_collections.config_dict.placeholder(
        str
    )
    # Model-health pack (obs/health.py): per-layer-group gradient norms,
    # post-optimizer update/param ratios, logit entropy, and per-dimension
    # token accuracy, computed inside the jitted step and fetched at log
    # steps (health/* scalars, rt1_train_health_* gauges). Measured
    # overhead on the packed tiny e2e bench is within the <=2% budget
    # (bench.py --health); off = bit-identical pre-health step program.
    config.obs.model_health = True
    # Param-tree path depth for health layer groups (2 = per decoder layer).
    config.obs.health_group_depth = 2
    # Run-level goodput ledger (obs/goodput.py): wall-time partition into
    # init/compile/step/data_stall/ckpt/rollback/preempt buckets, goodput/*
    # scalars + rt1_train_goodput_* gauges + <workdir>/goodput_summary.json
    # (merged into a post-mortem by scripts/run_report.py).
    config.obs.goodput = True
    config.obs.goodput_summary_path = ml_collections.config_dict.placeholder(
        str
    )
    # Live MFU gauge from XLA cost analysis of the lowered step (no second
    # compile; one extra trace of the step at startup).
    config.obs.goodput_mfu = True

    # Resilience (rt1_tpu/resilience/, docs/resilience.md). Defaults are
    # resolved by resilience.ResilienceOptions.from_config with everything
    # OFF, so configs without this block (pinned proof configs) keep the
    # exact pre-resilience loop; this flagship config turns the self-healing
    # paths on.
    config.resilience = ml_collections.ConfigDict()
    # Step guard: device-side non-finite update skip + host-side escalation
    # (skip -> checkpoint rollback with a fresh data seed -> abort).
    config.resilience.guard = True
    # > 0: also skip updates whose global grad-norm exceeds this (a
    # train-wrecking spike that is still finite). 0 = finiteness only.
    config.resilience.guard_grad_norm_max = 0.0
    # > 0: flag loss > factor * EMA(healthy losses) at log steps. 0 = off
    # (early-training loss cliffs make a universal default unsafe).
    config.resilience.guard_loss_spike_factor = 0.0
    config.resilience.guard_spike_ema_beta = 0.9
    config.resilience.guard_warmup_checks = 3
    # Consecutive bad log-step checks tolerated before rolling back.
    config.resilience.guard_skip_budget = 3
    # Rollbacks allowed before the run aborts (GuardAbortError).
    config.resilience.guard_rollback_budget = 2
    # Exponential-backoff retry on the I/O seams: checkpoint save/restore,
    # packed-cache open, feeder construction.
    config.resilience.io_retry = True
    config.resilience.retry_attempts = 3
    config.resilience.retry_backoff_s = 0.5
    config.resilience.retry_max_backoff_s = 8.0
    config.resilience.retry_deadline_s = 120.0
    # SIGTERM/SIGINT -> force-save at the current step, drain the feeder,
    # exit 0 (the preemption-resume path); a second signal escalates to the
    # previous handler (flight-recorder dump + die).
    config.resilience.preempt_save = True
    # Deterministic fault schedule for chaos runs/tests (resilience/faults
    # .py grammar, e.g. "nan_batch@7,ckpt_save@2"); RT1_FAULTS env appends.
    config.resilience.faults = ""

    # Checkpoint / logging cadence.
    config.checkpoint_every_steps = 975
    config.keep_period = 9750
    # None -> keep all checkpoints (reference save_top_k=-1). Set an int to
    # bound retention; keep_period still pins every Nth step.
    config.max_to_keep = ml_collections.config_dict.placeholder(int)
    config.log_every_steps = 50
    config.eval_every_steps = 975
    config.eval_batches = 6

    return config


def sweep():
    """Hyperparameter sweep hook (the open equivalent of the reference's
    `get_hyper` product-sweep, `configs/language_table_sim_local.py:84-89`):
    a list of {dotted-config-key: value} override dicts, one trial each.
    Apply with `config.update_from_flattened_dict(overrides)` or pass as
    `--config.<key>=<value>` CLI overrides per trial."""
    return [
        {"learning_rate": lr, "seed": seed}
        for lr in (1e-3, 5e-4, 1e-4)
        for seed in (42,)
    ]

"""rt1_tpu — a TPU-native (JAX/XLA/Flax/pjit) robotics-transformer framework.

Brand-new implementation of everything `tanhuajie/Pytorch-RT1-for-Distributed-Training`
provides — the RT-1 policy network (FiLM-EfficientNet-B3 image tokenizer, TokenLearner,
discretized action tokenizer, causal decoder transformer), an SPMD data-parallel /
FSDP / tensor-parallel trainer for Language-Table `blocktoblock_sim`, the RLDS→numpy
data path, and a closed-loop evaluation harness on the Language-Table simulator —
re-designed TPU-first:

* one `jax.sharding.Mesh`, `jit`-with-shardings everywhere; gradient reduction is an
  XLA `psum` over ICI instead of NCCL allreduce (reference: Lightning DDPStrategy,
  `distribute_train.py:235`).
* static shapes + `lax.scan`/`lax.cond` control flow so every hot path lives in one
  compiled XLA program (reference runs a Python loop of 3 transformer calls per
  control step, `transformer_network.py:246-268`; we compute all action tokens in a
  single pass — provably equivalent because action tokens are zeroed at input
  assembly, `transformer_network.py:383`).
* NHWC image layouts, bfloat16 matmul compute with fp32 params, fused XLA image
  preprocessing on device.

Package map (subpackage → reference counterpart):
  models/    ← pytorch_robotics_transformer/ (transformer_network.py, transformer.py,
               tokenizers/, film_efficientnet/)
  ops/       ← film_efficientnet/preprocessors.py + attention primitives
  parallel/  ← Lightning DDP / NCCL layer (distribute_train.py:235) → Mesh + shardings
  train/     ← distribute_train.py + language_table/train/{train,bc}.py
  data/      ← rlds_np_convert.py + load_np_dataset.py + input_pipeline_rlds.py
  envs/      ← language_table/environments/
  eval/      ← language_table/eval/ + language_table/train/policy.py
"""

__version__ = "0.1.0"

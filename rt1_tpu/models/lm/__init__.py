"""Decoder language models built from a block description (spec.py).

One family so far, ``lfm2_moe``: gated short convolutions and grouped-query
attention as mixers, SwiGLU and sigmoid-routed experts as feed-forward
layers (docs/lm_family.md).
"""

from rt1_tpu.models.lm.model import DecoderLM, make_lm_step_loss_fn  # noqa: F401
from rt1_tpu.models.lm.spec import BlockSpec, LMSpec  # noqa: F401

"""Decoder language models built from a block description (spec.py).

Two families (rt1_tpu/train/families.py): ``lfm2_moe``, gated short
convolutions and grouped-query attention as mixers, SwiGLU and sigmoid-routed
experts as feed-forward layers, a tied head; ``mellum``, sliding-window and
full attention with a rotary rule per kind of layer, softmax-routed experts in
every layer, an untied head (docs/lm_family.md).
"""

from rt1_tpu.models.lm.model import DecoderLM, make_lm_step_loss_fn  # noqa: F401
from rt1_tpu.models.lm.spec import BlockSpec, LMSpec  # noqa: F401

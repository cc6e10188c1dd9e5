"""One chip's share of LFM2-24B-A2B (model_type ``lfm2_moe``), trained
through the same CLI as the other families:

  python -m rt1_tpu.train.train --config rt1_tpu/train/configs/lfm2_moe.py \
      --workdir /tmp/lfm2

``config.model.lm`` carries the published config.json's keys
(https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json) at their
published values, except the four that describe the cut (docs/lm_family.md):
the depth (one leading dense layer and one whole period of the layer
pattern; the other 35 layers lie on further pipeline stages), and this chip's
share of an 8-chip expert- and vocabulary-parallel group: ``experts_held``
8 of the 64 routed experts, ``vocab_held`` 8192 of the 65536 embedding rows.
Every key is an override (``--config.model.lm.hidden_size=64 ...``).
"""

import ml_collections

from rt1_tpu.train.configs import language_table

sweep = language_table.sweep


def get_config():
    config = language_table.get_config()
    config.model.family = "lfm2_moe"
    config.model.dtype = "bfloat16"

    lm = ml_collections.ConfigDict()
    # -- published widths and constants
    lm.hidden_size = 2048
    lm.num_attention_heads = 32
    lm.num_key_value_heads = 8
    lm.head_dim = 64                      # hidden_size / heads (not in the config.json)
    lm.intermediate_size = 11776
    lm.moe_intermediate_size = 1536
    lm.num_experts = 64                   # the router's width
    lm.num_experts_per_tok = 4
    lm.conv_L_cache = 3
    lm.rope_theta = 1000000.0
    lm.norm_eps = 1e-5
    lm.norm_topk_prob = True
    lm.routed_scaling_factor = 1.0
    lm.use_expert_bias = True
    lm.vocab_size = 65536
    # -- the cut in depth (published: 40 layers, 2 dense)
    lm.num_hidden_layers = 5
    lm.num_dense_layers = 1
    lm.layer_types = ("conv", "full_attention", "conv", "conv", "conv")
    # -- this chip's share of a layer
    lm.experts_held = (0, 8)              # (first, count)
    lm.vocab_held = 8192
    # -- the job
    lm.seq_len = 8192
    # synthetic packed documents (rt1_tpu/data/tokens.py)
    lm.corpus_seed = 20240801
    lm.corpus_documents = 4096
    lm.doc_len_median = 1024
    lm.doc_len_sigma = 1.0
    lm.doc_len_min = 16
    config.model.lm = lm

    config.per_host_batch_size = 2
    config.obs.model_health = True
    config.resilience.guard = True
    return config

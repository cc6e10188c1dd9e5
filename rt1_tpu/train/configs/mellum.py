"""One chip's share of Mellum2-12B-A2.5B-Instruct (model_type ``mellum``),
trained through the same CLI as the other families:

  python -m rt1_tpu.train.train --config rt1_tpu/train/configs/mellum.py \
      --workdir /tmp/mellum

``config.model.lm`` carries the published config.json's keys
(https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json)
at their published values, except those that describe the cut
(docs/lm_family.md): the depth (layers 0-3 of the published list, one whole
period sliding, sliding, sliding, full; the other 24 layers lie on further
pipeline stages), and this chip's share of a 4-chip expert- and
vocabulary-parallel group: ``experts_held`` 16 of the 64 routed experts,
``vocab_held`` 24576 of the 98304 rows of the embedding and of the untied head.
Every key is an override (``--config.model.lm.hidden_size=64 ...``).
"""

import ml_collections

from rt1_tpu.train.configs import language_table

sweep = language_table.sweep


def get_config():
    config = language_table.get_config()
    config.model.family = "mellum"
    config.model.dtype = "bfloat16"

    lm = ml_collections.ConfigDict()
    # -- published widths and constants
    lm.hidden_size = 2304
    lm.num_attention_heads = 32
    lm.num_key_value_heads = 4
    lm.head_dim = 128
    lm.intermediate_size = 7168           # published; no layer here is dense
    lm.moe_intermediate_size = 896
    lm.num_experts = 64                   # the router's width
    lm.num_experts_per_tok = 8
    lm.norm_topk_prob = True
    lm.rms_norm_eps = 1e-6
    lm.sliding_window = 1024
    lm.tie_word_embeddings = False
    lm.vocab_size = 98304
    lm.rope_parameters = ml_collections.ConfigDict({
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000.0, "factor": 16.0,
            "original_max_position_embeddings": 8192, "beta_fast": 32.0, "beta_slow": 1.0,
            "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000.0},
    })
    # -- what the config.json has no key for (benchmarks/configs/mellum2-12b-a2.5b.json,
    #    ``assumed``): softmax scores before the top-k; every layer routed
    lm.scoring_func = "softmax"
    lm.num_dense_layers = 0               # mlp_layer_types: every layer "sparse"
    # -- the cut in depth (published: 28 layers, the period below seven times)
    lm.num_hidden_layers = 4
    lm.layer_types = ("sliding_attention", "sliding_attention", "sliding_attention",
                      "full_attention")
    # -- this chip's share of a layer
    lm.experts_held = (0, 16)             # (first, count)
    lm.vocab_held = 24576
    # -- the job
    lm.seq_len = 16384
    # synthetic packed documents (rt1_tpu/data/tokens.py)
    lm.corpus_seed = 20240801
    lm.corpus_documents = 4096
    lm.doc_len_median = 2048
    lm.doc_len_sigma = 1.0
    lm.doc_len_min = 16
    config.model.lm = lm

    config.per_host_batch_size = 1
    config.learning_rate = 1e-5
    config.obs.model_health = True
    config.resilience.guard = True
    return config

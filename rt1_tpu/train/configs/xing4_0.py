"""One chip's share of Xing4.0-29B-A4B (model_type ``xing4_0``), trained
through the same CLI as the other families:

  python -m rt1_tpu.train.train --config rt1_tpu/train/configs/xing4_0.py \
      --workdir /tmp/xing

``config.model.lm`` carries the published config.json's keys
(https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json)
at their published values, except those that describe the cut
(docs/lm_family.md): the depth (one leading dense layer and four routed ones;
the other 35 layers lie on further pipeline stages), and this chip's share of
an 8-chip group: ``experts_held`` 8 of the 64 routed experts (expert-parallel
8), ``vocab_held`` 16384 of the 131072 rows of the embedding and of the untied
head (vocabulary-parallel 8), ``heads_held`` 4 of the 32 heads (attention
tensor-parallel 8: the group's 8 chips share a layer's heads).  The program's names for three published
keys: ``num_experts`` is ``n_routed_experts`` (the router's width),
``num_dense_layers`` is ``first_k_dense_replace``, ``rms_norm_eps`` as it is;
``layer_types`` is the program's (the config.json has none: every layer is
latent attention) and the multi-token-prediction block is not one of them.
Every key is an override (``--config.model.lm.hidden_size=64 ...``).
"""

import ml_collections

from rt1_tpu.train.configs import language_table

sweep = language_table.sweep


def get_config():
    config = language_table.get_config()
    config.model.family = "xing4_0"
    config.model.dtype = "bfloat16"

    lm = ml_collections.ConfigDict()
    # -- published widths and constants
    lm.hidden_size = 3584
    lm.num_attention_heads = 32
    lm.num_key_value_heads = 32
    lm.q_lora_rank = 768
    lm.kv_lora_rank = 512
    lm.qk_nope_head_dim = 128
    lm.qk_rope_head_dim = 64
    lm.v_head_dim = 128
    lm.intermediate_size = 9216
    lm.moe_intermediate_size = 1024
    lm.num_experts = 64                   # n_routed_experts: the router's width
    lm.n_shared_experts = 1
    lm.num_experts_per_tok = 4
    lm.norm_topk_prob = True
    lm.routed_scaling_factor = 2.0
    lm.scoring_func = "sigmoid"
    lm.use_expert_bias = True             # topk_method noaux_tc: a selection bias
    lm.rms_norm_eps = 1e-6
    lm.rope_theta = 10000.0
    lm.rope_scaling = ml_collections.ConfigDict({
        "type": "yarn", "factor": 64.0, "original_max_position_embeddings": 4096,
        "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0, "mscale_all_dim": 1.0})
    lm.hc_mult = 4
    lm.hc_sinkhorn_iters = 20
    lm.hc_eps = 1e-6
    lm.mhc_h_res_clamp_min = -30.0
    lm.mhc_h_res_clamp_max = 30.0
    lm.num_nextn_predict_layers = 1
    lm.tie_word_embeddings = False
    lm.vocab_size = 131072
    # -- what the config.json has no key for (benchmarks/configs/xing4.0-29b-a4b.json,
    #    ``assumed``): the weight of the second loss term
    lm.mtp_loss_weight = 0.3
    # -- the cut in depth (published: 40 layers, the first 2 dense)
    lm.num_hidden_layers = 5
    lm.num_dense_layers = 1               # first_k_dense_replace
    lm.layer_types = ("latent_attention",) * 5
    # -- this chip's share of a layer
    lm.experts_held = (0, 8)              # (first, count)
    lm.heads_held = (0, 4)                # (first, count)
    lm.vocab_held = 16384
    # -- the job
    lm.seq_len = 8192
    # synthetic packed documents (rt1_tpu/data/tokens.py)
    lm.corpus_seed = 20240801
    lm.corpus_documents = 4096
    lm.doc_len_median = 1024
    lm.doc_len_sigma = 1.0
    lm.doc_len_min = 16
    config.model.lm = lm

    config.per_host_batch_size = 1
    config.learning_rate = 1e-5
    config.obs.model_health = True
    config.resilience.guard = True
    return config

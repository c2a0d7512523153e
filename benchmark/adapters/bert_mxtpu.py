"""System under test for BERT configurations: ``models.bert.BERTModel`` with
``BERTForPretraining`` under ``parallel.SPMDTrainer``, through their public
constructors, with the benchmark's weights installed. Rematerialization is
what ``ops.kernel_policy.training_plan`` plans for the sizes."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common_mxtpu as common  # noqa: E402


def build_trainer(config, job, weights, n_chips, rehearsal):
    from incubator_mxnet_tpu.models import bert as B
    from incubator_mxnet_tpu.ops.kernel_policy import training_plan
    c = config
    plan = training_plan(c["num_hidden_layers"], c["hidden_size"],
                         c["intermediate_size"], vocab=c["vocab_size"],
                         seq_len=job["seq_len"])
    m = B.BERTModel(vocab_size=c["vocab_size"], units=c["hidden_size"],
                    hidden_size=c["intermediate_size"],
                    num_layers=c["num_hidden_layers"],
                    num_heads=c["num_attention_heads"],
                    max_length=c["max_position_embeddings"],
                    type_vocab_size=c["type_vocab_size"],
                    dropout=c["hidden_dropout_prob"],
                    layer_norm_eps=c["layer_norm_eps"],
                    dtype=c["compute_dtype"], flash=True,
                    remat=job.get("remat", plan["remat"]))
    pre = B.BERTForPretraining(m, layer_norm_eps=c["layer_norm_eps"])
    pairs = [("word", None, m.word_embed.weight),
             ("type", None, m.token_type_embed.weight),
             ("pos", None, m.position_embed.weight),
             ("emb_g", None, m.embed_ln.gamma),
             ("emb_b", None, m.embed_ln.beta),
             ("pool_w", None, m.pooler.weight),
             ("pool_b", None, m.pooler.bias),
             ("mlm_w", None, pre.mlm_transform.weight),
             ("mlm_b", None, pre.mlm_transform.bias),
             ("mlm_g", None, pre.mlm_ln.gamma),
             ("mlm_beta", None, pre.mlm_ln.beta),
             ("mlm_bias", None, pre.mlm_bias),
             ("nsp_w", None, pre.nsp.weight), ("nsp_b", None, pre.nsp.bias)]
    for l in range(c["num_hidden_layers"]):
        b = getattr(m, f"layer{l}")
        pairs += [("qkv_w", l, b.attention.qkv.weight),
                  ("qkv_b", l, b.attention.qkv.bias),
                  ("proj_w", l, b.attention.proj.weight),
                  ("proj_b", l, b.attention.proj.bias),
                  ("ln1_g", l, b.ln1.gamma), ("ln1_b", l, b.ln1.beta),
                  ("fc_w", l, b.ffn_in.weight), ("fc_b", l, b.ffn_in.bias),
                  ("out_w", l, b.ffn_out.weight),
                  ("out_b", l, b.ffn_out.bias),
                  ("ln2_g", l, b.ln2.gamma), ("ln2_b", l, b.ln2.beta)]
    common.install(pairs, weights)
    order = ["input_ids", "token_types", "valid_length", "masked_positions",
             "masked_labels", "masked_weights", "nsp_labels"]
    return common.build_trainer(pre, pairs, B.pretraining_loss, job, order,
                                n_chips)

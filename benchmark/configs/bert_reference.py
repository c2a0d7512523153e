"""Plain reference for BERT pretraining (Devlin et al. 2019; the
bert-large-uncased config.json): token + position + segment embeddings with a
norm, post-norm encoder layers with bidirectional attention and an exact-GELU
MLP, tanh pooler over the first token, masked-LM head (dense, GELU, norm,
decoder tied to the token embedding plus a bias) and next-sentence head.
float32, no kernels. Layers are one ``lax.scan`` over stacked leaves.

Departures, stated in the configuration file: dropout is 0 (``reduced``);
sequences are full length, so no padding mask is needed.
"""

import jax
import jax.numpy as jnp


def param_spec(c):
    u, h, L, V, P = (c["hidden_size"], c["intermediate_size"],
                     c["num_hidden_layers"], c["vocab_size"],
                     c["max_position_embeddings"])
    w = c["compute_dtype"]
    return [
        ("word", (V, u), "float32", "matrix"),
        ("type", (c["type_vocab_size"], u), "float32", "matrix"),
        ("pos", (P, u), "float32", "matrix"),
        ("emb_g", (u,), "float32", "gamma"),
        ("emb_b", (u,), "float32", "beta"),
        ("qkv_w", (L, 3 * u, u), w, "matrix/L:3"),
        ("qkv_b", (L, 3 * u), w, "bias/L:3"),
        ("proj_w", (L, u, u), w, "matrix/L"),
        ("proj_b", (L, u), w, "bias/L"),
        ("ln1_g", (L, u), "float32", "gamma/L"),
        ("ln1_b", (L, u), "float32", "beta/L"),
        ("fc_w", (L, h, u), w, "matrix/L"),
        ("fc_b", (L, h), w, "bias/L"),
        ("out_w", (L, u, h), w, "matrix/L"),
        ("out_b", (L, u), w, "bias/L"),
        ("ln2_g", (L, u), "float32", "gamma/L"),
        ("ln2_b", (L, u), "float32", "beta/L"),
        ("pool_w", (u, u), "float32", "matrix"),
        ("pool_b", (u,), "float32", "bias"),
        ("mlm_w", (u, u), "float32", "matrix"),
        ("mlm_b", (u,), "float32", "bias"),
        ("mlm_g", (u,), "float32", "gamma"),
        ("mlm_beta", (u,), "float32", "beta"),
        ("mlm_bias", (V,), "float32", "bias"),
        ("nsp_w", (2, u), "float32", "matrix"),
        ("nsp_b", (2,), "float32", "bias"),
    ]


_LAYER = ("qkv_w", "qkv_b", "proj_w", "proj_b", "ln1_g", "ln1_b", "fc_w",
          "fc_b", "out_w", "out_b", "ln2_g", "ln2_b")


def encode(p, ids, types, c, ops, R):
    B, T = ids.shape
    H = c["num_attention_heads"]
    eps = c["layer_norm_eps"]
    x = p["word"][ids] + p["pos"][:T][None] + p["type"][types]
    x = R.layer_norm(x, p["emb_g"], p["emb_b"], eps)

    def layer(x, lp):
        qkv = ops.dot(x, lp["qkv_w"]) + lp["qkv_b"]
        qkv = qkv.reshape(B, T, 3, H, -1)
        a = R.attention(ops, qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], None)
        x = R.layer_norm(x + ops.dot(a, lp["proj_w"]) + lp["proj_b"],
                         lp["ln1_g"], lp["ln1_b"], eps)
        h = R.gelu_erf(ops.dot(x, lp["fc_w"]) + lp["fc_b"])
        x = R.layer_norm(x + ops.dot(h, lp["out_w"]) + lp["out_b"],
                         lp["ln2_g"], lp["ln2_b"], eps)
        return x, None

    stacked = {k: p[k].astype(jnp.float32) for k in _LAYER}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    return x


def denominators(batch):
    return {"mlm": float(batch["masked_weights"].sum()) + 1e-6,
            "rows": float(batch["input_ids"].shape[0])}


def loss_contrib(p, block, den, c, ops, R):
    """This block of rows' part of the batch's MLM + NSP loss."""
    eps = c["layer_norm_eps"]
    x = encode(p, block["input_ids"], block["token_types"], c, ops, R)
    pooled = jnp.tanh(ops.dot(x[:, 0], p["pool_w"]) + p["pool_b"])
    g = jnp.take_along_axis(x, block["masked_positions"][..., None], axis=1)
    h = R.gelu_erf(ops.dot(g, p["mlm_w"]) + p["mlm_b"])
    h = R.layer_norm(h, p["mlm_g"], p["mlm_beta"], eps)
    scores = ops.dot(h, p["word"]) + p["mlm_bias"]
    lse = jax.nn.logsumexp(scores, axis=-1)
    pick = jnp.take_along_axis(scores, block["masked_labels"][..., None],
                               axis=-1)[..., 0]
    mlm = jnp.sum((lse - pick) * block["masked_weights"]) / den["mlm"]
    nsp = ops.dot(pooled, p["nsp_w"]) + p["nsp_b"]
    nsp_ll = jnp.take_along_axis(jax.nn.log_softmax(nsp, axis=-1),
                                 block["nsp_labels"][:, None], axis=-1)
    return mlm - jnp.sum(nsp_ll) / den["rows"]


def batch_fields(c, job):
    T, M = job["seq_len"], job["masked_positions"]
    return [("input_ids", (T,), "token"),
            ("token_types", (T,), "segment"),
            ("valid_length", (), "full_length"),
            ("masked_positions", (M,), "position"),
            ("masked_labels", (M,), "token"),
            ("masked_weights", (M,), "ones"),
            ("nsp_labels", (), "binary")]


def finish_batch(raw):
    return raw


def train_flops_per_step(c, job, rows):
    """Forward and backward matmul operations (2x and 4x; recomputation not
    counted), copied from bench.py:_bert_flops_per_step: encoder matmuls,
    full attention, and the MLM and NSP heads. Embedding gathers are not
    matmul operations and are left out."""
    T, M = job["seq_len"], job["masked_positions"]
    L, u, h, V = (c["num_hidden_layers"], c["hidden_size"],
                  c["intermediate_size"], c["vocab_size"])
    enc = 6.0 * rows * T * L * (4 * u * u + 2 * u * h)
    attn = 12.0 * L * rows * T * T * u
    heads = 6.0 * rows * M * u * (V + u) + 6.0 * rows * (u * u + 2 * u)
    return enc + attn + heads


def attention_shape(c, job, rows):
    """Shape of one training call of attention on ``rows`` rows."""
    return {"B": rows, "H": c["num_attention_heads"], "T": job["seq_len"],
            "D": c["hidden_size"] // c["num_attention_heads"],
            "causal": False}


def n_layers(c):
    return c["num_hidden_layers"]

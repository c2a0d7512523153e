"""Plain reference for GPT-2 (Radford et al. 2019; the 124M config.json):
learned positions, pre-norm blocks, fused q|k|v projection, causal attention,
GELU MLP, final norm, head tied to the token embedding. float32, no kernels,
no cache, no batching tricks. Layers are one ``lax.scan`` over stacked leaves
so that it compiles once.

Departure from the published model, stated in the configuration file: the
activation is the exact (erf) GELU where GPT-2 publishes the tanh form
(``gelu_new``); dropout is 0 (see the file's ``reduced``).
"""

import jax
import jax.numpy as jnp


def param_spec(c):
    u, h, L, V, P = (c["n_embd"], c["n_inner"], c["n_layer"],
                     c["vocab_size"], c["n_positions"])
    w = c["compute_dtype"]
    return [
        ("wte", (V, u), "float32", "matrix"),
        ("wpe", (P, u), "float32", "matrix"),
        ("ln1_g", (L, u), "float32", "gamma/L"),
        ("ln1_b", (L, u), "float32", "beta/L"),
        ("qkv_w", (L, 3 * u, u), w, "matrix/L:3"),
        ("qkv_b", (L, 3 * u), w, "bias/L:3"),
        ("proj_w", (L, u, u), w, "matrix/L"),
        ("proj_b", (L, u), w, "bias/L"),
        ("ln2_g", (L, u), "float32", "gamma/L"),
        ("ln2_b", (L, u), "float32", "beta/L"),
        ("fc_w", (L, h, u), w, "matrix/L"),
        ("fc_b", (L, h), w, "bias/L"),
        ("out_w", (L, u, h), w, "matrix/L"),
        ("out_b", (L, u), w, "bias/L"),
        ("lnf_g", (u,), "float32", "gamma"),
        ("lnf_b", (u,), "float32", "beta"),
    ]


_LAYER = ("ln1_g", "ln1_b", "qkv_w", "qkv_b", "proj_w", "proj_b", "ln2_g",
          "ln2_b", "fc_w", "fc_b", "out_w", "out_b")


def hidden(p, ids, c, ops, R):
    """ids (B, T) -> final-normed hidden states (B, T, u)."""
    B, T = ids.shape
    H = c["n_head"]
    eps = c["layer_norm_epsilon"]
    x = p["wte"][ids] + p["wpe"][:T][None]
    causal = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None, None]

    def layer(x, lp):
        h = R.layer_norm(x, lp["ln1_g"], lp["ln1_b"], eps)
        qkv = ops.dot(h, lp["qkv_w"]) + lp["qkv_b"]
        qkv = qkv.reshape(B, T, 3, H, -1)
        a = R.attention(ops, qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2], causal)
        x = x + ops.dot(a, lp["proj_w"]) + lp["proj_b"]
        h = R.layer_norm(x, lp["ln2_g"], lp["ln2_b"], eps)
        h = R.gelu_erf(ops.dot(h, lp["fc_w"]) + lp["fc_b"])
        return x + ops.dot(h, lp["out_w"]) + lp["out_b"], None

    stacked = {k: p[k].astype(jnp.float32) for k in _LAYER}
    x, _ = jax.lax.scan(jax.checkpoint(layer), x, stacked)
    return R.layer_norm(x, p["lnf_g"], p["lnf_b"], eps)


def logits_at(p, ids, positions, c, ops, R):
    """Next-token logits at ``positions`` of one sequence ``ids (T,)``."""
    x = hidden(p, ids[None], c, ops, R)[0][positions]
    return ops.dot(x, p["wte"])


def denominators(batch):
    """What the loss divides by, taken over the whole batch."""
    return {"tokens": float(batch["labels"].size)}


def loss_contrib(p, block, den, c, ops, R):
    """This block of rows' part of the batch's mean next-token loss."""
    x = hidden(p, block["input_ids"], c, ops, R)
    logits = ops.dot(x, p["wte"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, block["labels"][..., None],
                               axis=-1)[..., 0]
    return jnp.sum(lse - pick) / den["tokens"]


def batch_fields(c, job):
    """Fields of one training batch: (name, trailing shape, kind)."""
    T = job["seq_len"]
    return [("tokens", (T + 1,), "token")]


def finish_batch(raw):
    """input_ids and labels are one drawn row shifted by a position."""
    t = raw.pop("tokens")
    return {"input_ids": t[:, :-1], "labels": t[:, 1:]}


def train_flops_per_step(c, job, rows):
    """Forward and backward matmul operations (2x and 4x; recomputation not
    counted), copied from bench.py:_gpt_flops_per_step: blocks, causal
    attention at half the square, and the full-vocabulary head."""
    T, L, u, h, V = (job["seq_len"], c["n_layer"], c["n_embd"], c["n_inner"],
                     c["vocab_size"])
    dec = 6.0 * rows * T * L * (4 * u * u + 2 * u * h)
    attn = 6.0 * L * rows * T * T * u
    head = 6.0 * rows * T * u * V
    return dec + attn + head


def forward_flops(c, n_tokens, context_tokens, n_sampled):
    """Forward operations of serving work: ``n_tokens`` positions through the
    blocks, attention over ``context_tokens`` (sum over those positions of
    the keys each attends to) and the head for ``n_sampled`` positions."""
    L, u, h, V = c["n_layer"], c["n_embd"], c["n_inner"], c["vocab_size"]
    return (2.0 * n_tokens * L * (4 * u * u + 2 * u * h)
            + 4.0 * L * u * context_tokens + 2.0 * n_sampled * u * V)


def kv_bytes_per_token(c):
    """Bytes one cached position holds: keys and values of every layer."""
    return 2 * c["n_layer"] * c["n_embd"] * 2


def attention_shape(c, job, rows):
    """Shape of one training call of attention on ``rows`` rows."""
    return {"B": rows, "H": c["n_head"], "T": job["seq_len"],
            "D": c["n_embd"] // c["n_head"], "causal": True}


def n_layers(c):
    return c["n_layer"]

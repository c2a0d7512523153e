"""Plain reference for the ``granitemoehybrid`` family with no experts (IBM
Granite 4.0-H config.json; the Mamba-2 mixer in the Hugging Face Bamba form).
float32, no kernels, no cache, no chunked form: **the recurrence is written as
the recurrence**, a ``lax.scan`` over positions with a (heads, head width,
state) carry. It imports nothing of the program and takes nothing it made.

With ``e = embedding_multiplier``, ``r = residual_multiplier``,
``a = attention_multiplier``, ``s = logits_scaling``, ``eps = rms_norm_eps``
and ``n(x; w) = x / sqrt(mean(x^2) + eps) * w``:

    h = e * E[ids]
    for each layer, by ``layer_types``:
        h = h + r * mixer(n(h; w1));   h = h + r * mlp(n(h; w2))
    logits = n(h; wf) @ E^T / s                      (head tied to E)

    mlp(x) = W_out (silu(u) * g),  [u, g] = split(W_in x), no bias
    attention: q ``num_attention_heads`` heads, k and v
        ``num_key_value_heads`` heads, no bias, no rotary and no position
        of any kind (``position_embedding_type: nope``), causal softmax of
        a * q k^T, query head j reads key-value head j // (heads / kv heads)
    mamba: [z, xBC, dt] = split(W_in x)
        xBC = silu(causal_depthwise_conv(xBC) + b_conv), width mamba_d_conv
        [x, B, C] = split(xBC);  dt = softplus(dt + dt_bias);  A = -exp(A_log)
        S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;   y_t = S_t C_t + D x_t
        out = W_out n(y * silu(z); w_norm), the norm over all heads (one group)

Departures from the published model: none in the equations. The fused leaves
(``qkv_w`` = q | k | v rows, ``mlp_in_w`` = u | g rows, ``ssm_in_w`` = z | xBC
| dt rows) are the source's separate or already fused projections laid row
under row. Weights are drawn by the harness, whose kinds are ``matrix``,
``bias``, ``beta`` (N(0, 0.02)) and ``gamma`` (1 + N(0, 0.02)): the matrices
are ``matrix``; ``A_log`` is ``beta``, so A is about -1; ``D``, the norm gains
and **the convolution's taps are ``gamma``** (a tap near 1, as wide
as PyTorch's default for a width-4 depthwise filter, and not 0.02: with taps
of 0.02 x, B and C would be so small that the state would add a thousandth of
the skip ``D x`` and no fault in the state could show in a logit). **The leaf
``embed`` is e * E, not E**: the table is the harness's N(0, 0.02) draw
divided by the embedding multiplier, so that what enters the first layer,
e * E[ids], has the spread the draw has. With E itself at 0.02 and e = 12 the
token's own embedding would be the loudest thing in the residual stream, the
tied head would give the input token back at every position by six times the
logits' spread whatever the layers compute (measured at the rehearsal size:
a margin of 0.19 on logits of spread 0.03; every served token its prompt's
last), and the comparison that decides ``correct`` could not see any fault.
**The leaf ``dt_bias`` is the model's dt_bias less ``dt_bias_mean``**, a key
of the configuration file (-4.6, Mamba-2's own initialisation: dt log-uniform
in 0.001 to 0.1, about 0.01 in the middle), so that the model's dt_bias is
the harness's ``bias`` draw about that mean and a state remembers some hundred
positions. With the draw alone (dt about 0.69) a state forgets in two or
three positions, and what the state cache can get wrong that pages cannot (a
slot's rows not zeroed at admission, a carry dropped between chunks, state
kept in bfloat16) would never reach a served token.
"""

import jax
import jax.numpy as jnp


def _dims(c):
    H, P, N = c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"]
    D = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    if c["mamba_n_groups"] != 1:
        raise ValueError("one B/C group only")
    return {"u": c["hidden_size"], "ffn": c["shared_intermediate_size"],
            "V": c["vocab_size"], "H": H, "P": P, "N": N,
            "K": c["mamba_d_conv"], "inner": H * P, "conv": H * P + 2 * N,
            "hq": c["num_attention_heads"], "hkv": c["num_key_value_heads"],
            "D": D, "types": list(c["layer_types"])}


def param_spec(c):
    d = _dims(c)
    w = c["compute_dtype"]
    L = len(d["types"])
    Lm = d["types"].count("mamba")
    La = L - Lm
    u, f = d["u"], d["ffn"]
    return [
        ("embed", (d["V"], u), "float32", "matrix"),     # e * E
        ("norm1_w", (L, u), "float32", "gamma/L"),
        ("norm2_w", (L, u), "float32", "gamma/L"),
        ("mlp_in_w", (L, 2 * f, u), w, "matrix/L"),
        ("mlp_out_w", (L, u, f), w, "matrix/L"),
        ("ssm_in_w", (Lm, d["inner"] + d["conv"] + d["H"], u), w,
         "matrix/L"),
        ("conv_w", (Lm, d["conv"], d["K"]), "float32", "gamma/L"),
        ("conv_b", (Lm, d["conv"]), "float32", "bias/L"),
        ("dt_bias", (Lm, d["H"]), "float32", "bias/L"),
        ("A_log", (Lm, d["H"]), "float32", "beta/L"),
        ("D", (Lm, d["H"]), "float32", "gamma/L"),
        ("ssm_norm_w", (Lm, d["inner"]), "float32", "gamma/L"),
        ("ssm_out_w", (Lm, u, d["inner"]), w, "matrix/L"),
        ("qkv_w", (La, (d["hq"] + 2 * d["hkv"]) * d["D"], u), w,
         "matrix/L"),
        ("o_w", (La, u, d["hq"] * d["D"]), w, "matrix/L"),
        ("final_norm_w", (u,), "float32", "gamma"),
    ]


_EVERY = ("norm1_w", "norm2_w", "mlp_in_w", "mlp_out_w")
_MAMBA = ("ssm_in_w", "conv_w", "conv_b", "dt_bias", "A_log", "D",
          "ssm_norm_w", "ssm_out_w")
_ATTN = ("qkv_w", "o_w")


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + eps) * w


def _mlp(x, lp, c, ops):
    u, g = jnp.split(ops.dot(rms_norm(x, lp["norm2_w"], c["rms_norm_eps"]),
                             lp["mlp_in_w"]), 2, axis=-1)
    return ops.dot(jax.nn.silu(u) * g, lp["mlp_out_w"])


def _attention(x, lp, c, d, ops):
    """x (T, u): masked softmax over repeated key-value heads."""
    T = x.shape[0]
    hq, hkv, D = d["hq"], d["hkv"], d["D"]
    qkv = ops.dot(rms_norm(x, lp["norm1_w"], c["rms_norm_eps"]), lp["qkv_w"])
    q = qkv[:, :hq * D].reshape(T, hq, D)
    k = qkv[:, hq * D:(hq + hkv) * D].reshape(T, hkv, D)
    v = qkv[:, (hq + hkv) * D:].reshape(T, hkv, D)
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    s = ops.einsum("qhd,khd->hqk", q, k) * c["attention_multiplier"]
    causal = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None], s, -1e30), axis=-1)
    o = ops.einsum("hqk,khd->qhd", p, v).reshape(T, hq * D)
    return ops.dot(o, lp["o_w"])


def _mamba(x, lp, c, d, ops):
    """x (T, u): the convolution, then the recurrence a position a turn."""
    T = x.shape[0]
    H, P, N, K, inner = d["H"], d["P"], d["N"], d["K"], d["inner"]
    zxd = ops.dot(rms_norm(x, lp["norm1_w"], c["rms_norm_eps"]),
                  lp["ssm_in_w"])
    z, xbc, dt = (zxd[:, :inner], zxd[:, inner:inner + d["conv"]],
                  zxd[:, inner + d["conv"]:])
    padded = jnp.concatenate([jnp.zeros((K - 1, d["conv"])), xbc], axis=0)
    xbc = lp["conv_b"] + sum(padded[k:k + T] * lp["conv_w"][:, k]
                             for k in range(K))
    xbc = jax.nn.silu(xbc)
    xs = xbc[:, :inner].reshape(T, H, P)
    Bm, Cm = xbc[:, inner:inner + N], xbc[:, inner + N:]
    # the leaf is the model's dt_bias less its mean (the docstring)
    dt = jax.nn.softplus(dt + lp["dt_bias"] + c["dt_bias_mean"])  # (T, H)
    A = -jnp.exp(lp["A_log"])

    def turn(S, t):
        x_t, b_t, c_t, dt_t = t
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :]
        return S, jnp.sum(S * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(turn, jnp.zeros((H, P, N)), (xs, Bm, Cm, dt))
    y = (y + lp["D"][:, None] * xs).reshape(T, inner) * jax.nn.silu(z)
    return ops.dot(rms_norm(y, lp["ssm_norm_w"], c["rms_norm_eps"]),
                   lp["ssm_out_w"])


def hidden(p, ids, c, ops, R=None):
    """ids (T,) -> final-normed hidden states (T, u). A run of equal layers
    is one ``lax.scan`` over its indices into the stacked leaves (the leaves
    are read a layer at a time and never copied whole)."""
    d = _dims(c)
    r = c["residual_multiplier"]
    x = p["embed"][ids].astype(jnp.float32)     # e * E[ids]: the leaf is e * E
    types = d["types"]

    def take(names, i):
        return {k: jax.lax.dynamic_index_in_dim(p[k], i, keepdims=False)
                .astype(jnp.float32) for k in names}

    def layer(kind):
        def run(x, idx):
            i, j = idx                  # among all layers, among its kind
            if kind == "mamba":
                x = x + r * _mamba(x, {**take(_MAMBA, j),
                                       **take(("norm1_w",), i)}, c, d, ops)
            else:
                x = x + r * _attention(x, {**take(_ATTN, j),
                                           **take(("norm1_w",), i)}, c, d,
                                       ops)
            lp = take(("norm2_w", "mlp_in_w", "mlp_out_w"), i)
            return x + r * _mlp(x, lp, c, ops), None
        return run

    seen = {"mamba": 0, "attention": 0}
    i = 0
    while i < len(types):
        kind, n = types[i], 1
        while i + n < len(types) and types[i + n] == kind:
            n += 1
        idx = (jnp.arange(i, i + n), jnp.arange(seen[kind], seen[kind] + n))
        x, _ = jax.lax.scan(layer(kind), x, idx)
        seen[kind] += n
        i += n
    return rms_norm(x, p["final_norm_w"], c["rms_norm_eps"])


def logits_at(p, ids, positions, c, ops, R=None):
    """Next-token logits at ``positions`` of one sequence ``ids (T,)``."""
    x = hidden(p, ids, c, ops)[positions]
    return ops.dot(x, p["embed"]) / (c["embedding_multiplier"]
                                     * c["logits_scaling"])


def forward_flops(c, n_tokens, context_tokens, n_sampled):
    """Forward operations of serving work: ``n_tokens`` positions through
    every matrix (2 x its parameters), attention over ``context_tokens`` in
    the attention layers, the state update (decay, outer product and read:
    6 operations an element of a head's state) in the Mamba layers, and the
    head for ``n_sampled`` positions."""
    d = _dims(c)
    u, f = d["u"], d["ffn"]
    Lm = d["types"].count("mamba")
    La = len(d["types"]) - Lm
    mats = (Lm + La) * 3 * u * f \
        + Lm * (u * (d["inner"] + d["conv"] + d["H"]) + u * d["inner"]) \
        + La * (u * (d["hq"] + 2 * d["hkv"]) * d["D"] + u * d["hq"] * d["D"])
    return (2.0 * n_tokens * mats
            + 4.0 * La * d["hq"] * d["D"] * context_tokens
            + 6.0 * n_tokens * Lm * d["H"] * d["P"] * d["N"]
            + 2.0 * n_sampled * u * d["V"])


def kv_bytes_per_token(c):
    """Bytes one cached position holds: keys and values of the attention
    layers only."""
    d = _dims(c)
    return 2 * d["types"].count("attention") * d["hkv"] * d["D"] * 2


def state_bytes_per_slot(c, itemsize=4):
    """Bytes of recurrent state one sequence holds, every Mamba layer."""
    d = _dims(c)
    return d["types"].count("mamba") * d["H"] * d["P"] * d["N"] * itemsize


def n_layers(c):
    return len(c["layer_types"])

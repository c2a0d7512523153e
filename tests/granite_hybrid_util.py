"""Shared by the hybrid (state-space + attention) tests: a small
configuration in the reference's own keys, weights drawn the way the
benchmark's harness draws them (``matrix`` / ``bias`` / ``beta`` N(0, 0.02),
``gamma`` 1 + N(0, 0.02)), the arithmetic the reference is written in, and
the model built from both."""

import jax
import jax.numpy as jnp
import numpy as np

import granite_hybrid_reference as ref

HI = jax.lax.Precision.HIGHEST


class Ops:
    """float32 at ``HIGHEST``: what benchmark/harness/refops.py::Ops("f32")
    is, without the benchmark."""

    @staticmethod
    def dot(x, w):
        return jnp.einsum("...i,oi->...o", x.astype(jnp.float32),
                          w.astype(jnp.float32), precision=HI)

    @staticmethod
    def einsum(expr, a, b):
        return jnp.einsum(expr, a.astype(jnp.float32),
                          b.astype(jnp.float32), precision=HI)


def tiny_config(compute_dtype="float32", vocab=256, layer_types=None):
    """7 layers m m a m m m a, width 128, 4 state heads of 32 with state
    16, 4 query over 2 key-value heads of 32."""
    return {
        "hidden_size": 128, "shared_intermediate_size": 256,
        "vocab_size": vocab, "mamba_n_heads": 4, "mamba_d_head": 32,
        "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_n_groups": 1,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "layer_types": layer_types or ["mamba", "mamba", "attention",
                                       "mamba", "mamba", "mamba",
                                       "attention"],
        "embedding_multiplier": 12.0, "residual_multiplier": 0.22,
        "attention_multiplier": 1.0 / 64, "logits_scaling": 8.0,
        "rms_norm_eps": 1e-5, "compute_dtype": compute_dtype,
        "state_dtype": "float32", "n_positions": 256,
        # the benchmark's configurations move ``dt_bias`` by this key; here
        # ``draw_weights(slow_decay=...)`` moves the leaf itself
        "dt_bias_mean": 0.0}


def draw_weights(cfg, seed, slow_decay=True):
    """{leaf: array}. ``slow_decay`` moves ``dt_bias`` to about -4.6, so
    that dt is about 0.01 and the state remembers some hundred positions:
    an error in old state then shows, where the harness's own draw (dt
    about 0.69) forgets in a few."""
    rng = np.random.default_rng(seed)
    out = {}
    for name, shape, dtype, kind in ref.param_spec(cfg):
        x = 0.02 * rng.standard_normal(shape).astype(np.float32)
        if kind.split("/")[0] == "gamma":
            x = 1.0 + x
        if name == "dt_bias" and slow_decay:
            x = x - 4.6
        out[name] = jnp.asarray(x).astype(dtype)
    return out


_MAMBA = (("ssm_in_w", "ssm_in"), ("conv_w", "conv_w"), ("conv_b", "conv_b"),
          ("dt_bias", "dt_bias"), ("A_log", "A_log"), ("D", "D"),
          ("ssm_norm_w", "ssm_norm"), ("ssm_out_w", "ssm_out"))
_ATTN = (("qkv_w", "qkv"), ("o_w", "o"))
_EVERY = (("norm1_w", "norm1"), ("norm2_w", "norm2"),
          ("mlp_in_w", "mlp_in"), ("mlp_out_w", "mlp_out"))


def build_model(cfg, weights, max_length=256):
    from incubator_mxnet_tpu.models.granite_hybrid import GraniteHybridModel
    from incubator_mxnet_tpu.ndarray import NDArray
    types = cfg["layer_types"]
    m = GraniteHybridModel(
        cfg["vocab_size"], cfg["hidden_size"],
        cfg["shared_intermediate_size"], types,
        cfg["num_attention_heads"], cfg["num_key_value_heads"],
        cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"],
        ssm_conv=cfg["mamba_d_conv"],
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        attention_multiplier=cfg["attention_multiplier"],
        logits_scaling=cfg["logits_scaling"], rms_eps=cfg["rms_norm_eps"],
        max_length=max_length, dtype=cfg["compute_dtype"],
        state_dtype=cfg["state_dtype"])
    seen = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(types):
        lay = getattr(m, f"layer{i}")
        for leaf, name in _EVERY:
            getattr(lay, name).set_data(NDArray(weights[leaf][i]))
        for leaf, name in (_MAMBA if kind == "mamba" else _ATTN):
            getattr(lay, name).set_data(NDArray(weights[leaf][seen[kind]]))
        seen[kind] += 1
    # the reference's leaf is e * E (its docstring says why)
    m.embed.weight.set_data(NDArray(
        weights["embed"] / cfg["embedding_multiplier"]))
    m.final_norm.weight.set_data(NDArray(weights["final_norm_w"]))
    return m


def served_gap(cfg, weights, request):
    """How far, at the widest, a served token's reference logit lies below
    the reference's best at its position (0 where the served token is the
    reference's own), and the logits' spread."""
    toks = np.asarray(request.token_ids, np.int32)
    ids = np.concatenate([np.asarray(request.prompt_ids, np.int32),
                          toks[:-1]])
    n_p = request.prompt_ids.size
    logits = np.asarray(ref.logits_at(
        weights, jnp.asarray(ids), jnp.arange(n_p - 1, n_p - 1 + toks.size),
        cfg, Ops))
    return float(np.max(logits.max(-1) - logits[np.arange(toks.size), toks])), \
        float(logits.std())

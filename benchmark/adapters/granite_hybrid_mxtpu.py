"""System under test for hybrid state-space / attention configurations (the
``granitemoehybrid`` family without experts): ``models.granite_hybrid.
GraniteHybridModel`` under ``serve.InferenceEngine``, through their public
constructors, with the benchmark's weights installed."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from gpt2_mxtpu import ServeSut  # noqa: E402

# reference leaf -> the model's parameter of a layer
_EVERY = (("norm1_w", "norm1"), ("norm2_w", "norm2"), ("mlp_in_w", "mlp_in"),
          ("mlp_out_w", "mlp_out"))
_MAMBA = (("ssm_in_w", "ssm_in"), ("conv_w", "conv_w"), ("conv_b", "conv_b"),
          ("dt_bias", "dt_bias"), ("A_log", "A_log"), ("D", "D"),
          ("ssm_norm_w", "ssm_norm"), ("ssm_out_w", "ssm_out"))
_ATTN = (("qkv_w", "qkv"), ("o_w", "o"))


def _model(c, weights):
    """The model with ``weights`` installed. Serving reads no gradient, so
    every parameter's ``grad_req`` is ``null`` before it gets its data (a
    parameter otherwise allocates a zero gradient as large as itself), and a
    stacked leaf is dropped from ``weights`` as soon as its layers are cut:
    at no time do more than the weights and one leaf's slices exist."""
    from incubator_mxnet_tpu.models.granite_hybrid import GraniteHybridModel
    from incubator_mxnet_tpu.ndarray import NDArray
    types = list(c["layer_types"])
    m = GraniteHybridModel(
        c["vocab_size"], c["hidden_size"], c["shared_intermediate_size"],
        types, c["num_attention_heads"], c["num_key_value_heads"],
        c["mamba_n_heads"], c["mamba_d_head"], c["mamba_d_state"],
        ssm_conv=c["mamba_d_conv"], ssm_groups=c["mamba_n_groups"],
        head_dim=c.get("head_dim"),
        embedding_multiplier=c["embedding_multiplier"],
        residual_multiplier=c["residual_multiplier"],
        attention_multiplier=c["attention_multiplier"],
        logits_scaling=c["logits_scaling"], rms_eps=c["rms_norm_eps"],
        max_length=c["n_positions"], dtype=c["compute_dtype"],
        state_dtype=c["state_dtype"])
    for p in m.collect_params().values():
        p.grad_req = "null"
    layers = [getattr(m, f"layer{i}") for i in range(len(types))]

    def install(leaf, params):
        stacked = weights.pop(leaf)
        if leaf == "dt_bias":       # the reference's leaf is less its mean
            stacked = stacked + c["dt_bias_mean"]
        for j, p in enumerate(params):
            p.set_data(NDArray(stacked[j]))

    for leaf, name in _EVERY:
        install(leaf, [getattr(lay, name) for lay in layers])
    for kind, table in (("mamba", _MAMBA), ("attention", _ATTN)):
        mine = [lay for lay, t in zip(layers, types) if t == kind]
        for leaf, name in table:
            install(leaf, [getattr(lay, name) for lay in mine])
    # the reference's leaf is e * E (its docstring says why, as of dt_bias)
    m.embed.weight.set_data(NDArray(
        weights.pop("embed") / c["embedding_multiplier"]))
    m.final_norm.weight.set_data(NDArray(weights.pop("final_norm_w")))
    return m


def build_engine(config, engine_cfg, weights, rehearsal):
    from incubator_mxnet_tpu import serve
    from incubator_mxnet_tpu.events import FlightRecorder
    model = _model(config, weights)
    recorder = FlightRecorder(capacity=4_000_000, histograms=False)
    engine = serve.InferenceEngine(
        model, num_slots=engine_cfg["num_slots"],
        page_size=engine_cfg["page_size"], max_len=engine_cfg["max_len"],
        num_pages=engine_cfg.get("num_pages"),
        prefix_cache=engine_cfg["prefix_cache"],
        chunk_pages=engine_cfg["chunk_pages"],
        token_budget=engine_cfg.get("token_budget"),
        interpret=True if rehearsal else None, recorder=recorder)
    return ServeSut(engine, recorder)

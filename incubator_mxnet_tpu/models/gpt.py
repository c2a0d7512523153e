"""Decoder-only causal language model (GPT-style).

The reference era predates decoder-only LMs as a model family, but its
GluonNLP zoo ships language models (`gluonnlp/model/language_model.py` —
AWD-LSTM/StandardRNN; file-level citation, SURVEY.md caveat); this is
the attention-generation replacement for that family and the natural
long-context flagship: causal Pallas flash attention
(ops/pallas_attention.py), per-layer rematerialization, tp/fsdp
parameter shardings, and greedy/temperature decoding as one
``lax.fori_loop`` program (fixed shapes, jitted once).

Sharding follows the BERT layout (qkv/ffn-in column-parallel, output
projections row-parallel, vocab-sharded embedding) so SPMDTrainer runs
it over any dp/fsdp/tp mesh with zero code changes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..base import MXNetError
from ..gluon import nn
from ._attention import packed_flash_self_attention, use_packed_fast_path
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from .. import initializer as init
from .. import random as _rand
from ..profiler import scope, scoped

__all__ = ["GPTModel", "gpt_mini", "gpt_small", "lm_loss", "lm_pipeline",
           "greedy_generate", "cached_generate", "init_kv_cache",
           "decode_forward"]


class CausalSelfAttention(HybridBlock):
    """``seq_parallel=True`` routes attention through the sp-axis ring
    (parallel/ring_attention.py) whenever the SPMD step's active mesh has
    an ``sp`` axis of size > 1 — exact long-context attention with the
    sequence sharded across chips; everywhere else it falls back to the
    ordinary (flash-capable) kernel, so the flag is safe to leave on."""

    def __init__(self, units, num_heads, dropout=0.0, dtype="float32",
                 flash=False, seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} % heads {num_heads} != 0")
        self._units, self._heads, self._flash = units, num_heads, flash
        self._seq_parallel = seq_parallel
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, in_units=units, flatten=False,
                                dtype=dtype,
                                weight_initializer=init.TruncNorm(stdev=0.02))
            self.proj = nn.Dense(units, in_units=units, flatten=False,
                                 dtype=dtype,
                                 weight_initializer=init.TruncNorm(stdev=0.02))
            self.dropout = nn.Dropout(dropout)
        self.qkv.weight._sharding = P("tp", None)
        self.qkv.bias._sharding = P("tp")
        self.proj.weight._sharding = P(None, "tp")

    def hybrid_forward(self, F, x):
        from ..parallel.spmd import constrain
        B, T = x.shape[0], x.shape[1]
        H, D = self._heads, self._units // self._heads
        qkv = self.qkv(x)                     # (B, T, 3*H*D)
        seq_ax = "sp" if self._seq_parallel else None
        mesh = None
        if self._seq_parallel:
            from ..parallel.ring_attention import active_ring_mesh
            mesh = active_ring_mesh(T)
        if mesh is None and self._flash and use_packed_fast_path(D):
            # packed fast path — see models/_attention.py
            out = packed_flash_self_attention(
                F, qkv, B, T, H, D, self._units, causal=True,
                seq_ax=seq_ax)
        else:
            qkv = constrain(qkv.reshape((B, T, 3, H, D)),
                            ("dp", "fsdp"), seq_ax, None, "tp", None)
            q = qkv._op("slice_axis", axis=2, begin=0,
                        end=1).reshape((B, T, H, D))
            k = qkv._op("slice_axis", axis=2, begin=1,
                        end=2).reshape((B, T, H, D))
            v = qkv._op("slice_axis", axis=2, begin=2,
                        end=3).reshape((B, T, H, D))
            if mesh is not None:
                from ..parallel.ring_attention import (ring_self_attention,
                                                       ring_flash_attention)
                from ..ops.pallas_attention import _on_tpu, pallas_path
                engine = ring_flash_attention if (
                    self._flash and _on_tpu() and pallas_path()) \
                    else ring_self_attention
                out = NDArray(engine(
                    q._data, k._data, v._data, mesh=mesh, causal=True,
                    batch_axis=("dp", "fsdp")))
            else:
                out = F.scaled_dot_product_attention(q, k, v, causal=True,
                                                     flash=self._flash)
            out = constrain(out, ("dp", "fsdp"), seq_ax, "tp", None)
            out = out.reshape((B, T, self._units))
        return constrain(self.dropout(self.proj(out)),
                         ("dp", "fsdp"), seq_ax, None)


class GPTBlock(HybridBlock):
    """Pre-norm transformer decoder block (LN → attn → residual,
    LN → MLP → residual)."""

    def __init__(self, units, hidden_size, num_heads, dropout=0.0,
                 layer_norm_eps=1e-5, dtype="float32", flash=False,
                 seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.ln1 = nn.LayerNorm(epsilon=layer_norm_eps,
                                    in_channels=units)
            self.attn = CausalSelfAttention(units, num_heads, dropout,
                                            dtype=dtype, flash=flash,
                                            seq_parallel=seq_parallel)
            self.ln2 = nn.LayerNorm(epsilon=layer_norm_eps,
                                    in_channels=units)
            self.ffn_in = nn.Dense(hidden_size, in_units=units,
                                   flatten=False, dtype=dtype,
                                   weight_initializer=init.TruncNorm(stdev=0.02))
            self.ffn_out = nn.Dense(units, in_units=hidden_size,
                                    flatten=False, dtype=dtype,
                                    weight_initializer=init.TruncNorm(stdev=0.02))
            self.dropout = nn.Dropout(dropout)
        self._seq_parallel = seq_parallel
        self.ffn_in.weight._sharding = P("tp", None)
        self.ffn_in.bias._sharding = P("tp")
        self.ffn_out.weight._sharding = P(None, "tp")

    def hybrid_forward(self, F, x):
        from ..parallel.spmd import constrain
        seq_ax = "sp" if self._seq_parallel else None
        # one scope a block part, the names models/bert.py uses
        # (docs/OBSERVABILITY.md "Named scopes"); a residual add goes
        # with the part it closes
        with scope("mx.norm"):
            n = self.ln1(x)
        with scope("mx.attn"):
            x = x + self.attn(n)
            x = constrain(x, ("dp", "fsdp"), seq_ax, None)
        with scope("mx.norm"):
            n = self.ln2(x)
        with scope("mx.ffn"):
            h = constrain(self.ffn_in(n), ("dp", "fsdp"), seq_ax, "tp")
            h = self.dropout(self.ffn_out(F.gelu(h)))
            return constrain(x + h, ("dp", "fsdp"), seq_ax, None)


class GPTModel(HybridBlock):
    """forward(input_ids (B, T)) -> logits (B, T, vocab); weights tied
    with the (vocab-sharded) input embedding."""

    def __init__(self, vocab_size=50257, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=1024,
                 dropout=0.0, layer_norm_eps=1e-5, dtype="float32",
                 flash=False, remat=False, seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        self.vocab_size = vocab_size
        self.num_layers = num_layers
        self._units = units
        self.hidden_size = hidden_size
        self._dtype = dtype
        self._remat = remat
        self._seq_parallel = seq_parallel
        self.max_length = max_length
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, sharded=True,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.position_embed = nn.Embedding(
                max_length, units,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.embed_dropout = nn.Dropout(dropout)
            for i in range(num_layers):
                blk = GPTBlock(units, hidden_size, num_heads, dropout,
                               layer_norm_eps, dtype=dtype, flash=flash,
                               seq_parallel=seq_parallel)
                self.register_child(blk, f"block{i}")
                setattr(self, f"block{i}", blk)
            self.ln_f = nn.LayerNorm(epsilon=layer_norm_eps,
                                     in_channels=units)

    def hybrid_forward(self, F, input_ids):
        from ..parallel.spmd import constrain
        B, T = input_ids.shape
        with scope("mx.embed"):
            pos = F.arange(0, T, dtype="int32").reshape((1, T)) \
                .broadcast_to((B, T))
            x = self.word_embed(input_ids) + self.position_embed(pos)
            x = constrain(x, ("dp", "fsdp"), None, None)
            x = self.embed_dropout(x)
            if self._dtype != "float32":
                x = x.astype(self._dtype)
        from ._remat import remat_call, resolve_policy
        pol = resolve_policy(self._remat)
        for i in range(self.num_layers):
            blk = getattr(self, f"block{i}")
            x = remat_call(blk, x, policy=pol) if self._remat else blk(x)
        # ln_f computes statistics in f32 but returns the input dtype, so
        # the (B, T, vocab) LM-head matmul runs at the compute dtype's MXU
        # rate (an f32 cast here poisoned the biggest matmul in the model);
        # losses do their log-sum-exp reduction with f32 accumulation
        with scope("mx.norm"):
            x = self.ln_f(x)
        with scope("mx.head"):
            embed_w = self.word_embed.weight.data()
            logits = F.dot(x, embed_w.astype(x.dtype), transpose_b=True)
            # vocab-sharded logits on tp meshes (see BERTForPretraining)
            seq_ax = "sp" if self._seq_parallel else None
            logits = constrain(logits, ("dp", "fsdp"), seq_ax, "tp")
        return logits

    def cache_layout(self):
        """What a cache has to hold, a layer an entry (the serving
        seam, docs/SERVING.md): every layer of this model keeps keys and
        values a position, ``heads`` of ``head_dim``, read at the usual
        ``head_dim ** -0.5``."""
        attn = self.block0.attn
        D = attn._units // attn._heads
        return [{"kind": "kv", "kv_heads": attn._heads, "head_dim": D,
                 "scale": D ** -0.5} for _ in range(self.num_layers)]

    def cached_forward(self, ids, pos, attend, last_row=None, state=None,
                       real=None):
        """Inference forward of tokens ``ids`` (B, T) at positions
        ``pos`` (B, T) against a cache the caller keeps: the one layer
        loop of cached inference (the dense buffers of
        ``decode_forward``, the page pools of serve/engine.py). The
        model owns its math; ``attend(i, q, k, v)`` owns where layer
        ``i``'s keys and values are kept and how they are read: per-head
        (B, T, H, D) arrays in, the attention output (B, T, H, D) in
        ``q``'s type out. ``last_row`` (a traced index) keeps that one
        row before the head. ``state`` and ``real`` are the seam's
        other half, for a model with state layers; this one has none
        and reads neither. Returns logits (B, T or 1, vocab), f32.
        No dropout: call it outside training mode."""
        B, T = ids.shape
        x = self.word_embed(NDArray(ids)) + self.position_embed(NDArray(pos))
        if self._dtype != "float32":
            x = x.astype(self._dtype)
        for i in range(self.num_layers):
            blk = getattr(self, f"block{i}")
            q, k, v = _qkv_heads(blk.attn, blk.ln1(x))
            out = attend(i, q, k, v)
            x = x + blk.attn.proj(NDArray(out.reshape(B, T, self._units)))
            x = x + _mlp(blk, x)
        if last_row is not None:
            x = NDArray(lax.dynamic_slice(
                x._data, (0, last_row, 0), (B, 1, self._units)))
        return _lm_head(self, x)._data


def lm_loss(model: GPTModel, input_ids, labels, weights=None):
    """Next-token cross entropy, shaped for SPMDTrainer.forward_loss.

    CE as pick − logsumexp with f32 accumulation: the (B, T, vocab)
    log-prob tensor is never materialized and bf16 logits lose no
    reduction precision (same streaming form as BERT's MLM loss)."""
    logits = model(input_ids)
    label_scores = logits.pick(labels, axis=-1)       # (B, T)
    lse = logits._op("logsumexp", axis=-1)
    ll = label_scores.astype("float32") - lse
    if weights is None:
        return -ll.mean()
    denom = weights.sum() + 1e-6
    return -(ll * weights).sum() / denom


def lm_pipeline(model: GPTModel, weighted: bool = False):
    """PipelineSpec for ``lm_loss`` training under the pipelined SPMD
    step (parallel/pipelined.py): stem = embeddings, one pipeline block
    per transformer layer, head = final norm + tied vocab projection +
    the next-token CE as LOCAL partial sums.

    ``weighted`` selects the ``lm_loss(..., weights=...)`` form (batch =
    (input_ids, labels, weights)); default mirrors the plain mean form
    (batch = (input_ids, labels)). The stem/head bodies replicate
    ``GPTModel.hybrid_forward`` + ``lm_loss`` op-for-op so the pipelined
    loss/gradients are bitwise-identical to the GSPMD step."""
    from ..parallel.pipelined import PipelineSpec
    from ..gluon.block import nd as F

    def stem(input_ids, *rest):
        from ..parallel.spmd import constrain
        B, T = input_ids.shape
        pos = F.arange(0, T, dtype="int32").reshape((1, T)) \
            .broadcast_to((B, T))
        x = model.word_embed(input_ids) + model.position_embed(pos)
        x = constrain(x, ("dp", "fsdp"), None, None)
        x = model.embed_dropout(x)
        if model._dtype != "float32":
            x = x.astype(model._dtype)
        return x

    def head(x, input_ids, labels, *rest):
        from ..parallel.spmd import constrain
        with scope("mx.norm"):
            x = model.ln_f(x)
        embed_w = model.word_embed.weight.data()
        logits = F.dot(x, embed_w.astype(x.dtype), transpose_b=True)
        logits = constrain(logits, ("dp", "fsdp"), None, "tp")
        with scope("mx.loss"):
            label_scores = logits.pick(labels, axis=-1)        # (B, T)
            lse = logits._op("logsumexp", axis=-1)
            ll = label_scores.astype("float32") - lse
            if weighted:
                if not rest:
                    raise MXNetError(
                        "lm_pipeline(weighted=True) expects batch = "
                        "(input_ids, labels, weights)")
                w = rest[0]
                return ((ll * w).sum(), w.sum())
            return (ll.sum(), NDArray(jnp.float32(ll._data.size)))

    if weighted:
        def finalize(n, d):
            return -(n / (d + 1e-6))
    else:
        def finalize(n, d):
            return -(n / d)

    blocks = [getattr(model, f"block{i}") for i in range(model.num_layers)]
    return PipelineSpec(
        blocks=blocks, head=scoped("mx.head", head), finalize=finalize,
        stem=scoped("mx.embed", stem),
        stem_modules=[model.word_embed, model.position_embed],
        head_modules=[model.ln_f, model.word_embed],
        name="gpt_lm")


def greedy_generate(model: GPTModel, prompt_ids, max_new_tokens=32,
                    temperature: float = 0.0):
    """Fixed-shape autoregressive decode: ONE lax.fori_loop program over
    a pre-allocated (B, T0 + max_new_tokens) buffer — full-prefix
    recompute per step (no KV cache), the shape-static jit-once design
    (BucketingModule's multi-shape caching is the alternative for many
    prompt lengths)."""
    ids = prompt_ids._data if isinstance(prompt_ids, NDArray) \
        else jnp.asarray(prompt_ids)
    B, T0 = ids.shape
    total = T0 + int(max_new_tokens)
    if total > model.max_length:
        raise MXNetError(f"decode length {total} exceeds max_length "
                         f"{model.max_length}")
    buf = jnp.zeros((B, total), jnp.int32)
    buf = lax.dynamic_update_slice(buf, ids.astype(jnp.int32), (0, 0))
    key = _rand.new_key()

    from ..gluon.block import _hybrid_trace_scope
    from .. import autograd

    def fwd(b):
        with _hybrid_trace_scope(), \
                autograd._ModeScope(recording=False, training=False):
            return model(NDArray(b))._data

    def step(t, carry):
        buf, key = carry
        logits = fwd(buf)                              # (B, total, V)
        idx = jnp.clip(t - 1, 0, total - 1)
        last = lax.dynamic_slice(
            logits, (0, idx, 0), (B, 1, logits.shape[-1]))[:, 0]
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, last / temperature, axis=-1)
        else:
            nxt = jnp.argmax(last, axis=-1)
        buf = lax.dynamic_update_slice(
            buf, nxt.astype(jnp.int32)[:, None], (0, idx + 1))
        return buf, key

    buf, _ = lax.fori_loop(T0, total, step, (buf, key))
    return NDArray(buf)


def gpt_mini(vocab_size=512, max_length=128, **kwargs) -> GPTModel:
    """Tiny config for tests/dry-runs."""
    return GPTModel(vocab_size=vocab_size, units=128, hidden_size=512,
                    num_layers=2, num_heads=4, max_length=max_length,
                    **kwargs)


def gpt_small(**kwargs) -> GPTModel:
    return GPTModel(vocab_size=50257, units=768, hidden_size=3072,
                    num_layers=12, num_heads=12, max_length=1024,
                    **kwargs)


# --------------------------------------------------------------------- #
# KV-cached incremental decode (the reference's stateful incremental
# inference path — RNN states / GluonNLP decoder states — re-designed
# for XLA: caches are fixed-shape (B, max_len, H, D) buffers updated
# with dynamic_update_slice, so prefill + every decode step compile to
# static-shape programs and generation is O(T) per new token instead of
# the O(T^2) full-prefix recompute of ``greedy_generate``.)
# --------------------------------------------------------------------- #

def _qkv_heads(attn: CausalSelfAttention, x):
    """Project and split x (B, Tin, units) into per-head q, k, v jnp
    arrays shaped (B, Tin, H, D): ``GPTModel.cached_forward``'s
    projection, one for every cache."""
    B, Tin = x.shape[0], x.shape[1]
    H, D = attn._heads, attn._units // attn._heads
    qkv = attn.qkv(x).reshape((B, Tin, 3, H, D))
    q = qkv._op("slice_axis", axis=2, begin=0, end=1).reshape(
        (B, Tin, H, D))._data
    k = qkv._op("slice_axis", axis=2, begin=1, end=2).reshape(
        (B, Tin, H, D))._data
    v = qkv._op("slice_axis", axis=2, begin=2, end=3).reshape(
        (B, Tin, H, D))._data
    return q, k, v


def _mlp(blk: GPTBlock, x):
    """The decode-path FFN half of a block: ln2 → ffn_in → exact gelu →
    ffn_out (no dropout — inference only)."""
    return blk.ffn_out(NDArray(jax.nn.gelu(
        blk.ffn_in(blk.ln2(x))._data, approximate=False)))


def _lm_head(model: GPTModel, x):
    """Final norm + tied vocab projection for the decode paths: cast to
    f32 BEFORE ``ln_f`` (norming bf16 then casting would feed
    bf16-rounded activations into the vocab projection and break token
    parity with the training/greedy path). x: (B, T, units) NDArray →
    (B, T, vocab) NDArray."""
    x = model.ln_f(x.astype("float32"))
    embed_w = model.word_embed.weight.data()
    return x._op("dot", embed_w, transpose_b=True)


def init_kv_cache(model: GPTModel, batch_size: int, max_len=None,
                  dtype=None):
    """Fresh (k, v) cache buffers for every layer."""
    Tmax = int(max_len or model.max_length)
    dt = jnp.dtype(dtype) if dtype else jnp.dtype(model._dtype)
    mk = lambda lay: jnp.zeros(
        (batch_size, Tmax, lay["kv_heads"], lay["head_dim"]), dt)
    return [(mk(lay), mk(lay)) for lay in model.cache_layout()]


def decode_forward(model: GPTModel, ids, caches, start_pos,
                   last_only=False):
    """Forward positions [start_pos, start_pos+Tin) with KV caches.
    ids: (B, Tin) int32; returns (logits, caches) — logits over all Tin
    positions, or only the last one when ``last_only`` (prefill wants
    one next-token row, not a (B, T0, vocab) tensor).

    INFERENCE-ONLY: dropout is never applied on this path, so results
    diverge from ``model(ids)`` under an active training mode — guarded
    below rather than silently wrong."""
    from .. import autograd as _ag
    if _ag.is_training():
        raise MXNetError(
            "decode_forward is inference-only (dropout is skipped); call "
            "it under autograd.predict_mode()")
    from ..ops.attention import scaled_dot_product_attention as _sdpa
    ids = ids._data if isinstance(ids, NDArray) else ids
    B, Tin = ids.shape
    Tmax = caches[0][0].shape[1]
    # causal mask against GLOBAL cache positions (static shapes: iota);
    # attention itself reuses the shared sdpa op so masking/softmax
    # numerics stay identical to the training path
    pos_q = start_pos + lax.broadcasted_iota(jnp.int32, (Tin, Tmax), 0)
    pos_k = lax.broadcasted_iota(jnp.int32, (Tin, Tmax), 1)
    mask = (pos_k <= pos_q)[None, None]            # (1, 1, Tin, Tmax)
    new_caches = list(caches)

    def attend(i, q, k, v):
        k_buf, v_buf = caches[i]
        k_buf = lax.dynamic_update_slice(k_buf, k.astype(k_buf.dtype),
                                         (0, start_pos, 0, 0))
        v_buf = lax.dynamic_update_slice(v_buf, v.astype(v_buf.dtype),
                                         (0, start_pos, 0, 0))
        new_caches[i] = (k_buf, v_buf)
        return _sdpa(q, k_buf.astype(q.dtype), v_buf.astype(q.dtype),
                     mask=mask)

    pos = start_pos + lax.broadcasted_iota(jnp.int32, (B, Tin), 1)
    logits = model.cached_forward(ids, pos, attend,
                                  last_row=Tin - 1 if last_only else None)
    return NDArray(logits), new_caches


def cached_generate(model: GPTModel, prompt_ids, max_new_tokens=32,
                    temperature: float = 0.0):
    """KV-cached autoregressive decode: one prefill pass over the prompt,
    then one single-token program per step (both jit-compiled once).
    Same contract/output as ``greedy_generate``."""
    ids = prompt_ids._data if isinstance(prompt_ids, NDArray) \
        else jnp.asarray(prompt_ids)
    B, T0 = ids.shape
    total = T0 + int(max_new_tokens)
    if total > model.max_length:
        raise MXNetError(f"decode length {total} exceeds max_length "
                         f"{model.max_length}")
    from ..gluon.block import _hybrid_trace_scope
    from .. import autograd

    caches = init_kv_cache(model, B, max_len=total)
    key = _rand.new_key()

    with _hybrid_trace_scope(), autograd._ModeScope(recording=False,
                                                    training=False):
        logits, caches = decode_forward(model, NDArray(ids.astype(
            jnp.int32)), caches, 0, last_only=True)
        last = logits._data[:, 0]

        buf = jnp.zeros((B, total), jnp.int32)
        buf = lax.dynamic_update_slice(buf, ids.astype(jnp.int32), (0, 0))

        def step(t, carry):
            buf, last, key, lcaches = carry
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, last / temperature,
                                             axis=-1)
            else:
                nxt = jnp.argmax(last, axis=-1)
            buf = lax.dynamic_update_slice(
                buf, nxt.astype(jnp.int32)[:, None], (0, t))
            logits, ncaches = decode_forward(
                model, NDArray(nxt.astype(jnp.int32)[:, None]), lcaches, t)
            return (buf, logits._data[:, 0], key, ncaches)

        buf, _, _, _ = lax.fori_loop(T0, total, step,
                                     (buf, last, key, caches))
    return NDArray(buf)

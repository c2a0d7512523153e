"""BERT (the flagship model; BASELINE.md config #3 — GluonNLP
`scripts/bert`, model definition upstream `gluonnlp/model/bert.py`;
file-level citation, SURVEY.md caveat).

TPU-first design decisions:
  - attention runs through the ``scaled_dot_product_attention`` registry op
    (ops/attention.py): one fused XLA computation per layer instead of the
    reference's interleaved_matmul kernel pair; ``flash=True`` selects the
    blockwise kernel for long sequences;
  - tensor-parallel sharding hints are attached to parameters
    (PartitionSpec over the ``tp`` mesh axis: QKV/FFN-in column-sharded,
    output projections row-sharded) so SPMDTrainer/pjit shard the model
    with zero code changes — the idiomatic upgrade of the reference's
    manual group2ctx model parallelism (SURVEY.md §2.3);
  - compute dtype is a constructor knob (bf16 for the MFU target) while
    parameters/layernorm stay fp32 (AMP contract, SURVEY.md §2.2 AMP row).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..ndarray import NDArray
from .. import random as _rand

from ..base import MXNetError
from ..gluon import nn
from ._attention import packed_flash_self_attention, use_packed_fast_path
from ..gluon.block import HybridBlock
from .. import initializer as init
from ..profiler import scope, scoped

__all__ = ["BERTModel", "BERTForPretraining", "BERTClassifier",
           "bert_base", "bert_large", "bert_tiny",
           "pretraining_pipeline"]


class BERTSelfAttention(HybridBlock):
    """Multi-head self-attention with fused QKV projection.

    DESIGN NOTE (deviation from the reference): the reference's attention
    cell (GluonNLP MultiHeadAttentionCell) applies dropout to the
    (B, H, Tq, Tk) attention PROBABILITIES; here the ``dropout`` rate is
    applied once to the attention output instead. Streaming/flash
    attention never materializes the probability matrix — prob-dropout
    would force O(T^2) memory traffic and break the Pallas kernel's
    online softmax — so the regularizer moves to the output projection,
    the standard choice in flash-attention training stacks. Inference
    (dropout off) is bit-identical either way.

    ``seq_parallel=True``: inside a (non-recording) SPMD trace whose mesh
    has an ``sp`` axis, attention rides the sequence-parallel ring
    (parallel/ring_attention.py) with the key-padding mask converted to
    global valid lengths — exact encoder long-context attention with the
    sequence sharded across chips. Falls back to the standard kernel
    everywhere else."""

    def __init__(self, units, num_heads, dropout=0.1, dtype="float32",
                 flash=False, seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        if units % num_heads:
            raise MXNetError(f"units {units} not divisible by heads {num_heads}")
        self._units = units
        self._heads = num_heads
        self._flash = flash
        self._seq_parallel = seq_parallel
        with self.name_scope():
            self.qkv = nn.Dense(3 * units, in_units=units, flatten=False,
                                dtype=dtype, weight_initializer=init.TruncNorm(stdev=0.02))
            self.proj = nn.Dense(units, in_units=units, flatten=False,
                                 dtype=dtype, weight_initializer=init.TruncNorm(stdev=0.02))
            self.dropout = nn.Dropout(dropout)
        # tp sharding: qkv column-parallel, out proj row-parallel
        self.qkv.weight._sharding = P("tp", None)
        self.qkv.bias._sharding = P("tp")
        self.proj.weight._sharding = P(None, "tp")

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        from ..parallel.spmd import constrain
        B, T = x.shape[0], x.shape[1]
        H, D = self._heads, self._units // self._heads
        seq_ax = "sp" if self._seq_parallel else None
        qkv = self.qkv(x)                     # (B, T, 3*H*D)
        mesh = None
        # ring dispatch requires EXPLICIT valid lengths (or no mask):
        # an arbitrary key mask is NOT converted — a non-prefix mask
        # would silently mis-attend, so it always takes the dense path
        if self._seq_parallel and (mask is None or valid_length is not None):
            from ..parallel.ring_attention import active_ring_mesh
            mesh = active_ring_mesh(T)
        # LENGTH form passes through both branches — it is what lets the
        # Pallas flash kernel engage on TPU (a boolean mask alone forces
        # the jnp fallback; see sdpa docstring)
        vl = valid_length.astype("int32") \
            if valid_length is not None else None
        if mesh is None and self._flash \
                and (mask is None or
                     (len(mask.shape) == 2 and vl is not None)) \
                and use_packed_fast_path(D):
            # packed fast path — see models/_attention.py
            out = packed_flash_self_attention(
                F, qkv, B, T, H, D, self._units, mask=mask,
                valid_length=vl, seq_ax=seq_ax)
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
                from ..parallel.ring_attention import ring_self_attention
                out = NDArray(ring_self_attention(
                    q._data, k._data, v._data, mesh=mesh, causal=False,
                    batch_axis=("dp", "fsdp"),
                    valid_length=vl._data if vl is not None else None))
            else:
                out = F.scaled_dot_product_attention(q, k, v, mask=mask,
                                                     flash=self._flash,
                                                     valid_length=vl)
            out = constrain(out, ("dp", "fsdp"), seq_ax, "tp", None)
            out = out.reshape((B, T, self._units))
        return constrain(self.dropout(self.proj(out)),
                         ("dp", "fsdp"), seq_ax, None)


class BERTEncoderLayer(HybridBlock):
    def __init__(self, units, hidden_size, num_heads, dropout=0.1,
                 layer_norm_eps=1e-12, dtype="float32", flash=False,
                 seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        self._seq_parallel = seq_parallel
        with self.name_scope():
            self.attention = BERTSelfAttention(units, num_heads, dropout,
                                               dtype=dtype, flash=flash,
                                               seq_parallel=seq_parallel)
            self.ln1 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
            self.ffn_in = nn.Dense(hidden_size, in_units=units, flatten=False,
                                   dtype=dtype,
                                   weight_initializer=init.TruncNorm(stdev=0.02))
            self.ffn_out = nn.Dense(units, in_units=hidden_size,
                                    flatten=False, dtype=dtype,
                                    weight_initializer=init.TruncNorm(stdev=0.02))
            self.ln2 = nn.LayerNorm(epsilon=layer_norm_eps, in_channels=units)
            self.dropout = nn.Dropout(dropout)
        self.ffn_in.weight._sharding = P("tp", None)
        self.ffn_in.bias._sharding = P("tp")
        self.ffn_out.weight._sharding = P(None, "tp")

    def hybrid_forward(self, F, x, mask=None, valid_length=None):
        from ..parallel.spmd import constrain
        seq_ax = "sp" if self._seq_parallel else None
        # one scope a block part (docs/OBSERVABILITY.md "Named scopes"):
        # metadata on the compiled ops, nothing at run time
        with scope("mx.attn"):
            a = self.attention(x, mask, valid_length)
        with scope("mx.norm"):
            x = self.ln1(x + a)
            x = constrain(x, ("dp", "fsdp"), seq_ax, None)
        with scope("mx.ffn"):
            h = constrain(self.ffn_in(x), ("dp", "fsdp"), seq_ax, "tp")
            h = F.gelu(h)
            h = self.dropout(self.ffn_out(h))
        with scope("mx.norm"):
            return constrain(self.ln2(x + h), ("dp", "fsdp"), seq_ax, None)


class BERTModel(HybridBlock):
    """BERT encoder: embeddings + N transformer layers + pooler.

    forward(input_ids, token_types, valid_length) ->
        (sequence_output (B,T,units), pooled_output (B,units))
    """

    def __init__(self, vocab_size=30522, units=768, hidden_size=3072,
                 num_layers=12, num_heads=12, max_length=512,
                 type_vocab_size=2, dropout=0.1, layer_norm_eps=1e-12,
                 dtype="float32", flash=False, remat=False,
                 seq_parallel=False, **kwargs):
        super().__init__(**kwargs)
        self._units = units
        self._dtype = dtype
        self._remat = remat
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.hidden_size = hidden_size
        self.vocab_size = vocab_size
        with self.name_scope():
            self.word_embed = nn.Embedding(
                vocab_size, units, sharded=True,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.token_type_embed = nn.Embedding(
                type_vocab_size, units,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.position_embed = nn.Embedding(
                max_length, units,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.embed_ln = nn.LayerNorm(epsilon=layer_norm_eps,
                                         in_channels=units)
            self.embed_dropout = nn.Dropout(dropout)
            self.layers = []
            for i in range(num_layers):
                layer = BERTEncoderLayer(units, hidden_size, num_heads,
                                         dropout, layer_norm_eps,
                                         dtype=dtype, flash=flash,
                                         seq_parallel=seq_parallel)
                self.register_child(layer, f"layer{i}")
                setattr(self, f"layer{i}", layer)
            self.pooler = nn.Dense(units, in_units=units, flatten=False,
                                   activation="tanh",
                                   weight_initializer=init.TruncNorm(stdev=0.02))
        # word_embed is vocab-sharded via Embedding(sharded=True) — the
        # TPU analogue of PS-sharded row_sparse embedding weights
        # (SURVEY.md §2.3 last row; see nn.Embedding docstring)

    def hybrid_forward(self, F, input_ids, token_types=None,
                       valid_length=None):
        from ..parallel.spmd import constrain
        B, T = input_ids.shape
        with scope("mx.embed"):
            pos = F.arange(0, T, dtype="int32").reshape((1, T)) \
                .broadcast_to((B, T))
            emb = self.word_embed(input_ids) + self.position_embed(pos)
            if token_types is not None:
                emb = emb + self.token_type_embed(token_types)
            emb = constrain(emb, ("dp", "fsdp"), None, None)
            # enter the compute dtype BEFORE the embedding LN/dropout:
            # both are (B, T, units) elementwise passes, and LN computes
            # its statistics in f32 internally regardless of stream dtype
            if self._dtype != "float32":
                emb = emb.astype(self._dtype)
            x = self.embed_dropout(self.embed_ln(emb))
        mask = None
        if valid_length is not None:
            ar = F.arange(0, T, dtype="float32").reshape((1, T))
            mask = (ar < valid_length.astype("float32").reshape((-1, 1)))
        for i in range(self.num_layers):
            layer = getattr(self, f"layer{i}")
            if self._remat:
                # rematerialize each encoder layer in the backward pass:
                # trades recompute FLOPs for activation HBM so bigger
                # batches fit (see models/_remat.py for the key contract);
                # remat="dots" keeps matmul outputs and recomputes only
                # elementwise work
                from ._remat import remat_call, resolve_policy
                x = remat_call(layer, x, mask, valid_length,
                               policy=resolve_policy(self._remat))
            else:
                x = layer(x, mask, valid_length)
        # sequence output stays in the compute dtype: casting the whole
        # (B, T, units) stream to f32 here poisoned every downstream
        # consumer (the r3 trace shows the MLM gather/scatter running as
        # 42 ms of f32 sort fusions); only the pooled [CLS] path, which
        # is tiny, is promoted
        # batch-pin the pooled stream: the pooler Dense may be
        # fsdp-sharded on out-features, and without this the partitioner
        # propagates a units-over-fsdp layout into the tiny [CLS] path,
        # paying a full rematerialization to reconcile it with the
        # batch-sharded NSP head (the dp>=4 dryrun warning)
        with scope("mx.head"):
            cls = x._op("slice_axis", axis=1, begin=0, end=1).reshape(
                (B, self._units)).astype("float32")
            pooled = constrain(self.pooler(cls), ("dp", "fsdp"), None)
        return x, pooled


class BERTForPretraining(HybridBlock):
    """MLM + NSP pretraining heads (GluonNLP BERTForPretrain parity).

    forward(input_ids, token_types, valid_length, masked_positions) ->
        (mlm_scores (B,M,vocab), nsp_scores (B,2))
    """

    def __init__(self, bert: BERTModel, layer_norm_eps=1e-12, **kwargs):
        super().__init__(**kwargs)
        units = bert._units
        with self.name_scope():
            self.bert = bert
            self.mlm_transform = nn.Dense(
                units, in_units=units, flatten=False,
                weight_initializer=init.TruncNorm(stdev=0.02))
            self.mlm_ln = nn.LayerNorm(epsilon=layer_norm_eps,
                                       in_channels=units)
            # decoder shares the word embedding matrix (tied weights)
            from ..gluon.parameter import Parameter
            self.mlm_bias = Parameter("mlm_bias", shape=(bert.vocab_size,),
                                      init=init.Zero())
            self.nsp = nn.Dense(2, in_units=units,
                                weight_initializer=init.TruncNorm(stdev=0.02))

    def hybrid_forward(self, F, input_ids, token_types, valid_length,
                       masked_positions, mlm_bias=None):
        seq, pooled = self.bert(input_ids, token_types, valid_length)
        with scope("mx.head"):
            # gather masked positions as a one-hot batched matmul:
            # (B,M,T) @ (B,T,units) -> (B,M,units). A take_along_axis
            # gather lowers to sort-based scatter fusions on TPU (42
            # ms/step in the r3 trace, fwd+bwd); the one-hot contraction
            # rides the MXU both directions and is numerically EXACT (each
            # row of the one-hot has a single 1.0, so the "sum" copies one
            # value untouched, any dtype)
            T = seq.shape[1]
            onehot = F.one_hot(masked_positions, depth=T,
                               dtype=self.bert._dtype)
            gathered = F.batch_dot(onehot, seq)
            # head runs in f32 (it is M=76 tokens — cheap); astype's VJP
            # casts the cotangent back to the compute dtype, so the f32
            # head cannot poison the encoder backward stream
            from ..parallel.spmd import constrain
            # keep the (B, M, units) head stream batch-sharded:
            # mlm_transform's weight is fsdp-sharded (out-features), and
            # unconstrained its output inherits a units-over-fsdp layout
            # that the LN backward can only undo with a full
            # rematerialization on dp>=4 meshes — the constraint makes the
            # partitioner all-gather the small weight instead of
            # resharding the activation
            h = constrain(self.mlm_transform(gathered.astype("float32")),
                          ("dp", "fsdp"), None, None)
            h = F.gelu(h)
            h = constrain(self.mlm_ln(h), ("dp", "fsdp"), None, None)
            embed_w = self.bert.word_embed.weight.data()  # (vocab, units)
            # decoder matmul runs in the model compute dtype: with bf16
            # this keeps the (B, M, vocab) logits half-width and the MXU at
            # full rate; the loss (pretraining_loss) does its log-sum-exp
            # reduction with f32 accumulation, so no f32 logits tensor is
            # ever written
            dt = self.bert._dtype
            scores = F.dot(h.astype(dt), embed_w.astype(dt),
                           transpose_b=True) + mlm_bias.astype(dt)
            # vocab-sharded logits on tp meshes: the decoder matmul
            # inherits the embedding table's vocab-dim sharding instead of
            # allgathering a (B, M, vocab) replicated tensor; the loss's
            # logsumexp then reduces across tp via an XLA psum
            scores = constrain(scores, ("dp", "fsdp"), None, "tp")
            return scores, self.nsp(pooled)


def pretraining_loss(model: BERTForPretraining, input_ids, token_types,
                     valid_length, masked_positions, masked_labels,
                     masked_weights, nsp_labels):
    """Scalar pretraining loss (MLM + NSP), shaped for SPMDTrainer's
    ``forward_loss`` hook."""
    from .. import ndarray as nd

    mlm_scores, nsp_scores = model(input_ids, token_types, valid_length,
                                   masked_positions)
    # CE as pick - logsumexp: gathers one score per position and reduces
    # the vocab axis with f32 accumulation — the full (B, M, vocab)
    # log-prob tensor is never materialized (it is ~300 MB in f32 at the
    # bench shapes, and writing it dominated the head's step time)
    label_scores = mlm_scores.pick(masked_labels, axis=-1)  # (B, M)
    lse = mlm_scores._op("logsumexp", axis=-1)
    mlm_ll = label_scores.astype("float32") - lse
    denom = masked_weights.sum() + 1e-6
    mlm_loss = -(mlm_ll * masked_weights).sum() / denom
    nsp_logp = nsp_scores.log_softmax(axis=-1)
    nsp_loss = -nsp_logp.pick(nsp_labels, axis=-1).mean()
    return mlm_loss + nsp_loss


def pretraining_pipeline(model: BERTForPretraining):
    """PipelineSpec for ``pretraining_loss`` under the pipelined SPMD
    step (parallel/pipelined.py): stem = embeddings + embedding LN/
    dropout, one pipeline block per encoder layer (attention mask and
    valid_length ride the parameter-free context), head = pooler + MLM
    transform/decoder + NSP with the MLM/NSP losses emitted as LOCAL
    partial sums. Batch layout matches ``pretraining_loss``:
    (input_ids, token_types, valid_length, masked_positions,
    masked_labels, masked_weights, nsp_labels). Stem/head replicate the
    forward op-for-op so loss/grads are bitwise vs the GSPMD step."""
    from ..parallel.pipelined import PipelineSpec
    from ..gluon.block import nd as F
    bert = model.bert

    def stem(input_ids, token_types, valid_length, *rest):
        from ..parallel.spmd import constrain
        B, T = input_ids.shape
        pos = F.arange(0, T, dtype="int32").reshape((1, T)) \
            .broadcast_to((B, T))
        emb = bert.word_embed(input_ids) + bert.position_embed(pos)
        if token_types is not None:
            emb = emb + bert.token_type_embed(token_types)
        emb = constrain(emb, ("dp", "fsdp"), None, None)
        if bert._dtype != "float32":
            emb = emb.astype(bert._dtype)
        return bert.embed_dropout(bert.embed_ln(emb))

    def context(input_ids, token_types, valid_length, *rest):
        T = input_ids.shape[1]
        mask = None
        if valid_length is not None:
            ar = F.arange(0, T, dtype="float32").reshape((1, T))
            mask = (ar < valid_length.astype("float32").reshape((-1, 1)))
        return (mask, valid_length)

    def head(x, input_ids, token_types, valid_length, masked_positions,
             masked_labels, masked_weights, nsp_labels):
        from ..parallel.spmd import constrain
        B, T = x.shape[0], x.shape[1]
        cls = x._op("slice_axis", axis=1, begin=0, end=1).reshape(
            (B, bert._units)).astype("float32")
        pooled = constrain(bert.pooler(cls), ("dp", "fsdp"), None)
        onehot = F.one_hot(masked_positions, depth=T, dtype=bert._dtype)
        gathered = F.batch_dot(onehot, x)
        h = constrain(model.mlm_transform(gathered.astype("float32")),
                      ("dp", "fsdp"), None, None)
        h = F.gelu(h)
        h = constrain(model.mlm_ln(h), ("dp", "fsdp"), None, None)
        embed_w = bert.word_embed.weight.data()
        dt = bert._dtype
        scores = F.dot(h.astype(dt), embed_w.astype(dt),
                       transpose_b=True) + model.mlm_bias.data().astype(dt)
        scores = constrain(scores, ("dp", "fsdp"), None, "tp")
        nsp_scores = model.nsp(pooled)
        with scope("mx.loss"):
            label_scores = scores.pick(masked_labels, axis=-1)   # (B, M)
            lse = scores._op("logsumexp", axis=-1)
            mlm_ll = label_scores.astype("float32") - lse
            nsp_logp = nsp_scores.log_softmax(axis=-1)
            nsp_pick = nsp_logp.pick(nsp_labels, axis=-1)        # (B,)
            return ((mlm_ll * masked_weights).sum(),
                    masked_weights.sum(), nsp_pick.sum(),
                    NDArray(jnp.float32(nsp_pick._data.size)))

    def finalize(n_mlm, d_mlm, n_nsp, d_nsp):
        # mirrors pretraining_loss: mlm_loss + nsp_loss, with the MLM
        # denominator's +1e-6 applied to the GLOBAL weight sum
        return -(n_mlm / (d_mlm + 1e-6)) - (n_nsp / d_nsp)

    blocks = [getattr(bert, f"layer{i}") for i in range(bert.num_layers)]
    return PipelineSpec(
        blocks=blocks, head=scoped("mx.head", head), finalize=finalize,
        stem=scoped("mx.embed", stem), context=context,
        stem_modules=[bert.word_embed, bert.token_type_embed,
                      bert.position_embed, bert.embed_ln],
        head_modules=[bert.pooler, model.mlm_transform, model.mlm_ln,
                      model.nsp, model.mlm_bias, bert.word_embed],
        name="bert_pretrain")


def bert_tiny(vocab_size=1024, max_length=128, **kwargs) -> BERTModel:
    """Small config for tests/dry-runs."""
    return BERTModel(vocab_size=vocab_size, units=128, hidden_size=512,
                     num_layers=2, num_heads=2, max_length=max_length,
                     **kwargs)


def bert_base(**kwargs) -> BERTModel:
    return BERTModel(vocab_size=30522, units=768, hidden_size=3072,
                     num_layers=12, num_heads=12, **kwargs)


def bert_large(**kwargs) -> BERTModel:
    return BERTModel(vocab_size=30522, units=1024, hidden_size=4096,
                     num_layers=24, num_heads=16, **kwargs)


class BERTClassifier(HybridBlock):
    """Sentence(-pair) classification head on a BERT encoder (parity:
    GluonNLP bert.BERTClassifier — the fine-tuning surface of
    scripts/bert/finetune_classifier.py).

    forward(input_ids, token_types, valid_length) -> (B, num_classes)
    logits from a dropout + dense head over the pooled [CLS] output.
    """

    def __init__(self, bert: BERTModel, num_classes=2, dropout=0.1,
                 **kwargs):
        super().__init__(**kwargs)
        with self.name_scope():
            self.bert = bert
            self.dropout = nn.Dropout(dropout)
            self.classifier = nn.Dense(
                num_classes, in_units=bert._units,
                weight_initializer=init.TruncNorm(stdev=0.02))

    def hybrid_forward(self, F, input_ids, token_types=None,
                       valid_length=None):
        _, pooled = self.bert(input_ids, token_types, valid_length)
        return self.classifier(self.dropout(pooled))

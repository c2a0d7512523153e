"""Hybrid state-space / attention decoder (the ``granitemoehybrid`` family
with no experts: IBM Granite 4.0-H; the Mamba-2 mixer in the Bamba form).

Pre-norm blocks by ``layer_types``: a Mamba-2 mixer or grouped-query
causal attention with NO position information of any kind, then a gated
(SwiGLU) feed-forward; RMS norms; a head tied to the embedding; Granite's
four multipliers. With ``e`` the embedding, ``r`` the residual, ``a`` the
attention multiplier and ``s`` the logits scaling,
``n(x; w) = x / sqrt(mean(x^2) + eps) * w`` in float32:

    h = e * E[ids]
    each layer:  h = h + r * mixer(n(h; w1));  h = h + r * mlp(n(h; w2))
    logits = n(h; wf) @ E^T / s

    mlp(x)    = W_out (silu(u) * g),  [u, g] = split(W_in x)
    attention = softmax(a * q k^T, causal) v, query head j on key-value
                head j // (heads / kv_heads); then W_o
    mamba     : [z, xBC, dt] = split(W_in x)
                xBC = silu(causal_depthwise_conv(xBC) + b_conv)
                [x, B, C] = split(xBC);  dt = softplus(dt + dt_bias)
                S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  A = -exp(A_log)
                y_t = S_t C_t + D x_t
                out = W_out n(y * silu(z); w_norm)     (one group)

The state update lives in ops/ssm_scan.py. ``hybrid_forward`` runs whole
sequences (dense causal attention, the chunked scan from a zero state);
``cached_forward`` is the serving seam (docs/SERVING.md "What the engine
asks of a model"): the model owns this math, the caller owns where keys,
values and state rows are kept. The math is written on jnp arrays (the
parameters are read through ``Parameter.data()``, so a traced binding as
in ``SPMDTrainer`` or the engine's ``_model_scope`` works; the eager
autograd tape does not see it).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError
from ..gluon.block import HybridBlock
from ..ndarray import NDArray
from .. import initializer as init
from ..ops import ssm_scan
from ..profiler import scope

__all__ = ["GraniteHybridModel", "granite_hybrid_mini"]


class _Leaves(HybridBlock):
    """Holds one layer's parameters; the model's methods do the math."""

    def leaf(self, name, shape, dtype="float32", initializer=None):
        with self.name_scope():
            p = self.params.get(name, shape=tuple(shape), dtype=dtype,
                                init=initializer)
        setattr(self, name, p)
        return p

    def hybrid_forward(self, F, *args, **kwargs):
        raise MXNetError("a parameter holder of GraniteHybridModel is not "
                         "called; call the model")


def _val(p):
    return p.data()._data


def _dot(x, p):
    """x (..., in) @ weight (out, in)^T in x's type."""
    w = _val(p)
    return lax.dot_general(x, w.astype(x.dtype),
                           (((x.ndim - 1,), (1,)), ((), ())))


def _rms_norm(x, p, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * lax.rsqrt(var + eps) * _val(p)


class GraniteHybridModel(HybridBlock):
    """forward(input_ids (B, T)) -> logits (B, T, vocab), float32.

    ``layer_types``: ``"mamba"`` or ``"attention"`` a layer. Matrices are
    kept in ``dtype``; the embedding, the norms, the convolution and the
    state heads' ``A_log`` / ``dt_bias`` / ``D`` in float32. ``state_dtype``
    is what a cache keeps the recurrent state in."""

    def __init__(self, vocab_size, units, hidden_size, layer_types,
                 num_heads, num_kv_heads, ssm_heads, ssm_head_dim,
                 ssm_state, ssm_conv=4, ssm_groups=1, head_dim=None,
                 embedding_multiplier=1.0, residual_multiplier=1.0,
                 attention_multiplier=None, logits_scaling=1.0,
                 rms_eps=1e-5, max_length=131072, dtype="float32",
                 state_dtype="float32", **kwargs):
        super().__init__(**kwargs)
        if ssm_groups != 1:
            raise MXNetError("GraniteHybridModel: one B/C group only "
                             f"(mamba_n_groups {ssm_groups})")
        bad = [t for t in layer_types if t not in ("mamba", "attention")]
        if bad:
            raise MXNetError(f"GraniteHybridModel: layer types {bad}")
        if num_heads % num_kv_heads:
            raise MXNetError(f"heads {num_heads} % kv heads "
                             f"{num_kv_heads} != 0")
        self.vocab_size = vocab_size
        self.max_length = max_length
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self._units, self._ffn = units, hidden_size
        self._heads, self._kv_heads = num_heads, num_kv_heads
        self._head_dim = head_dim or units // num_heads
        self._ssm = (ssm_heads, ssm_head_dim, ssm_state, ssm_conv)
        self._e, self._r = embedding_multiplier, residual_multiplier
        self._a = attention_multiplier if attention_multiplier is not None \
            else self._head_dim ** -0.5
        self._s, self._eps = logits_scaling, rms_eps
        self._dtype, self._state_dtype = dtype, state_dtype
        mat = init.TruncNorm(stdev=0.02)
        H, P, N, K = self._ssm
        inner, conv_dim = H * P, H * P + 2 * N
        hq, hkv = num_heads * self._head_dim, num_kv_heads * self._head_dim
        with self.name_scope():
            self.embed = _Leaves(prefix="embed_")
            self.embed.leaf("weight", (vocab_size, units), initializer=mat)
            self.final_norm = _Leaves(prefix="final_norm_")
            self.final_norm.leaf("weight", (units,), initializer="ones")
            for i, kind in enumerate(self.layer_types):
                lay = _Leaves(prefix=f"layer{i}_")
                lay.leaf("norm1", (units,), initializer="ones")
                lay.leaf("norm2", (units,), initializer="ones")
                lay.leaf("mlp_in", (2 * hidden_size, units), dtype, mat)
                lay.leaf("mlp_out", (units, hidden_size), dtype, mat)
                if kind == "mamba":
                    lay.leaf("ssm_in", (2 * inner + 2 * N + H, units),
                             dtype, mat)
                    lay.leaf("conv_w", (conv_dim, K),
                             initializer=init.Uniform(K ** -0.5))
                    lay.leaf("conv_b", (conv_dim,), initializer="zeros")
                    # dt near 0.01 and A = -1: a state that remembers
                    # some hundred positions
                    lay.leaf("dt_bias", (H,),
                             initializer=init.Constant(-4.6))
                    lay.leaf("A_log", (H,), initializer="zeros")
                    lay.leaf("D", (H,), initializer="ones")
                    lay.leaf("ssm_norm", (inner,), initializer="ones")
                    lay.leaf("ssm_out", (units, inner), dtype, mat)
                else:
                    lay.leaf("qkv", (hq + 2 * hkv, units), dtype, mat)
                    lay.leaf("o", (units, hq), dtype, mat)
                self.register_child(lay, f"layer{i}")
                setattr(self, f"layer{i}", lay)

    # --------------------------------------------------------------- #
    # what a cache has to hold
    # --------------------------------------------------------------- #

    def cache_layout(self):
        """A layer an entry: ``{"kind": "kv", "kv_heads", "head_dim",
        "scale"}`` for keys and values a position, or ``{"kind": "state",
        "rows": {name: (shape a slot, dtype)}}`` for rows that exist once
        a sequence: ``ssm`` the packed recurrent state
        (ops/ssm_scan.py::state_row_shape), ``conv`` the last K - 1 inputs
        of the convolution."""
        H, P, N, K = self._ssm
        def entry(kind):
            if kind == "attention":
                return {"kind": "kv", "kv_heads": self._kv_heads,
                        "head_dim": self._head_dim, "scale": self._a}
            return {"kind": "state", "rows": {
                "ssm": (ssm_scan.state_row_shape(H, P, N),
                        self._state_dtype),
                "conv": ((K - 1, H * P + 2 * N), self._dtype)}}

        return [entry(t) for t in self.layer_types]

    # --------------------------------------------------------------- #
    # the layers
    # --------------------------------------------------------------- #

    def _mlp(self, lay, x):
        with scope("mx.norm"):
            n = _rms_norm(x, lay.norm2, self._eps).astype(x.dtype)
        with scope("mx.ffn"):
            u, g = jnp.split(_dot(n, lay.mlp_in), 2, axis=-1)
            h = (jax.nn.silu(u.astype(jnp.float32))
                 * g.astype(jnp.float32)).astype(x.dtype)
            return x + self._r * _dot(h, lay.mlp_out)

    def _attention(self, lay, i, x, attend):
        B, T, _ = x.shape
        D, hq, hkv = self._head_dim, self._heads, self._kv_heads
        with scope("mx.norm"):
            n = _rms_norm(x, lay.norm1, self._eps).astype(x.dtype)
        with scope("mx.attn"):
            qkv = _dot(n, lay.qkv)
            q = qkv[..., :hq * D].reshape(B, T, hq, D)
            k = qkv[..., hq * D:(hq + hkv) * D].reshape(B, T, hkv, D)
            v = qkv[..., (hq + hkv) * D:].reshape(B, T, hkv, D)
            out = attend(i, q, k, v).reshape(B, T, hq * D)
            return x + self._r * _dot(out.astype(x.dtype), lay.o)

    def _mamba(self, lay, i, x, state, real):
        B, T, _ = x.shape
        H, P, N, K = self._ssm
        inner = H * P
        with scope("mx.norm"):
            n = _rms_norm(x, lay.norm1, self._eps).astype(x.dtype)
        with scope("mx.ssm"):
            zxd = _dot(n, lay.ssm_in)
            z = zxd[..., :inner]
            xbc = zxd[..., inner:2 * inner + 2 * N]
            dt = jax.nn.softplus(zxd[..., 2 * inner + 2 * N:]
                                 .astype(jnp.float32) + _val(lay.dt_bias))
            A = -jnp.exp(_val(lay.A_log))

            def update(rows, interpret=None):
                conv, tail = ssm_scan.causal_conv(
                    rows["conv"], xbc, _val(lay.conv_w), _val(lay.conv_b),
                    real)
                conv = jax.nn.silu(conv)
                xs = conv[..., :inner].reshape(B, T, H, P)
                Bm = conv[..., inner:inner + N]
                Cm = conv[..., inner + N:]
                with scope("mx.ssm_state"):
                    if T == 1:
                        live = jnp.ones((B,), bool) if real is None \
                            else real[:, 0]
                        y, new = ssm_scan.ssm_decode(
                            rows["ssm"], xs[:, 0], dt[:, 0], A, Bm[:, 0],
                            Cm[:, 0], live, interpret=interpret)
                        y = y[:, None]
                    else:
                        y, new = ssm_scan.ssm_chunk_scan(
                            ssm_scan.unpack_state(rows["ssm"], P), xs, dt,
                            A, Bm, Cm, real)
                        new = ssm_scan.pack_state(new)
                y = y + _val(lay.D)[:, None] * xs
                return y, {"ssm": new.astype(rows["ssm"].dtype),
                           "conv": tail}

            y = state(i, update).reshape(B, T, inner)
            y = y * jax.nn.silu(z.astype(jnp.float32))
            y = _rms_norm(y, lay.ssm_norm, self._eps).astype(x.dtype)
            return x + self._r * _dot(y, lay.ssm_out)

    def _forward(self, ids, attend, state, real, last_row):
        with scope("mx.embed"):
            x = self._e * _val(self.embed.weight)[ids]
            x = x.astype(self._dtype)
        for i, kind in enumerate(self.layer_types):
            lay = getattr(self, f"layer{i}")
            if kind == "mamba":
                x = self._mamba(lay, i, x, state, real)
            else:
                x = self._attention(lay, i, x, attend)
            x = self._mlp(lay, x)
        if last_row is not None:
            x = lax.dynamic_slice(x, (0, last_row, 0),
                                  (x.shape[0], 1, x.shape[2]))
        with scope("mx.norm"):
            x = _rms_norm(x, self.final_norm.weight, self._eps)
        with scope("mx.head"):
            w = _val(self.embed.weight)
            return lax.dot_general(
                x, w, (((2,), (1,)), ((), ()))) / self._s

    def zero_rows(self, batch):
        """Fresh state rows for ``batch`` sequences, a state layer an
        entry (None for an attention layer)."""
        return [None if lay["kind"] == "kv" else
                {name: jnp.zeros((batch,) + tuple(shape), dtype)
                 for name, (shape, dtype) in lay["rows"].items()}
                for lay in self.cache_layout()]

    def hybrid_forward(self, F, input_ids):
        ids = input_ids._data if isinstance(input_ids, NDArray) \
            else input_ids
        B, T = ids.shape
        rows = self.zero_rows(B)
        causal = (jnp.arange(T)[None, :] <= jnp.arange(T)[:, None])[None,
                                                                     None]
        rep = self._heads // self._kv_heads

        def attend(i, q, k, v):
            k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
            s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                           k.astype(jnp.float32)) * self._a
            p = jax.nn.softmax(jnp.where(causal, s, -1e30), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p,
                              v.astype(jnp.float32)).astype(q.dtype)

        def state(i, update):
            return update(rows[i])[0]

        return NDArray(self._forward(ids, attend, state, None, None))

    def cached_forward(self, ids, pos, attend, last_row=None, state=None,
                       real=None):
        """Inference forward of tokens ``ids`` (B, T) against a cache the
        caller keeps. ``attend(i, q, k, v)`` owns an attention layer's
        keys and values: q (B, T, heads, D), k and v (B, T, kv_heads, D)
        in, the attention output (B, T, heads, D) out, scaled by the
        layout's ``scale``. ``state(i, update)`` owns a state layer's
        rows: it calls ``update(rows, interpret=None)`` with the rows of
        this call's B sequences (``{name: (B, ...)}``, the whole cache at a
        decode step) and gets back ``(y, new rows)``; it keeps the rows
        and returns ``y``. ``real`` (B, T) bool says which positions exist
        (real ones first): a position that does not advances no state,
        the convolution's tail is the last three REAL inputs, and a row
        with none keeps its rows bit for bit. ``pos`` is not read: the
        model has no positions. Returns logits (B, T or 1, vocab), f32."""
        del pos
        return self._forward(ids, attend, state, real, last_row)


def granite_hybrid_mini(vocab_size=256, layer_types=None, **kwargs):
    """A small hybrid for tests: width 128, 4 state heads of 32 with state
    16, 4 query over 2 key-value heads of 32."""
    layer_types = layer_types or ["mamba", "mamba", "attention", "mamba",
                                  "mamba", "mamba", "attention"]
    cfg = dict(units=128, hidden_size=256, num_heads=4, num_kv_heads=2,
               ssm_heads=4, ssm_head_dim=32, ssm_state=16,
               embedding_multiplier=12.0, residual_multiplier=0.22,
               attention_multiplier=1.0 / 64, logits_scaling=8.0,
               max_length=256)
    cfg.update(kwargs)
    return GraniteHybridModel(vocab_size, layer_types=layer_types, **cfg)

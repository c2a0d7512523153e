"""The arithmetic the plain references are written in.

``Ops("f32")`` is the reference: float32 with ``precision=HIGHEST`` (on a TPU
a float32 matmul otherwise runs in bfloat16 passes). ``Ops("fp8")`` is the
control for a bfloat16 configuration, the nearest precision below it and the
step that would tempt a later PR: every product with a weight matrix takes
both operands through float8 e4m3 (three bits of mantissa; one scale a tensor,
set from its largest magnitude), with a straight-through gradient. (int8 with
a scale for each row was tried first: at these widths it is as fine as
bfloat16 and separates nothing, see PERF.md.) ``Ops("bf16")`` rounds both
operands to bfloat16 instead: the control for a float32 configuration, and the
second witness for what bfloat16 compute weights do to a job (PERF.md).
"""

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _fake_fp8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def round_to(x, dtype):
    """``x`` (float32) rounded to ``dtype``'s exponent and mantissa, still in
    float32. ``x.astype(dtype).astype(float32)`` is not that on a TPU: XLA may
    keep the excess precision and drop both converts, which it did to this
    file's first bfloat16 mode (PERF.md, review round)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _fake_bf16(x):
    return x + jax.lax.stop_gradient(round_to(x, jnp.bfloat16) - x)


_ROUND = {"f32": None, "fp8": _fake_fp8, "bf16": _fake_bf16}


class Ops:
    def __init__(self, mode="f32"):
        if mode not in _ROUND:
            raise ValueError(mode)
        self.round = _ROUND[mode]

    def dot(self, x, w):
        """``x (..., in) @ w (out, in)^T`` -> ``(..., out)`` in float32."""
        x = x.astype(jnp.float32)
        w = w.astype(jnp.float32)
        if self.round is not None:
            x, w = self.round(x), self.round(w)
        return jnp.einsum("...i,oi->...o", x, w, precision=HI)

    @staticmethod
    def einsum(expr, a, b):
        """Attention's own products stay in float32 in every mode."""
        return jnp.einsum(expr, a.astype(jnp.float32),
                          b.astype(jnp.float32), precision=HI)


def layer_norm(x, gamma, beta, eps):
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * gamma + beta


def gelu_erf(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / jnp.sqrt(2.0).astype(x.dtype)))


def attention(ops, q, k, v, mask):
    """q (B,Tq,H,D), k/v (B,Tk,H,D), mask broadcastable to (B,H,Tq,Tk) or
    None -> (B,Tq,H*D). Softmax in float32."""
    d = q.shape[-1]
    s = ops.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = ops.einsum("bhqk,bkhd->bqhd", p, v)
    return o.reshape(o.shape[0], o.shape[1], -1)

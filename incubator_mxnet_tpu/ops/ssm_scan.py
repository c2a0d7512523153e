"""Mamba-2 state-space mixer: the one-token state update of a decode step
and the chunked (matrix) scan of a prefill chunk.

A head's state is a (P, N) matrix (P the head's width, N the state size);
over positions

    S_t = exp(dt_t * A) * S_{t-1} + dt_t * x_t B_t^T        y_t = S_t C_t

with ``A < 0`` a head, ``dt_t > 0`` a head and position, ``x_t`` (P,) a
head, ``B_t`` and ``C_t`` (N,) shared by the heads (one group). The skip
``D * x_t``, the convolution before and the gated norm after are the
model's (models/granite_hybrid.py); ``causal_conv`` is here because its
tail is cache state like ``S``.

Two operations, as serving needs them:

``ssm_decode`` (kernel ``mxtpu_ssm_decode``): one new position for every
slot of a batch, the states updated IN PLACE. It is bound by bytes: a live
slot's state is read once and written once (2 x 2 MB a layer at 64 heads of
64 x 128 in f32) for a handful of operations an element. One program a
block of a live slot's heads; the live slots' indices are compacted and
scalar-prefetched, so a dead slot costs neither a DMA nor a store, and its
rows stay bit for bit what they were (``input_output_aliases``; the grid
steps past the last live slot revisit the last block, which Pallas neither
fetches nor writes again).

The cache keeps a state TRANSPOSED AND PACKED, ``(H / g, N, g * P)`` with
``g = 128 // P`` heads side by side on the lanes (``state_row_shape``): the
update is then elementwise with a sublane broadcast of ``dt x`` and the
decay and a lane broadcast of ``B``, and ``y`` is a sum over sublanes and
vregs. In the (H, P, N) layout ``y`` is a lane reduction a row, seven
rotate-and-add steps for each of 4096 rows a slot, more than the bytes
cost. ``pack_state`` / ``unpack_state`` convert; only a prefill chunk's one
slot ever is converted.

``ssm_chunk_scan``: T positions of one sequence at once, from an incoming
state, in the chunked form (the "SSD" form of the Mamba-2 paper): inside a
block of Q positions the recurrence is a masked (Q, Q) matrix a head, and
the incoming state enters through one more product; blocks follow one
another through a ``lax.scan``. Plain ``jnp`` einsums, float32 at
``HIGHEST`` (they are under a tenth of a chunk's matmul work; the state is
what the next thousand positions read). A position that is not ``real``
gets ``dt = 0``: it decays nothing, adds nothing and the state passes it
unchanged, which is how a chunk's padding and a dead row are handled.

``ssm_scan_reference`` is the recurrence as a recurrence, the twin the
tests hold both to. The dispatch is read off the platform as the ragged
kernels' is (``ops.pallas_attention.pallas_path``): the Mosaic kernel on a
TPU, the ``jnp`` twin elsewhere, the kernel in the interpreter with
``interpret=True``. ``dispatch_tally`` counts which one each call site got
(``profiler.ssm_dispatch``).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_attention as _pa

__all__ = ["ssm_decode", "ssm_decode_reference", "ssm_chunk_scan",
           "ssm_scan_reference", "causal_conv", "state_row_shape",
           "pack_state", "unpack_state", "dispatch_tally"]

_HI = lax.Precision.HIGHEST
_LANES = 128

# Trace-time count of which implementation each call site got:
# ``ssm_decode_pallas`` (the kernel), ``ssm_decode_jnp`` (its twin),
# ``ssm_chunk_jnp`` (the chunked scan, which has one form).
_DISPATCH = collections.Counter()


def dispatch_tally(reset=False):
    """{implementation: call sites traced so far};
    ``profiler.ssm_dispatch`` is its public face."""
    tally = dict(_DISPATCH)
    if reset:
        _DISPATCH.clear()
    return tally


def _lane_heads(heads, head_dim):
    """Heads side by side on the lanes of a packed state row."""
    g = max(1, _LANES // head_dim)
    return g if heads % g == 0 else 1


def state_row_shape(heads, head_dim, state_dim):
    """Shape of one slot's packed state: (H / g, N, g * P)."""
    g = _lane_heads(heads, head_dim)
    return heads // g, state_dim, g * head_dim


def pack_state(s):
    """(..., H, P, N) -> (..., H / g, N, g * P)."""
    *lead, H, P, N = s.shape
    g = _lane_heads(H, P)
    n = len(lead)
    s = s.reshape(*lead, H // g, g, P, N)
    s = s.transpose(*range(n), n, n + 3, n + 1, n + 2)
    return s.reshape(*lead, H // g, N, g * P)


def unpack_state(s, head_dim):
    """(..., H / g, N, g * P) -> (..., H, P, N)."""
    *lead, H2, N, L = s.shape
    g = L // head_dim
    n = len(lead)
    s = s.reshape(*lead, H2, N, g, head_dim)
    s = s.transpose(*range(n), n, n + 2, n + 3, n + 1)
    return s.reshape(*lead, H2 * g, head_dim, N)


def _decode_operands(state, x, dt, A):
    """The per-lane decay and input of a packed update: (S, H2, 1, L)
    each, a head's value repeated over its P lanes."""
    S, H, P = x.shape
    H2, _, L = state.shape[1:]
    decay = jnp.exp(dt * A)                                   # (S, H)
    decay = jnp.broadcast_to(decay[..., None], (S, H, P))
    u = dt[..., None] * x.astype(jnp.float32)
    return decay.reshape(S, H2, 1, L), u.reshape(S, H2, 1, L)


def ssm_decode_reference(state, x, dt, A, Bm, Cm, live):
    """The kernel's ``jnp`` twin, on the packed state. state
    (S, H2, N, L) f32 or bf16; x (S, H, P); dt (S, H) f32, after the
    softplus; A (H,) f32; Bm, Cm (S, N); live (S,) bool. Returns (y
    (S, H, P) f32, new state); a dead slot's state is the array it came in
    as and its y 0."""
    decay, u = _decode_operands(state, x, dt, A)
    b = Bm.astype(jnp.float32)[:, None, :, None]              # (S,1,N,1)
    c = Cm.astype(jnp.float32)[:, None, :, None]
    new = decay * state.astype(jnp.float32) + b * u
    y = jnp.sum(new * c, axis=2)                              # (S, H2, L)
    keep = live[:, None, None, None]
    return (jnp.where(live[:, None, None], y, 0.0).reshape(x.shape),
            jnp.where(keep, new.astype(state.dtype), state))


def _head_block(H2, N, L):
    """Packed heads a program takes: as many as keep a block within
    1 MiB (the in and out blocks are double-buffered: 4 MiB of VMEM),
    a divisor of H2 that is H2 itself or a multiple of 8."""
    cap = max(1, (1 << 20) // (N * L * 4))
    return max((d for d in range(1, H2 + 1) if H2 % d == 0 and d <= cap
                and (d % 8 == 0 or d == H2)), default=H2)


def _ssm_decode_kernel(idx_ref, n_ref, s_ref, a_ref, u_ref, b_ref, c_ref,
                       o_ref, y_ref):
    from jax.experimental import pallas as pl

    live = pl.program_id(0) < n_ref[0]

    @pl.when(live)
    def _update():
        b = b_ref[0]                            # (N, 1): over the lanes
        c = c_ref[0]

        def head(h, carry):
            new = a_ref[0, h] * s_ref[0, h].astype(jnp.float32) \
                + b * u_ref[0, h]                               # (N, L)
            o_ref[0, h] = new.astype(o_ref.dtype)
            y_ref[0, h] = jnp.sum(new * c, axis=0, keepdims=True)
            return carry

        lax.fori_loop(0, s_ref.shape[1], head, 0)

    # no live slot at all: every step maps to one block that no step
    # computes, and Pallas still writes it back once: hand it its input
    @pl.when(n_ref[0] == 0)
    def _nothing():
        o_ref[...] = s_ref[...]
        y_ref[...] = jnp.zeros_like(y_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ssm_decode_pallas(state, decay, u, b, c, live, interpret):
    """state (S, H2, N, L), f32 or (the precision below, for the
    benchmark's control) bf16, updated in place for the live slots: the
    update and ``y`` are float32 whatever the state is kept in. decay, u
    (S, H2, 1, L); b, c (S, N, 1). Returns (y (S, H2, 1, L), state)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H2, N, L = state.shape
    hb = _head_block(H2, N, L)
    nj = H2 // hb
    # live slots' indices first, in order; the rest repeat the last live
    # one, so that the steps past it revisit its last block. The k-th
    # live slot is the first whose running count of live slots reaches
    # k + 1: S x S compares, no sort (a sort of 64 keys costs the chip
    # more than a layer's kernel)
    cum = jnp.cumsum(live.astype(jnp.int32))
    n_live = cum[-1]
    k = jnp.minimum(jnp.arange(S), jnp.maximum(n_live - 1, 0))
    idx = jnp.sum(cum[None, :] <= k[:, None], axis=1).astype(jnp.int32)
    idx = jnp.minimum(idx, S - 1)           # no live slot: any block

    def heads_map(i, j, idx_ref, n_ref):
        return (idx_ref[i], jnp.where(i < n_ref[0], j, nj - 1), 0, 0)

    def slot_map(i, j, idx_ref, n_ref):
        return (idx_ref[i], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S, nj),
        in_specs=[
            pl.BlockSpec((1, hb, N, L), heads_map),
            pl.BlockSpec((1, hb, 1, L), heads_map),
            pl.BlockSpec((1, hb, 1, L), heads_map),
            pl.BlockSpec((1, N, 1), slot_map),
            pl.BlockSpec((1, N, 1), slot_map),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, N, L), heads_map),
            pl.BlockSpec((1, hb, 1, L), heads_map),
        ],
    )
    new, y = pl.pallas_call(
        _ssm_decode_kernel,
        name="mxtpu_ssm_decode",
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((S, H2, 1, L), jnp.float32)],
        input_output_aliases={2: 0},        # after idx and n_live
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 << 20),
        interpret=interpret,
    )(idx, n_live[None], state, decay, u, b, c)
    return y, new


def ssm_decode(state, x, dt, A, Bm, Cm, live, interpret=None):
    """One position a slot against the packed state cache, in place for
    the live slots; see ``ssm_decode_reference`` for the arguments."""
    if interpret is None:
        interpret = _pa._env_interpret()
    if not _pa.pallas_path(interpret):
        _DISPATCH["ssm_decode_jnp"] += 1
        return ssm_decode_reference(state, x, dt, A, Bm, Cm, live)
    _DISPATCH["ssm_decode_pallas"] += 1
    decay, u = _decode_operands(state, x, dt, A)
    y, new = _ssm_decode_pallas(
        state, decay, u, Bm.astype(jnp.float32)[..., None],
        Cm.astype(jnp.float32)[..., None], live, interpret)
    # a dead slot's block of y is never visited: what lies there is
    # whatever the buffer held
    y = jnp.where(live[:, None, None, None], y, 0.0)
    return y.reshape(x.shape), new


def _scan_block(state, blk):
    """One block of Q positions in the matrix form. state (B, H, P, N);
    blk: x (B, Q, H, P) already times dt, a (B, Q, H) = dt * A, Bm and Cm
    (B, Q, N). Returns (state after the block, y (B, Q, H, P))."""
    xdt, a, Bm, Cm = blk
    Q = a.shape[1]
    cum = jnp.cumsum(a, axis=1)                               # (B, Q, H)
    # inside the block: y_t = sum_{s <= t} (C_t . B_s) exp(cum_t - cum_s)
    # dt_s x_s
    seg = cum[:, :, None, :] - cum[:, None, :, :]             # (B,t,s,H)
    tri = (jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :])[None, :, :,
                                                             None]
    decay = jnp.exp(jnp.where(tri, seg, -jnp.inf))
    G = jnp.einsum("btn,bsn->bts", Cm, Bm, precision=_HI)
    y = jnp.einsum("btsh,bshp->bthp", G[..., None] * decay, xdt,
                   precision=_HI)
    # what the incoming state adds: exp(cum_t) * (S_in C_t)
    y = y + jnp.einsum("bhpn,btn->bthp", state, Cm, precision=_HI) \
        * jnp.exp(cum)[..., None]
    # the state after the block's last position
    to_end = jnp.exp(cum[:, -1:, :] - cum)                    # (B, Q, H)
    new = state * jnp.exp(cum[:, -1])[..., None, None] + jnp.einsum(
        "bshp,bsn->bhpn", xdt * to_end[..., None], Bm, precision=_HI)
    return new, y


def ssm_chunk_scan(state, x, dt, A, Bm, Cm, real=None, block=256):
    """T positions of B sequences from ``state``. state (B, H, P, N) f32
    (unpacked); x (B, T, H, P); dt (B, T, H) f32 after the softplus; A
    (H,) f32; Bm, Cm (B, T, N); real (B, T) bool or None (all real).
    Returns (y (B, T, H, P) f32, the state after the last real position).
    ``block`` is this scan's own block of positions, not the published
    ``mamba_chunk_size``: any block gives the same answer."""
    _DISPATCH["ssm_chunk_jnp"] += 1
    B, T, H, P = x.shape
    state = state.astype(jnp.float32)   # whatever the cache keeps it in
    dt = dt.astype(jnp.float32)
    if real is not None:
        dt = jnp.where(real[..., None], dt, 0.0)
    xdt = x.astype(jnp.float32) * dt[..., None]
    a = dt * A
    Bm = Bm.astype(jnp.float32)
    Cm = Cm.astype(jnp.float32)
    Q = min(T, block)
    nb = -(-T // Q)
    if nb == 1:
        new, y = _scan_block(state, (xdt, a, Bm, Cm))
        return y, new
    pad = nb * Q - T                  # zeros: dt = 0, nothing moves

    def blocks(v):
        v = jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
        return jnp.moveaxis(v.reshape(B, nb, Q, *v.shape[2:]), 1, 0)

    new, ys = lax.scan(_scan_block, state,
                       tuple(map(blocks, (xdt, a, Bm, Cm))))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, nb * Q, H, P)
    return y[:, :T], new


def ssm_scan_reference(state, x, dt, A, Bm, Cm, real=None):
    """The recurrence, a position a turn: the twin of ``ssm_chunk_scan``
    (same arguments, same returns)."""
    dt = dt.astype(jnp.float32)
    if real is None:
        real = jnp.ones(dt.shape[:2], bool)

    def turn(S, t):
        x_t, dt_t, b_t, c_t, r_t = t
        new = jnp.exp(dt_t * A)[..., None, None] * S + jnp.einsum(
            "bhp,bn->bhpn", dt_t[..., None] * x_t, b_t, precision=_HI)
        y = jnp.einsum("bhpn,bn->bhp", new, c_t, precision=_HI)
        return jnp.where(r_t[:, None, None, None], new, S), y

    seq = tuple(jnp.moveaxis(v, 1, 0) for v in (
        x.astype(jnp.float32), dt, Bm.astype(jnp.float32),
        Cm.astype(jnp.float32), real))
    new, ys = lax.scan(turn, state, seq)
    return jnp.moveaxis(ys, 0, 1), new


def causal_conv(tail, x, w, b, real=None):
    """Causal depthwise convolution of width K over T positions with the
    K - 1 inputs before them. tail (B, K - 1, C); x (B, T, C); w (C, K):
    ``out_t = sum_k w[:, k] * in[t - (K - 1) + k]``; b (C,); real (B, T)
    bool, real positions first (padding at the end only), or None.
    Returns (out (B, T, C) f32, the new tail: the last K - 1 REAL inputs,
    in ``tail``'s type; a row with no real position keeps its tail bit
    for bit)."""
    K = w.shape[1]
    T = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w = w.astype(jnp.float32)
    out = b.astype(jnp.float32) + sum(
        full[:, k:k + T].astype(jnp.float32) * w[:, k] for k in range(K))
    n = jnp.full((x.shape[0],), T, jnp.int32) if real is None \
        else jnp.sum(real.astype(jnp.int32), axis=1)
    keep = n[:, None] + jnp.arange(K - 1)[None, :]            # (B, K - 1)
    new_tail = jnp.take_along_axis(full, keep[..., None], axis=1)
    return out, new_tail.astype(tail.dtype)

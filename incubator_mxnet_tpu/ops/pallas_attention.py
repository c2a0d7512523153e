"""Pallas TPU flash-attention kernels (forward AND backward).

The MXU-resident analogue of the reference's fused BERT attention CUDA
kernels (`src/operator/contrib/transformer.cc`,
``interleaved_matmul_selfatt_*`` — file-level citation, SURVEY.md caveat)
and the performance backbone for the BERT MFU target (SURVEY.md §7.2).

Design (per /opt/skills/guides/pallas_guide.md):
  - forward: grid (B, H, Tq/block_q); each program owns one q tile in
    VMEM; K/V are streamed in block_k chunks by a ``fori_loop`` carrying
    the online-softmax state (m, l, acc) — never materializing the
    (Tq, Tk) score matrix in HBM. The per-row logsumexp is written as a
    second output for the backward pass.
  - backward: two Pallas kernels (the FlashAttention-2 recurrences).
    dq: grid over q tiles, streaming K/V — p is rebuilt from q, k and the
    saved logsumexp (no O(T^2) memory), ds = p*(dO·V^T − Δ), dq += ds·K.
    dk/dv: grid over k tiles, streaming Q/dO — dv += p^T·dO,
    dk += ds^T·q. Δ = rowsum(dO ⊙ O) is a cheap XLA-fused reduction
    computed outside the kernels.
  - score blocks hit the MXU via ``jnp.dot(..., preferred_element_type=
    float32)``; masks (key-padding + causal) are built from iota and
    program ids, no mask tensor traffic.
  - padding contract: q/k/v/dO are zero-padded to block multiples;
    padded-query contributions to dk/dv vanish because dO is zero there,
    padded keys never attend because valid_len caps at the real Tk.

Kernel-vs-reference is ONE static decision, ``pallas_path``: on platform
``tpu`` the answer is the Mosaic kernel or an ``MXNetError`` (Pallas not
importable, or interpret mode requested) — never the interpreter or the
jnp path standing in for it. Off TPU (the CPU test mesh) the jnp
blockwise path is the default and ``interpret=True`` /
``MXTPU_FLASH_INTERPRET=1`` runs the same kernels in the interpreter.
Selection from static shapes (D > 256, causal with Tq != Tk, a boolean
mask) picks the jnp path on every platform.
"""

from __future__ import annotations

import collections
import functools
import os

import jax
import jax.numpy as jnp
from jax import lax

from ..base import MXNetError

_NEG_INF = -1e30


def _env_block(name, default):
    """Kernel tile-size knob (MXTPU_FLASH_BLOCK_Q / _K). Resolved in the
    NON-jitted wrappers so the concrete value becomes part of the jit
    cache key — changing the env between calls recompiles instead of
    silently reusing the old tile size."""
    import os
    try:
        # mxlint: allow-trace-host-leak(args are host ints: every jitted caller passes the block sizes via static_argnames)
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _resolve_blocks(block_q, block_k):
    if block_q is None:
        block_q = _env_block("MXTPU_FLASH_BLOCK_Q", 128)
    if block_k is None:
        block_k = _env_block("MXTPU_FLASH_BLOCK_K", 128)
    return block_q, block_k


def _pallas_available():
    try:
        from jax.experimental import pallas  # mxlint: allow-import-effect(availability probe)
        return True
    except Exception:  # pragma: no cover
        return False


def _on_tpu():
    return jax.default_backend() == "tpu"


def _env_interpret():
    return os.environ.get("MXTPU_FLASH_INTERPRET") == "1"


def pallas_path(interpret=False):
    """The one static kernel-vs-reference decision behind every attention
    dispatcher (flash, block/ring, ragged). True = run the Pallas kernel
    (compiled by Mosaic on TPU; in the interpreter off TPU when
    ``interpret``); False = the jnp reference, which only an off-TPU
    process may get. On TPU nothing stands in for the kernel."""
    if _on_tpu():
        if not _pallas_available():
            raise MXNetError(
                "platform is tpu but jax.experimental.pallas failed to "
                "import: refusing to run the jnp reference in place of "
                "the Mosaic attention kernel")
        if interpret:
            raise MXNetError(
                "interpret mode (MXTPU_FLASH_INTERPRET=1 / interpret=True) "
                "on platform tpu would run the Pallas interpreter in "
                "place of the Mosaic attention kernel; unset it")
        return True
    # mxlint: allow-trace-host-leak(interpret is a host flag: env var or a static jit arg, never traced)
    return bool(interpret) and _pallas_available()


def _tile_mask(rows, cols, vl, causal, q_off=0, k_off=0, k_axis=1):
    """(rows, cols) boolean attend-mask for one score tile, keys along
    ``k_axis`` (1: a (bq, bk) tile; 0: the packed dense backward's
    transposed (bk, bq) tile): keys < ``vl``, optionally causal (top-left
    aligned — square Tq == Tk only, enforced by use_flash_attention).
    ``q_off``/``k_off`` position the tile inside the full (Tq, Tk) score
    matrix. Shared by ALL kernels (streaming fwd/dq/dkv, dense fwd/bwd in
    both layouts) so mask semantics cannot drift between paths."""
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (rows, cols), k_axis)
    mask = k_pos < vl
    if causal:
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (rows, cols),
                                             1 - k_axis)
        mask = mask & (k_pos <= q_pos)
    return mask


# --------------------------------------------------------------------- #
# forward kernel
# --------------------------------------------------------------------- #

def _largest_divisor(H, cap, per_head_bytes=0, budget=None):
    """Largest divisor of H within ``cap`` and (optionally) a byte
    budget — the single selection rule behind BOTH head-grouping
    helpers so the dense and streaming paths cannot diverge."""
    hpp = 1
    for d in range(1, H + 1):
        if H % d == 0 and d <= cap and (
                budget is None or d * per_head_bytes <= budget):
            hpp = d
    return hpp


def _stream_hpp(H, per_head_bytes):
    """Heads per program for the STREAMING kernels: largest divisor of H
    whose block set stays inside a ~2.5 MB per-program VMEM budget
    (double-buffered by Pallas on top). Derived from static shapes only
    — no env knob — so resolving it at trace time inside the jitted
    wrappers cannot create a stale-cache hazard. Same rationale as the
    dense kernels' grouping: per-program MXU work at one (head, tile)
    is ~0.3 us, the same order as Mosaic's per-program overhead."""
    return _largest_divisor(H, 8, per_head_bytes, 2_500_000)


def _flash_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                  causal, block_q, block_k, n_k_blocks, hpp):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    # lengths ride along as the full (B, 1) array in SMEM (Mosaic requires
    # SMEM blocks tiled 8x128 OR equal to the array dims; (1,1) blocks of
    # a (B,1) array violate that) — each program picks its batch row.
    vl = vl_ref[pl.program_id(0), 0]                     # valid key length

    for h in range(hpp):                                 # unrolled heads
        # dot OPERANDS stay in the input dtype (bf16 inputs hit the MXU
        # at full rate — an f32 upcast here quarters matmul throughput);
        # ACCUMULATION (s, m, l, acc) is f32 via preferred_element_type.
        # The scale is applied to the f32 scores, not the narrow operands.
        q = q_ref[0, h]                                  # (bq, D)
        bq, D = q.shape

        def body(j, carry, _h=h, _q=q):
            m, l, acc = carry
            k = k_ref[0, _h, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, _h, pl.ds(j * block_k, block_k), :]
            s = jnp.dot(_q, k.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT) * scale
            mask = _tile_mask(block_q, block_k, vl, causal,
                              q_off=qi * block_q, k_off=j * block_k)
            s = jnp.where(mask, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1)
            acc_new = acc * alpha[:, None] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            return m_new, l_new, acc_new

        m0 = jnp.full((block_q,), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((block_q,), jnp.float32)
        acc0 = jnp.zeros((block_q, D), jnp.float32)
        m, l, acc = lax.fori_loop(0, n_k_blocks, body, (m0, l0, acc0))
        l_safe = jnp.maximum(l, 1e-30)
        # fully-masked rows (vl==0, or padded q rows past vl): m never
        # left _NEG_INF, so p was uniformly 1 and acc/l is the mean of V
        # — zero the output and pin lse to _NEG_INF (finite, so ring
        # merges weight the row out without producing NaN)
        row_ok = m > _NEG_INF / 2
        o_ref[0, h] = jnp.where(row_ok[:, None], acc / l_safe[:, None],
                                0.0).astype(o_ref.dtype)
        # lse carries a trailing singleton lane dim: Mosaic requires the
        # last two block dims (8, 128)-tiled or equal to the array dims,
        # which a (1, 1, block_q) block of a (B, H, Tq) array is not.
        lse_ref[0, h] = jnp.where(row_ok, m + jnp.log(l_safe),
                                  _NEG_INF)[:, None]


def _pad_to(x, axis, multiple):
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "dense", "hpp"))
def _flash_fwd_lse(q, k, v, valid_len, causal=False, scale=None,
                   block_q=None, block_k=None, interpret=False,
                   dense=False, hpp=None):
    """q/k/v: (B, H, T, D). Returns (out, lse) with lse (B, H, Tq).
    ``dense`` (static; resolve via _use_dense in the NON-jitted callers,
    like the block knobs, so it is part of the jit cache key) selects the
    single-tile kernel over the streaming one."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    if dense:
        return _dense_fwd_lse(q, k, v, valid_len, causal, scale, interpret,
                              hpp)
    scale = D ** -0.5 if scale is None else scale
    block_q = min(block_q or 128, max(Tq, 8))
    block_k = min(block_k or 128, max(Tk, 8))
    q, _ = _pad_to(q, 2, block_q)
    k, _ = _pad_to(k, 2, block_k)
    v, _ = _pad_to(v, 2, block_k)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    n_k_blocks = Tk_p // block_k

    # valid_len caps at real Tk so padded keys never attend
    vl = jnp.minimum(valid_len.astype(jnp.int32), Tk).reshape(B, 1)

    itemsize = q.dtype.itemsize
    # per-head blocks: k+v (Tk_p) and q+o (block_q), plus the f32 lse
    shpp = _stream_hpp(H, (2 * Tk_p + 2 * block_q) * D * itemsize
                       + 4 * block_q)
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k_blocks=n_k_blocks, hpp=shpp)

    out, lse = pl.pallas_call(
        kernel,
        name="mxtpu_flash_stream_fwd",
        grid=(B, H // shpp, Tq_p // block_q),
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, g, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, shpp, block_q, D),
                         lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, shpp, Tk_p, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, shpp, Tk_p, D), lambda b, g, i: (b, g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, shpp, block_q, D),
                         lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, shpp, block_q, 1),
                         lambda b, g, i: (b, g, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq_p, 1), jnp.float32),
        ],
        interpret=interpret,
    )(vl, q, k, v)
    return out[:, :, :Tq, :], lse[:, :, :Tq, 0]


def _flash_forward(q, k, v, valid_len, causal=False, scale=None,
                   block_q=None, block_k=None, interpret=False):
    """Forward-only entry (kept for tests / direct use)."""
    dense = _use_dense(q.shape[2], k.shape[2])
    if not dense:                 # blocks are dead args on the dense path
        block_q, block_k = _resolve_blocks(block_q, block_k)
    return _flash_fwd_lse(q, k, v, valid_len, causal=causal, scale=scale,
                          block_q=block_q, block_k=block_k,
                          interpret=interpret, dense=dense,
                          hpp=_dense_hpp(q.shape[1]) if dense else None)[0]


# --------------------------------------------------------------------- #
# dense single-tile kernels (short sequences)
# --------------------------------------------------------------------- #
#
# Profiling the streaming kernels on v5e (trace_r4) showed per-program
# grid overhead dominating at short T: grid (B, H, T/128) is 2304
# programs of ~0.2 ms ideal compute each, and the step spent 42% of its
# time in attention at ~5% MXU utilization. For T where the whole
# (Tq, Tk) score tile fits comfortably in VMEM there is no reason to
# stream: one program per (batch, head) computes the full softmax in a
# single shot (no online-softmax carry, no fori_loop), and the backward
# fuses dq/dk/dv into ONE kernel so s and p are rebuilt once instead of
# twice. Programs drop 4-8x and each does T/block_q times more work.
# Long sequences (> MXTPU_FLASH_DENSE_T, default 1024) keep the
# streaming FlashAttention-2 kernels above.

def _dense_hpp(H, bwd=False):
    """Static heads-per-program for the dense kernels, resolved in the
    NON-jitted callers (cache-key correct, like block_q/block_k)."""
    if bwd:
        return _heads_per_program(H, "MXTPU_FLASH_BWD_HPP", 8)
    return _heads_per_program(H, "MXTPU_FLASH_FWD_HPP", 16)


def _use_dense(Tq, Tk):
    """Static dispatch (shapes are trace-time constants). The env knob is
    read at trace time: like the block-size knobs it must not change
    between calls inside one process (bench runs one config per
    process)."""
    # Default 512 = the largest shape validated on v5e hardware. The
    # fused dense backward's single-program working set grows as T^2
    # (s/p/dp f32 tiles); T=1024 pencils out near the VMEM budget and
    # has not been run on a real chip — raise the knob only with a
    # measurement in hand.
    limit = _env_block("MXTPU_FLASH_DENSE_T", 512)
    return max(Tq, Tk) <= limit


def _heads_per_program(H, cap_env, cap_default):
    """Largest divisor of H within the per-program VMEM budget. Per-
    program MXU work at one (head, T<=512) tile is sub-microsecond —
    comparable to Mosaic's per-program overhead — so packing several
    heads into each program is what actually amortizes the grid cost.
    Caps (fwd 16 / bwd 8 by default, env-tunable) keep the double-
    buffered block set inside the ~16 MB/core VMEM."""
    cap = max(1, _env_block(cap_env, cap_default))
    return _largest_divisor(H, cap)


def _dense_vmem_limit(hpp, Tq, Tk, D, itemsize, q_blocks, k_blocks,
                      col_blocks, tiles_per_head):
    """Scoped-VMEM request for one dense program, from its block set.
    Per head: ``q_blocks``/``k_blocks`` (Tq, D)/(Tk, D) operand-dtype
    blocks and ``col_blocks`` (Tq, 1) f32 columns, lane-padded to 128
    and double-buffered by Pallas, plus ``tiles_per_head`` (Tq, Tk) f32
    tiles — the unrolled head loop keeps scores/probabilities of
    neighbouring heads live, so the tile count grows with the grouping.
    Plus 4 MiB of slack. Mosaic's default scoped limit is 16 MiB; first
    contact (2026-09) measured the forward's need inside a training step
    at 26.34M for 12 heads (GPT-2-small) and 37.41M for 16 (BERT-large)
    at T=512 — about 1.5 MiB of blocks and 0.75 MiB of tiles a head, and
    more than the same kernel needs compiled alone (17.41M for 16). The
    request is a cap, not an allocation, and stays inside v5e's 128 MiB
    of VMEM."""
    lanes = -(-D // 128) * 128
    blocks = (q_blocks * Tq + k_blocks * Tk) * lanes * itemsize \
        + col_blocks * Tq * 128 * 4
    need = hpp * (2 * blocks + tiles_per_head * Tq * Tk * 4) + (4 << 20)
    return min(max(need, 16 << 20), 100 << 20)


def _dense_fwd_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                      scale, causal, hpp):
    from jax.experimental import pallas as pl

    vl = vl_ref[pl.program_id(0), 0]
    for h in range(hpp):                       # unrolled head loop
        q = q_ref[0, h]                                   # (Tqp, D)
        k = k_ref[0, h]                                   # (Tkp, D)
        v = v_ref[0, h]
        Tqp, Tkp = q.shape[0], k.shape[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT) * scale
        s = jnp.where(_tile_mask(Tqp, Tkp, vl, causal), s, _NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.exp(s - m[:, None])
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        o = jnp.dot(p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT) / l[:, None]
        # zero fully-masked rows (vl==0 / padded q rows) instead of the
        # uniform mean of V, and pin their lse to _NEG_INF (see the
        # streaming kernel for the rationale)
        row_ok = m > _NEG_INF / 2
        o_ref[0, h] = jnp.where(row_ok[:, None], o, 0.0) \
            .astype(o_ref.dtype)
        lse_ref[0, h] = jnp.where(row_ok, m + jnp.log(l),
                                  _NEG_INF)[:, None]


def _dense_bwd_kernel(vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                      delta_ref, dq_ref, dk_ref, dv_ref, *, scale,
                      causal, hpp):
    from jax.experimental import pallas as pl

    vl = vl_ref[pl.program_id(0), 0]
    for h in range(hpp):                       # unrolled head loop
        q = q_ref[0, h]                                   # (Tqp, D)
        k = k_ref[0, h]                                   # (Tkp, D)
        v = v_ref[0, h]
        do = do_ref[0, h]
        lse = lse_ref[0, h, :, 0].astype(jnp.float32)     # (Tqp,)
        delta = delta_ref[0, h, :, 0].astype(jnp.float32)
        Tqp, Tkp = q.shape[0], k.shape[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT) * scale
        mask = _tile_mask(Tqp, Tkp, vl, causal)
        p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # (Tqp, Tkp)
        dv = jnp.dot(p.astype(do.dtype).T, do,
                     preferred_element_type=jnp.float32,
                     precision=lax.Precision.DEFAULT)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32,
                     precision=lax.Precision.DEFAULT)
        ds = (p * (dp - delta[:, None]) * scale).astype(k.dtype)
        dq_ref[0, h] = jnp.dot(ds, k, preferred_element_type=jnp.float32,
                               precision=lax.Precision.DEFAULT) \
            .astype(dq_ref.dtype)
        dk_ref[0, h] = jnp.dot(ds.T, q, preferred_element_type=jnp.float32,
                               precision=lax.Precision.DEFAULT) \
            .astype(dk_ref.dtype)
        dv_ref[0, h] = dv.astype(dv_ref.dtype)


def _dense_fwd_lse(q, k, v, valid_len, causal, scale, interpret,
                   hpp=None):
    """Single-tile forward: grid (B, H/hpp), whole (Tq, Tk) tiles.
    ``hpp`` (heads per program) is static — resolved by the NON-jitted
    callers via _heads_per_program, like every other env knob."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    q, _ = _pad_to(q, 2, 8)          # sublane alignment for q rows
    k, _ = _pad_to(k, 2, 128)        # lane alignment for score columns
    v, _ = _pad_to(v, 2, 128)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    vl = jnp.minimum(valid_len.astype(jnp.int32), Tk).reshape(B, 1)
    if hpp is None:
        hpp = _heads_per_program(H, "MXTPU_FLASH_FWD_HPP", 16)
    kernel = functools.partial(_dense_fwd_kernel, scale=scale,
                               causal=causal, hpp=hpp)
    out, lse = pl.pallas_call(
        kernel,
        name="mxtpu_flash_dense_fwd",
        grid=(B, H // hpp),
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, g: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hpp, Tq_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hpp, Tq_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tq_p, 1), lambda b, g: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tq_p, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_dense_vmem_limit(
                hpp, Tq_p, Tk_p, D, q.dtype.itemsize, q_blocks=2,
                k_blocks=2, col_blocks=1, tiles_per_head=1)),
        interpret=interpret,
    )(vl, q, k, v)
    return out[:, :, :Tq, :], lse[:, :, :Tq, 0]


def _dense_backward(q, k, v, valid_len, lse, g, delta, causal, scale,
                    interpret, hpp=None):
    """Fused single-tile backward: ONE kernel for dq, dk and dv.
    ``hpp`` static, resolved by non-jitted callers (see
    _dense_fwd_lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = D ** -0.5 if scale is None else scale
    qp, _ = _pad_to(q, 2, 8)
    dop = _pad_to(g.astype(q.dtype), 2, 8)[0]
    lsep = _pad_to(lse, 2, 8)[0][..., None]
    deltap = _pad_to(delta, 2, 8)[0][..., None]
    kp, _ = _pad_to(k, 2, 128)
    vp, _ = _pad_to(v, 2, 128)
    Tq_p, Tk_p = qp.shape[2], kp.shape[2]
    vl = jnp.minimum(valid_len.astype(jnp.int32), Tk).reshape(B, 1)
    if hpp is None:
        hpp = _heads_per_program(H, "MXTPU_FLASH_BWD_HPP", 8)
    kernel = functools.partial(_dense_bwd_kernel, scale=scale,
                               causal=causal, hpp=hpp)
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="mxtpu_flash_dense_bwd",
        grid=(B, H // hpp),
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, g: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, hpp, Tq_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tq_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tq_p, 1), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tq_p, 1), lambda b, g: (b, g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hpp, Tq_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
            pl.BlockSpec((1, hpp, Tk_p, D), lambda b, g: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_dense_vmem_limit(
                hpp, Tq_p, Tk_p, D, q.dtype.itemsize, q_blocks=3,
                k_blocks=4, col_blocks=2, tiles_per_head=2)),
        interpret=interpret,
    )(vl, qp, kp, vp, dop, lsep, deltap)
    return dq[:, :, :Tq, :], dk[:, :, :Tk, :], dv[:, :, :Tk, :]


# --------------------------------------------------------------------- #
# backward kernels (FlashAttention-2 recurrences)
# --------------------------------------------------------------------- #

def _flash_bwd_dq_kernel(vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                         delta_ref, dq_ref, *, scale, causal, block_q,
                         block_k, n_k_blocks, hpp):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    vl = vl_ref[pl.program_id(0), 0]

    for h in range(hpp):                                  # unrolled heads
        # same dtype discipline as the forward kernel: dot operands keep
        # the input dtype (bf16 -> full-rate MXU), accumulators f32
        q = q_ref[0, h]                                   # (bq, D)
        do = do_ref[0, h]                                 # (bq, D)
        lse = lse_ref[0, h, :, 0].astype(jnp.float32)     # (bq,)
        delta = delta_ref[0, h, :, 0].astype(jnp.float32)
        bq, D = q.shape

        def body(j, dq, _h=h, _q=q, _do=do, _lse=lse, _delta=delta):
            k = k_ref[0, _h, pl.ds(j * block_k, block_k), :]
            v = v_ref[0, _h, pl.ds(j * block_k, block_k), :]
            s = jnp.dot(_q, k.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT) * scale
            mask = _tile_mask(block_q, block_k, vl, causal,
                              q_off=qi * block_q, k_off=j * block_k)
            p = jnp.where(mask, jnp.exp(s - _lse[:, None]), 0.0)
            dp = jnp.dot(_do, v.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            ds = (p * (dp - _delta[:, None]) * scale).astype(k.dtype)
            return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)

        dq = lax.fori_loop(0, n_k_blocks, body,
                           jnp.zeros((bq, D), jnp.float32))
        dq_ref[0, h] = dq.astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(vl_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, dk_ref, dv_ref, *, scale, causal,
                          block_q, block_k, n_q_blocks, hpp):
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    vl = vl_ref[pl.program_id(0), 0]

    for h in range(hpp):                                  # unrolled heads
        # dot operands keep the input dtype; accumulators f32 (see fwd)
        k = k_ref[0, h]                                   # (bk, D)
        v = v_ref[0, h]                                   # (bk, D)
        bk, D = k.shape

        def body(i, carry, _h=h, _k=k, _v=v):
            dk, dv = carry
            q = q_ref[0, _h, pl.ds(i * block_q, block_q), :]
            do = do_ref[0, _h, pl.ds(i * block_q, block_q), :]
            lse = lse_ref[0, _h, pl.ds(i * block_q, block_q), 0] \
                .astype(jnp.float32)
            delta = delta_ref[0, _h, pl.ds(i * block_q, block_q), 0] \
                .astype(jnp.float32)
            s = jnp.dot(q, _k.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT) * scale
            mask = _tile_mask(block_q, block_k, vl, causal,
                              q_off=i * block_q, k_off=ki * block_k)
            p = jnp.where(mask, jnp.exp(s - lse[:, None]), 0.0)  # (bq,bk)
            dv = dv + jnp.dot(p.astype(do.dtype).T, do,
                              preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            dp = jnp.dot(do, _v.T, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            ds = (p * (dp - delta[:, None]) * scale).astype(q.dtype)
            dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)
            return dk, dv

        dk0 = jnp.zeros((bk, D), jnp.float32)
        dv0 = jnp.zeros((bk, D), jnp.float32)
        dk, dv = lax.fori_loop(0, n_q_blocks, body, (dk0, dv0))
        dk_ref[0, h] = dk.astype(dk_ref.dtype)
        dv_ref[0, h] = dv.astype(dv_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "interpret",
                                             "dense", "hpp"))
def _flash_backward(q, k, v, valid_len, out, lse, g, causal=False,
                    scale=None, block_q=None, block_k=None,
                    interpret=False, dense=False, hpp=None):
    """Pallas backward: returns (dq, dk, dv). Shapes as forward.
    ``dense`` static, resolved by the non-jitted callers (see
    _flash_fwd_lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    scale = D ** -0.5 if scale is None else scale

    # Δ = rowsum(dO ⊙ O): cheap elementwise+reduce, XLA fuses it
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                              # (B, H, Tq)

    if dense:
        return _dense_backward(q, k, v, valid_len, lse, g, delta, causal,
                               scale, interpret, hpp)
    block_q = min(block_q or 128, max(Tq, 8))
    block_k = min(block_k or 128, max(Tk, 8))

    qp, _ = _pad_to(q, 2, block_q)
    dop, _ = _pad_to(g.astype(q.dtype), 2, block_q)
    # trailing singleton lane dim for the same Mosaic tiling reason as the
    # forward's lse output
    lsep = _pad_to(lse, 2, block_q)[0][..., None]
    deltap = _pad_to(delta, 2, block_q)[0][..., None]
    kp, _ = _pad_to(k, 2, block_k)
    vp, _ = _pad_to(v, 2, block_k)
    Tq_p, Tk_p = qp.shape[2], kp.shape[2]
    n_q_blocks, n_k_blocks = Tq_p // block_q, Tk_p // block_k
    vl = jnp.minimum(valid_len.astype(jnp.int32), Tk).reshape(B, 1)

    itemsize = q.dtype.itemsize
    # dq per-head blocks: k+v (Tk_p), q+do+dq (block_q), lse+delta f32
    qhpp = _stream_hpp(H, (2 * Tk_p + 3 * block_q) * D * itemsize
                       + 8 * block_q)
    dq_kernel = functools.partial(
        _flash_bwd_dq_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_k_blocks=n_k_blocks, hpp=qhpp)
    dq = pl.pallas_call(
        dq_kernel,
        name="mxtpu_flash_stream_dq",
        grid=(B, H // qhpp, n_q_blocks),
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, g, i: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, qhpp, block_q, D),
                         lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, qhpp, Tk_p, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, qhpp, Tk_p, D), lambda b, g, i: (b, g, 0, 0)),
            pl.BlockSpec((1, qhpp, block_q, D),
                         lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, qhpp, block_q, 1),
                         lambda b, g, i: (b, g, i, 0)),
            pl.BlockSpec((1, qhpp, block_q, 1),
                         lambda b, g, i: (b, g, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, qhpp, block_q, D),
                               lambda b, g, i: (b, g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tq_p, D), q.dtype),
        interpret=interpret,
    )(vl, qp, kp, vp, dop, lsep, deltap)

    # dkv per-head blocks: q+do (Tq_p), k+v+dk+dv (block_k), lse+delta
    khpp = _stream_hpp(H, (2 * Tq_p + 4 * block_k) * D * itemsize
                       + 8 * Tq_p)
    dkv_kernel = functools.partial(
        _flash_bwd_dkv_kernel, scale=scale, causal=causal, block_q=block_q,
        block_k=block_k, n_q_blocks=n_q_blocks, hpp=khpp)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="mxtpu_flash_stream_dkv",
        grid=(B, H // khpp, n_k_blocks),
        in_specs=[
            pl.BlockSpec((B, 1), lambda b, g, j: (0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, khpp, Tq_p, D), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, khpp, block_k, D),
                         lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, khpp, block_k, D),
                         lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, khpp, Tq_p, D), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, khpp, Tq_p, 1), lambda b, g, j: (b, g, 0, 0)),
            pl.BlockSpec((1, khpp, Tq_p, 1), lambda b, g, j: (b, g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, khpp, block_k, D),
                         lambda b, g, j: (b, g, j, 0)),
            pl.BlockSpec((1, khpp, block_k, D),
                         lambda b, g, j: (b, g, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tk_p, D), k.dtype),
            jax.ShapeDtypeStruct((B, H, Tk_p, D), v.dtype),
        ],
        interpret=interpret,
    )(vl, qp, kp, vp, dop, lsep, deltap)

    return dq[:, :, :Tq, :], dk[:, :, :Tk, :], dv[:, :, :Tk, :]


# --------------------------------------------------------------------- #
# the dense pair in the projection's own layout, (B, T, 3*H*D)
# --------------------------------------------------------------------- #
#
# A second dense pair beside the (B, H, T, D) one above, for
# self-attention straight off the fused projection. Operands are column
# blocks of the packed (B, T, 3*H*D) array, heads side by side on the
# lanes: the same array is handed over three times with column offsets
# 0, H*D, 2*H*D, the output is (B, T, H*D) and the backward writes ONE
# (B, T, 3*H*D) gradient. Why (the v5e compiler's own layouts at
# BERT-large's B=32, T=512, H=16, D=64): a (B, H, T, 64) operand is
# `bf16[32,16,512,64]{3,2,1,0:T(8,128)(2,1)}`, D=64 minor under a
# 128-lane tile, so every tile is half empty and the kernels and the
# `copy`/`slice_bitcast_fusion`/`add_bitcast_fusion` relayouts round
# them (five a layer forward, four backward) move twice the bytes;
# lse/delta as `f32[32,16,512,1]` pad 128-fold. Here a lane group is 128
# lanes = two D=64 heads (or one D>=128 head), every load and store is a
# whole lane group, and the per-row statistics travel with T on the
# lanes. Same mathematics and precision as the pair above; the two share
# ``_tile_mask`` and nothing else (``packed_dense_eligible`` says which
# call sites get this one, and why only those).

_LANES = 128


def _lane_group(D):
    """(lanes, heads) of one lane group: the unit the packed dense
    kernels load and store, so every access is lane-dense."""
    return max(D, _LANES), max(1, _LANES // D)


def packed_dense_shapes(T, H, D):
    """The shapes the packed dense pair takes: T within the dense limit
    and a multiple of the lane tile (the statistics ride the lanes),
    heads filling whole lane groups (D=64 with H even, or D a multiple
    of 128)."""
    return (_use_dense(T, T) and T % _LANES == 0
            and (D == 64 or D % _LANES == 0)
            and H % _lane_group(D)[1] == 0)


# one program's double-buffered blocks stay under this; v5e has 128 MiB
# of VMEM and the f32 score tiles need their share
_PACKED_BLOCK_BUDGET = 24 << 20


def _packed_hpp(H, D, T, itemsize, bwd=False):
    """Heads per program: the largest divisor of H, in whole lane
    groups, whose blocks fit ``_PACKED_BLOCK_BUDGET`` — the whole row
    (grid (B, 1)) at every size a cell runs. Derived from static shapes
    only, so resolving it at trace time cannot go stale."""
    heads = _lane_group(D)[1]
    # forward: q, k, v, out; backward adds d(out) and the gradient's
    # three parts
    rows = 8 * T if bwd else 4 * T
    return heads * _largest_divisor(
        H // heads, H // heads, 2 * rows * heads * D * itemsize,
        _PACKED_BLOCK_BUDGET)


def _packed_vmem_limit(block_bytes, T, tiles):
    """Scoped-VMEM request for one packed dense program: its blocks,
    double-buffered by Pallas, ``tiles`` (T, T) f32 tiles for the heads
    of a loop turn in flight, and 4 MiB of slack. Mosaic's default
    scoped limit is 16 MiB; the request is a cap, not an allocation.
    (AOT compile against v5e:2x2: inside the BERT-large step the forward
    needs 11 MiB and the backward 19-20.)"""
    need = 2 * block_bytes + tiles * T * T * 4 + (4 << 20)
    return min(max(need, 16 << 20), 100 << 20)


def _lanes_at(i, width):
    """Lane slice ``[i*width, (i+1)*width)``; ``i`` static or traced,
    ``width`` a multiple of the lane tile."""
    from jax.experimental import pallas as pl
    if isinstance(i, int):
        return pl.ds(i * width, width)
    return pl.ds(pl.multiple_of(i * width, _LANES), width)


# lane groups a loop turn: two (four D=64 heads) let the scheduler run
# one group's matmuls under the other's softmax, 6% off the pair at both
# cells' shapes against one a turn; all of them at once gains 2% more
# and needs every group's tiles live (PR 28's builder runs, v5e)
_GROUP_UNROLL = 2


def _for_groups(n, body):
    """Run ``body(i)`` for the ``n`` lane groups of a block,
    ``_GROUP_UNROLL`` a turn of a ``fori_loop`` (in place where that is
    all of them) — a fully unrolled loop keeps every group's score
    tiles live (the (B, H, T, D) forward: 37.41M of scoped VMEM for 16
    heads)."""
    u = _GROUP_UNROLL if n % _GROUP_UNROLL == 0 else 1
    if n == u:
        for i in range(n):
            body(i)
        return

    def turn(j, carry):
        for r in range(u):
            body(j * u + r)
        return carry

    lax.fori_loop(0, n // u, turn, 0)


def _packed_fwd_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                       scale, causal, D):
    from jax.experimental import pallas as pl

    vl = vl_ref[pl.program_id(0), 0]
    T, W = q_ref.shape[1:]
    L, heads = _lane_group(D)
    mask = _tile_mask(T, T, vl, causal)
    stat_lanes = _LANES // heads

    def group(i):
        lanes = _lanes_at(i, L)
        qg, kg, vg = q_ref[0, :, lanes], k_ref[0, :, lanes], \
            v_ref[0, :, lanes]
        outs, stats = [], []
        for h in range(heads):                 # the group's heads
            q, k, v = (x[:, h * D:(h + 1) * D] for x in (qg, kg, vg))
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT) * scale
            s = jnp.where(mask, s, _NEG_INF)
            m = jnp.max(s, axis=-1, keepdims=True)           # (T, 1)
            p = jnp.exp(s - m)
            l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
            o = jnp.dot(p.astype(v.dtype), v,
                        preferred_element_type=jnp.float32,
                        precision=lax.Precision.DEFAULT) / l
            # zero fully-masked rows (vl==0) instead of the uniform mean
            # of V, and pin their lse to _NEG_INF (see the streaming
            # kernel for the rationale)
            row_ok = m > _NEG_INF / 2
            outs.append(jnp.where(row_ok, o, 0.0).astype(o_ref.dtype))
            stats.append(jnp.broadcast_to(
                jnp.where(row_ok, m + jnp.log(l), _NEG_INF),
                (T, stat_lanes)))
        o_ref[0, :, lanes] = outs[0] if heads == 1 else \
            jnp.concatenate(outs, axis=1)
        # the statistics leave with T on the lanes: one (T, 128)
        # transpose a group, a head's row every ``stat_lanes`` sublanes
        lse_t = (stats[0] if heads == 1 else
                 jnp.concatenate(stats, axis=1)).T           # (128, T)
        for h in range(heads):
            lse_ref[0, i, h:h + 1, :] = \
                lse_t[h * stat_lanes:h * stat_lanes + 1, :]

    _for_groups(W // L, group)


def _packed_bwd_kernel(vl_ref, q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                       grad_ref, *, scale, causal, D, part_cols):
    """Scores are rebuilt TRANSPOSED, (Tk, Tq): the saved lse and delta
    broadcast along sublanes from their lane-dense rows, and dv, dk are
    plain products; only dq needs a transposed operand (the (Tq, Tk)
    form needs two). ``grad_ref``: the whole-row (1, T, 3*H*D) block,
    resident across the head axis, its dq | dk | dv parts ``part_cols``
    apart."""
    from jax.experimental import pallas as pl

    vl = vl_ref[pl.program_id(0), 0]
    T, W = q_ref.shape[1:]
    L, heads = _lane_group(D)
    mask_t = _tile_mask(T, T, vl, causal, k_axis=0)
    groups = W // L
    first = pl.program_id(1) * groups          # this block's first group

    def group(i):
        lanes = _lanes_at(i, L)
        qg, kg, vg, dog = (r[0, :, lanes]
                           for r in (q_ref, k_ref, v_ref, do_ref))
        # delta = rowsum(dO * O), lane-dense: the product transposed
        # once a group, then a sublane sum a head
        prod_t = (dog.astype(jnp.float32)
                  * o_ref[0, :, lanes].astype(jnp.float32)).T  # (L, T)
        lse = lse_ref[0, i]                                # (heads, T)
        grads = ([], [], [])
        for h in range(heads):
            q, k, v, do = (x[:, h * D:(h + 1) * D]
                           for x in (qg, kg, vg, dog))
            delta = jnp.sum(prod_t[h * D:(h + 1) * D], axis=0,
                            keepdims=True)                   # (1, T)
            s_t = jnp.dot(k, q.T, preferred_element_type=jnp.float32,
                          precision=lax.Precision.DEFAULT) * scale
            p_t = jnp.where(mask_t, jnp.exp(s_t - lse[h:h + 1]), 0.0)
            dv = jnp.dot(p_t.astype(do.dtype), do,
                         preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
            dp_t = jnp.dot(v, do.T, preferred_element_type=jnp.float32,
                           precision=lax.Precision.DEFAULT)
            ds_t = (p_t * (dp_t - delta) * scale).astype(k.dtype)
            dk = jnp.dot(ds_t, q, preferred_element_type=jnp.float32,
                         precision=lax.Precision.DEFAULT)
            dq = lax.dot_general(ds_t, k, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32,
                                 precision=lax.Precision.DEFAULT)
            for part, g in zip(grads, (dq, dk, dv)):
                part.append(g.astype(grad_ref.dtype))
        for c, part in enumerate(grads):
            grad_ref[0, :, _lanes_at(first + i + c * (part_cols // L),
                                     L)] = \
                part[0] if heads == 1 else jnp.concatenate(part, axis=1)

    _for_groups(groups, group)


def _packed_specs(qkv, H, bwd=False):
    """(B, T, D, heads of a lane group, heads a program, the q | k | v
    column-block specs) for the packed (B, T, 3*H*D) projection: one
    array, three specs with column offsets 0, H*D, 2*H*D."""
    from jax.experimental import pallas as pl
    B, T, W = qkv.shape
    D = W // (3 * H)
    hpp = _packed_hpp(H, D, T, qkv.dtype.itemsize, bwd=bwd)
    G = H // hpp
    specs = [pl.BlockSpec((1, T, hpp * D),
                          lambda b, g, c=c: (b, 0, c * G + g))
             for c in range(3)]
    return B, T, D, _lane_group(D)[1], hpp, specs


@functools.partial(jax.jit, static_argnames=("H", "causal", "scale",
                                             "interpret"))
def _packed_fwd_lse(qkv, valid_len, H, causal, scale, interpret):
    """Single-tile forward over the packed projection: grid (B, H/hpp),
    whole (T, T) tiles. Returns out (B, T, H*D) and lse
    (B, H/heads, heads, T), heads = those of a lane group."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, D, heads, hpp, specs = _packed_specs(qkv, H)
    scale = D ** -0.5 if scale is None else scale
    vl = jnp.minimum(valid_len.astype(jnp.int32), T).reshape(B, 1)
    kernel = functools.partial(_packed_fwd_kernel, scale=scale,
                               causal=causal, D=D)
    return pl.pallas_call(
        kernel,
        name="mxtpu_flash_dense_fwd",
        grid=(B, H // hpp),
        in_specs=[pl.BlockSpec((B, 1), lambda b, g: (0, 0),
                               memory_space=pltpu.SMEM)] + specs,
        out_specs=[
            pl.BlockSpec((1, T, hpp * D), lambda b, g: (b, 0, g)),
            pl.BlockSpec((1, hpp // heads, heads, T),
                         lambda b, g: (b, g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H * D), qkv.dtype),
            jax.ShapeDtypeStruct((B, H // heads, heads, T), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_packed_vmem_limit(
                4 * T * hpp * D * qkv.dtype.itemsize, T,
                tiles=4 * _GROUP_UNROLL)),
        interpret=interpret,
    )(vl, qkv, qkv, qkv)


@functools.partial(jax.jit, static_argnames=("H", "causal", "scale",
                                             "interpret"))
def _packed_backward(qkv, valid_len, out, lse, g, H, causal, scale,
                     interpret):
    """Fused single-tile backward: ONE kernel for dq, dk and dv, delta
    computed inside from ``out`` and ``g``. Returns the projection's
    (B, T, 3*H*D) gradient, written in place: that output block is the
    whole row and stays in VMEM across the head axis, each program
    storing its columns of the three parts."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, D, heads, hpp, specs = _packed_specs(qkv, H, bwd=True)
    scale = D ** -0.5 if scale is None else scale
    vl = jnp.minimum(valid_len.astype(jnp.int32), T).reshape(B, 1)
    kernel = functools.partial(_packed_bwd_kernel, scale=scale,
                               causal=causal, D=D, part_cols=H * D)
    row = pl.BlockSpec((1, T, hpp * D), lambda b, g: (b, 0, g))
    return pl.pallas_call(
        kernel,
        name="mxtpu_flash_dense_bwd",
        grid=(B, H // hpp),
        in_specs=[pl.BlockSpec((B, 1), lambda b, g: (0, 0),
                               memory_space=pltpu.SMEM)] + specs
        + [row, row,
           pl.BlockSpec((1, hpp // heads, heads, T),
                        lambda b, g: (b, g, 0, 0))],
        out_specs=pl.BlockSpec((1, T, 3 * H * D), lambda b, g: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, T, 3 * H * D), qkv.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_packed_vmem_limit(
                T * (5 * hpp + 3 * H) * D * qkv.dtype.itemsize, T,
                tiles=8 * _GROUP_UNROLL)),
        interpret=interpret,
    )(vl, qkv, qkv, qkv, out, g.astype(qkv.dtype), lse)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def flash_dense_packed(qkv, valid_len, heads, causal=False, scale=None,
                       interpret=False):
    """Dense flash self-attention straight off the projection: ``qkv``
    (B, T, 3*H*D) with q | k | v side by side, the result (B, T, H*D),
    the gradient one (B, T, 3*H*D) array. No transpose, no split. For
    shapes ``packed_dense_shapes`` admits."""
    return _packed_fwd_lse(qkv, valid_len, heads, causal, scale,
                           interpret)[0]


def _packed_fwd(qkv, valid_len, heads, causal, scale, interpret):
    out, lse = _packed_fwd_lse(qkv, valid_len, heads, causal, scale,
                               interpret)
    return out, (qkv, valid_len, out, lse)


def _packed_bwd(heads, causal, scale, interpret, res, g):
    qkv, valid_len, out, lse = res
    return _packed_backward(qkv, valid_len, out, lse, g, heads, causal,
                            scale, interpret), None


flash_dense_packed.defvjp(_packed_fwd, _packed_bwd)


# --------------------------------------------------------------------- #
# custom-vjp entry
# --------------------------------------------------------------------- #

# Trace-time count of which implementation each attention call site got
# from the dispatchers below: ``dense_packed`` (the dense pair in the
# projection's layout), ``dense_bhtd`` (the (B, H, T, D) dense pair),
# ``stream_bhtd`` (the streaming kernels), ``blockwise_jnp`` (no
# kernel). One count a call site and a trace — a layer traced once
# counts once however often it runs.
_DISPATCH = collections.Counter()


def dispatch_tally(reset=False):
    """{implementation: call sites traced so far}; see ``_DISPATCH``.
    ``profiler.attention_dispatch`` is its public face."""
    tally = dict(_DISPATCH)
    if reset:
        _DISPATCH.clear()
    return tally


class _Static:
    """Pytree-static residual carrier: the forward's trace-time kernel
    decision (dense vs streaming) rides through the custom_vjp residuals
    as treedef aux data, so the backward can never disagree with the
    forward even if MXTPU_FLASH_DENSE_T changes between the fwd and bwd
    traces (the documented 'must not change within one process'
    invariant, now enforced structurally)."""

    def __init__(self, value):
        self.value = value


jax.tree_util.register_pytree_node(
    _Static, lambda s: ((), s.value), lambda aux, _: _Static(aux))

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def flash_attention_bhtd(q, k, v, valid_len, causal=False, scale=None,
                         interpret=False):
    """Flash attention in (B, H, T, D) layout with a Pallas backward.
    Public entry: ops.attention uses this when Pallas is available;
    ``interpret=True`` runs the same kernels on CPU."""
    return _flash_forward(q, k, v, valid_len, causal=causal, scale=scale,
                          interpret=interpret)


def _fwd(q, k, v, valid_len, causal, scale, interpret):
    dense = _use_dense(q.shape[2], k.shape[2])
    block_q, block_k = (None, None) if dense else _resolve_blocks(None,
                                                                  None)
    out, lse = _flash_fwd_lse(q, k, v, valid_len, causal=causal,
                              scale=scale, block_q=block_q,
                              block_k=block_k, interpret=interpret,
                              dense=dense,
                              hpp=_dense_hpp(q.shape[1]) if dense
                              else None)
    return out, (q, k, v, valid_len, out, lse, _Static(dense))


def _bwd(causal, scale, interpret, res, g):
    q, k, v, valid_len, out, lse, static = res
    dense = static.value                # the forward's decision, verbatim
    block_q, block_k = (None, None) if dense else \
        _resolve_blocks(None, None)
    dq, dk, dv = _flash_backward(q, k, v, valid_len, out, lse, g,
                                 causal=causal, scale=scale,
                                 block_q=block_q, block_k=block_k,
                                 interpret=interpret, dense=dense,
                                 hpp=_dense_hpp(q.shape[1], bwd=True)
                                 if dense else None)
    return dq, dk, dv, None


flash_attention_bhtd.defvjp(_fwd, _bwd)


def tpu_kernel_eligible(D, causal=False, Tq=None, Tk=None):
    """True when use_flash_attention will hand (length-maskable) inputs
    to the Pallas kernel rather than the jnp blockwise path. Shared with
    the models' packed-qkv fast path so the caller-side relayout is only
    done when the kernel actually consumes the bhtd layout. Raises on
    TPU when the kernel cannot run (see ``pallas_path``)."""
    on = pallas_path(_env_interpret())
    if os.environ.get("MXTPU_FLASH_FORCE_FALLBACK") == "1":
        on = False  # A/B lever: measure jnp blockwise vs the kernel
    # the Pallas kernel's causal grid assumes square Tq == Tk; offset
    # (KV-cache style) causal queries take the blockwise path, which is
    # bottom-right aligned
    if causal and Tq is not None and Tq != Tk:
        on = False
    return on and D <= 256


def _active_mesh():
    from ..parallel.spmd import _ACTIVE_MESH
    return _ACTIVE_MESH.get()


def packed_dense_eligible(T, H, D):
    """The packed dense pair's selection rule, from what the trace sees:
    the Pallas kernel runs here, the shapes are the pair's
    (``packed_dense_shapes``), and the active trainer mesh is absent or
    has exactly ONE device. A mesh of several devices (dp, fsdp, tp, sp
    alike) keeps the (B, H, T, D) route: a four-chip process of the
    benchmark died with SIGSEGV once with this pair under ``shard_map``
    (PERF.md, PR 28) and no cause is known, while that route has a clean
    record there."""
    mesh = _active_mesh()
    return (tpu_kernel_eligible(D) and packed_dense_shapes(T, H, D)
            and (mesh is None or mesh.size == 1))


def flash_packed_self_attention(qkv, heads, valid_length=None,
                                causal=False, scale=None):
    """Self-attention over the packed projection, (B, T, 3*H*D) in and
    (B, T, H*D) out, for call sites ``packed_dense_eligible`` admits."""
    B, T, W = qkv.shape
    if not packed_dense_eligible(T, heads, W // (3 * heads)):
        raise MXNetError(
            f"flash_packed_self_attention: T={T}, heads={heads}, "
            f"D={W // (3 * heads)} under the active mesh is outside "
            "packed_dense_eligible; use scaled_dot_product_attention")
    if valid_length is None:
        valid_length = jnp.full((B,), T, jnp.int32)
    _DISPATCH["dense_packed"] += 1
    return flash_dense_packed(qkv, valid_length, heads, causal, scale,
                              _env_interpret())


def use_flash_attention(q, k, v, key_mask=None, causal=False, scale=None,
                        valid_length=None, layout="bthd"):
    """Dispatch helper for ops.attention: (B, T, H, D) in/out by
    default; ``layout="bhtd"`` takes and returns (B, H, T, D) — the
    kernels' native layout — so layout-aware callers (the packed-qkv
    transformer cells) skip the per-tensor transposes entirely.

    The Pallas kernel runs on TPU when the mask is expressible as
    per-batch key LENGTHS (valid_length, or no mask at all) — the
    contiguous-prefix form every bucketing/padding pipeline produces.
    Arbitrary boolean masks take the pure-jnp blockwise path (same
    math, XLA-fused) on every platform — a selection from static
    shapes, not a fallback. Dispatch is static: no data-dependent
    branching, safe under jit.

    PRECEDENCE when both key_mask and valid_length are given: the two
    must describe the same keep-set (a prefix per batch row). The TPU
    kernel consumes the lengths; the fallback ANDs both, so a
    non-prefix key_mask combined with lengths would diverge between
    platforms — that combination is a caller bug which cannot be
    validated under jit (the check would be data-dependent)."""
    if layout == "bhtd":
        B, H, Tq, D = q.shape
        Tk = k.shape[2]
    else:
        B, Tq, H, D = q.shape
        Tk = k.shape[1]
    if valid_length is None and key_mask is None:
        valid_length = jnp.full((B,), Tk, jnp.int32)
    if not (tpu_kernel_eligible(D, causal, Tq, Tk)
            and valid_length is not None):
        from .attention import _sdpa_blockwise
        _DISPATCH["blockwise_jnp"] += 1
        sc = D ** -0.5 if scale is None else scale
        if valid_length is not None:
            vlm = lax.broadcasted_iota(jnp.int32, (B, Tk), 1) < \
                valid_length.astype(jnp.int32)[:, None]
            key_mask = vlm if key_mask is None else \
                jnp.logical_and(key_mask.astype(bool), vlm)
        if layout == "bhtd":    # blockwise math wants (B, T, H, D)
            out = _sdpa_blockwise(q.transpose(0, 2, 1, 3),
                                  k.transpose(0, 2, 1, 3),
                                  v.transpose(0, 2, 1, 3),
                                  key_mask, causal, sc)
            return out.transpose(0, 2, 1, 3)
        return _sdpa_blockwise(q, k, v, key_mask, causal, sc)
    interp = _env_interpret()
    _DISPATCH["dense_bhtd" if _use_dense(Tq, Tk) else "stream_bhtd"] += 1
    if layout == "bhtd":
        return _flash_on_mesh(q, k, v, valid_length, causal, scale,
                              interp)
    out = _flash_on_mesh(q.transpose(0, 2, 1, 3),
                         k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3),
                         valid_length, causal, scale, interp)
    return out.transpose(0, 2, 1, 3)


def _flash_on_mesh(q, k, v, valid_length, causal, scale, interp):
    """``flash_attention_bhtd`` inside SPMDTrainer's GSPMD step. A Mosaic
    custom call cannot be partitioned by the compiler (first contact on
    a four-chip mesh, 2026-09: "Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map"), so when the
    step's mesh spans several devices the kernel is mapped by hand over
    the layout the models already constrain q/k/v to: batch over
    (fsdp, dp), heads over tp. Attention is independent per (batch,
    head), so the body needs no collective. Outside a trainer's trace
    (no active mesh) and on a one-device mesh this is the plain call."""
    mesh = _active_mesh()
    if mesh is None or mesh.size == 1:
        return flash_attention_bhtd(q, k, v, valid_length, causal, scale,
                                    interp)
    from jax.sharding import PartitionSpec as P

    from ..base import shard_map
    # the trainer has checked that the batch divides over fsdp x dp;
    # heads that do not divide over tp stay whole on every tp rank
    batch = tuple(a for a in ("fsdp", "dp") if mesh.shape.get(a, 1) > 1)
    tp = mesh.shape.get("tp", 1)
    heads = "tp" if tp > 1 and q.shape[1] % tp == 0 else None
    spec = P(batch or None, heads, None, None)
    return shard_map(
        lambda q_, k_, v_, vl_: flash_attention_bhtd(
            q_, k_, v_, vl_, causal, scale, interp),
        mesh=mesh, in_specs=(spec, spec, spec, P(batch or None)),
        out_specs=spec, check_vma=False)(q, k, v, valid_length)


# --------------------------------------------------------------------- #
# (out, lse) block primitive — the ring-attention building block
# --------------------------------------------------------------------- #

def _prefix_causal_mask(B, Tq, Tk, valid_len, causal):
    """(B, 1, Tq, Tk) boolean mask: keys < valid_len, optionally causal.
    SHARED by the dense forward and the residual-based dense backward so
    the p = exp(s - LSE) identity holds bit-for-bit."""
    k_pos = lax.broadcasted_iota(jnp.int32, (B, 1, 1, Tk), 3)
    mask = k_pos < valid_len.astype(jnp.int32).reshape(B, 1, 1, 1)
    if causal:
        # bottom-right aligned for Tq != Tk (KV-cache convention)
        q_pos = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 0)
        kk = lax.broadcasted_iota(jnp.int32, (Tq, Tk), 1)
        mask = jnp.logical_and(mask,
                               (kk <= q_pos + (Tk - Tq))[None, None])
    return mask


def _dense_attn_lse(q, k, v, valid_len, causal, scale):
    """jnp fallback returning (out, lse). q/k/v: (B, H, T, D)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sc = D ** -0.5 if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    mask = _prefix_causal_mask(B, Tq, Tk, valid_len, causal)
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p,
                     v.astype(jnp.float32)) / \
        jnp.maximum(l, 1e-30)[..., None]
    # match the kernels: fully-masked rows are zero with lse=_NEG_INF
    row_ok = m > _NEG_INF / 2
    out = jnp.where(row_ok[..., None], out, 0.0)
    lse = jnp.where(row_ok, m + jnp.log(jnp.maximum(l, 1e-30)),
                    _NEG_INF)
    return out.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def block_attn_lse(q, k, v, valid_len, causal=False, scale=None,
                   interpret=False):
    """One attention block returning (out, lse) — Pallas forward AND
    backward on TPU (or off TPU under interpret mode), the jnp reference
    off TPU otherwise (``pallas_path``). The lse output is what makes
    partial results MERGEABLE across ring steps (see
    parallel/ring_attention.py merge rule); it is non-differentiable."""
    if pallas_path(interpret):
        dense = _use_dense(q.shape[2], k.shape[2])
        return _flash_fwd_lse(q, k, v, valid_len, causal=causal,
                              scale=scale, interpret=interpret,
                              dense=dense,
                              hpp=_dense_hpp(q.shape[1]) if dense
                              else None)
    return _dense_attn_lse(q, k, v, valid_len, causal, scale)


def _block_fwd(q, k, v, valid_len, causal, scale, interpret):
    out, lse = block_attn_lse(q, k, v, valid_len, causal, scale,
                              interpret)
    # None = jnp-fallback path taken; else the dense/streaming decision
    dense = (_use_dense(q.shape[2], k.shape[2])
             if pallas_path(interpret) else None)
    return (out, lse), (q, k, v, valid_len, out, lse, _Static(dense))


def _dense_block_bwd(q, k, v, valid_len, out, lse, g, causal, scale):
    """Residual-based dense backward: p = exp(s - LSE) rebuilt from the
    saved logsumexp — no forward recompute. All (B, H, T, D)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    sc = D ** -0.5 if scale is None else scale
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    gf = g.astype(jnp.float32)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * sc
    mask = _prefix_causal_mask(B, Tq, Tk, valid_len, causal)
    p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
    delta = jnp.sum(gf * out.astype(jnp.float32), axis=-1)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, vf)
    ds = p * (dp - delta[..., None]) * sc
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, kf)
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, qf)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _block_bwd(causal, scale, interpret, res, g):
    q, k, v, valid_len, out, lse, static = res
    g_out, _ = g                              # lse cotangent is dropped
    if static.value is not None:
        dense = static.value            # the forward's decision, verbatim
        dq, dk, dv = _flash_backward(q, k, v, valid_len, out, lse, g_out,
                                     causal=causal, scale=scale,
                                     interpret=interpret, dense=dense,
                                     hpp=_dense_hpp(q.shape[1], bwd=True)
                                     if dense else None)
        return dq, dk, dv, None
    dq, dk, dv = _dense_block_bwd(q, k, v, valid_len, out, lse, g_out,
                                  causal, scale)
    return dq, dk, dv, None


block_attn_lse.defvjp(_block_fwd, _block_bwd)

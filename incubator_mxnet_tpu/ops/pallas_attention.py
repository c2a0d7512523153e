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


def _tile_mask(bq, bk, vl, causal, q_off=0, k_off=0):
    """(bq, bk) boolean attend-mask for one score tile: keys < ``vl``,
    optionally causal (top-left aligned — square Tq == Tk only, enforced
    by use_flash_attention). ``q_off``/``k_off`` position the tile inside
    the full (Tq, Tk) score matrix. Shared by ALL kernels (streaming
    fwd/dq/dkv and dense fwd/bwd) so mask semantics cannot drift between
    paths."""
    k_pos = k_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = k_pos < vl
    if causal:
        q_pos = q_off + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
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
# custom-vjp entry
# --------------------------------------------------------------------- #

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
    from ..parallel.spmd import _ACTIVE_MESH
    mesh = _ACTIVE_MESH.get()
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

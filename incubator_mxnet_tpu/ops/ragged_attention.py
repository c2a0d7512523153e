"""Ragged paged-KV decode attention (the serving-side Pallas kernel).

The training kernels in ``ops.pallas_attention`` assume dense
(B, H, T, D) K/V buffers — every query pays DMA + compute over the full
``Tmax`` window regardless of how many tokens its sequence actually
holds. For continuous-batching inference that is exactly backwards: the
batch is a set of SLOTS at wildly different sequence lengths, and cache
memory must scale with live tokens, not ``B × Tmax``. Following the
ragged-paged-attention design (arxiv 2604.15464; the Gemma-on-TPU
serving study 2605.25645 attributes most TPU serving wins to this
batching + cache discipline), K/V live in ONE shared page pool a layer

    kv_pool : (num_pages, H, page_size, 2 * D)

a head's keys in lanes [0, D), its values in lanes [D, 2 * D). At
D = 64 a head's bf16 page is exactly one native (16, 128) tile, so the
chip stores the pool row-major, which is what a Mosaic kernel reads: no
program relays a pool out (a (.., 64)-wide pool is stored page-index
minor and cost two whole-pool copies a pool a program; PERF.md, PR 33).
Each slot owns an ordered list of pages (its PAGE TABLE row). Page 0
is the NULL page: never allocated, dead page-table entries point at it,
and its contents are garbage by construction — every read of it is
masked by the slot's length.

Kernel design (per /opt/skills/guides/pallas_guide.md):
  - grid (S, max_pages) under a ``PrefetchScalarGridSpec``: the page
    table and per-slot lengths are scalar-prefetched, so the K/V
    BlockSpec index_map dereferences ``page_table[s, j]`` to DMA exactly
    the page that grid step needs — the kernel never sees a gather.
  - the last grid dimension is sequential on TPU, so the online-softmax
    state (m, l, acc) carries across pages in VMEM scratch: init at
    j == 0, accumulate per live page, finalize (acc / l, masked rows
    zeroed) at j == max_pages - 1.
  - DEAD PAGES COST NOTHING: ``pl.when(j * page_size < length)`` skips
    the compute, and because every dead entry indexes the null page the
    block index is unchanged between consecutive dead steps — Pallas
    skips the re-DMA. A slot at length L pays for ceil(L / page_size)
    pages, not max_pages.
  - one decode query per slot: scores are (1, page_size) rows per head,
    dot operands stay in the input dtype, accumulation is f32 via
    ``preferred_element_type`` (same dtype discipline as the training
    kernels). Decode attention is a prefix mask — the query IS position
    ``length - 1`` — so no causal triangle is needed.
  - NO LANE SLICE in the head loop: the query arrives zero-padded to
    2 * D lanes, so ``q_pad · kvᵀ`` is exactly ``q · kᵀ``; ``p · kv``
    yields ``[p·k | p·v]`` and the caller keeps lanes [D, 2 * D) of the
    kernel's output. Contraction and output are one MXU tile wide
    either way.

Off TPU the dispatchers run a pure-jnp gather-and-mask reference (the
CPU serving path and the test oracle), or the real kernel in interpret
mode under ``MXTPU_FLASH_INTERPRET=1``. On TPU they run the Mosaic
kernel or raise — one decision, ``ops.pallas_attention.pallas_path``,
shared with the training kernels. Same masked-row contract as the
training kernels: a slot with length 0 produces EXACTLY zero output.

``ragged_prefill_attention`` is the chunked-prefill sibling: a CHUNK of
C consecutive prompt tokens of ONE slot (absolute positions
``q_start + i``) attends the slot's already-populated paged prefix plus
the causal intra-chunk part — the chunk's own K/V is scattered into the
pages first, so a single per-query prefix mask ``pos_k <= pos_q``
covers both. Same kernel shape as decode (grid over the page axis,
online-softmax scratch carried across pages, dead pages skipped via the
repeated-null-page index trick), with C query rows per head instead of
one; same jnp gather fallback as CPU path and oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from . import pallas_attention as _pa

_NEG_INF = -1e30

__all__ = ["ragged_paged_attention", "ragged_attention_reference",
           "ragged_prefill_attention", "ragged_prefill_reference",
           "ragged_verify_attention", "ragged_verify_reference"]


def _split(kv):
    """Keys (lanes [0, D)) and values (lanes [D, 2 * D)) of a fused
    array — the one place the jnp paths read the pool's lane layout."""
    D = kv.shape[-1] // 2
    return kv[..., :D], kv[..., D:]


def _pad_lanes(q):
    """Zero-pad queries to the pool's 2 * D lanes: against a fused
    ``keys | values`` tile the padded product is exactly ``q · kᵀ``."""
    return jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, q.shape[-1])])


def _page_scale(scale_refs, page, lanes):
    """(1, lanes) f32 inline-dequant scales of one quantized page: lanes
    [0, D) by its key scale, lanes [D, 2 * D) by its value scale; None
    for an unquantized pool (no scale refs). The (P,) per-page scale
    arrays ride the SAME scalar-prefetch path as the page table: the
    grid step that DMAs a page reads that page's scales from SMEM and
    dequantizes the int8/fp8 block at the DMA boundary — the pool never
    materializes in float anywhere."""
    if not scale_refs:
        return None
    ks_ref, vs_ref = scale_refs
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    return jnp.where(lane < lanes // 2, ks_ref[page], vs_ref[page])


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _accumulate_page(q_ref, kv_ref, m_ref, l_ref, acc_ref, *, heads,
                     scale, valid, visible, page_scale):
    """One page's online-softmax update for every head (unrolled).
    ``valid`` (page_size, 1): positions some consumed query row may
    read; ``visible`` (rows, page_size): the per-row prefix mask;
    ``page_scale`` (1, 2 * D) f32 or None (unquantized pool)."""
    for h in range(heads):
        q = q_ref[0, h]                 # (rows, 2D), lanes [D, 2D) zero
        kv = kv_ref[0, h]               # (page_size, 2D): keys | values
        if page_scale is not None:      # inline dequant
            q = q.astype(jnp.float32)
            kv = kv.astype(jnp.float32) * page_scale
        # SELECT masked rows out of the tile (not just zero-weight
        # them): a freed page can be reused carrying non-finite garbage
        # in positions past the new owner's length, and 0 * NaN = NaN
        # would leak it through the weighted sum (and, keys and values
        # sharing the tile, through the padded query's zero lanes) —
        # masked reads must never matter, even poisoned ones (a
        # quantized pool's NaN channel is the page SCALE — the select
        # covers it the same way)
        kv = jnp.where(valid, kv, 0.0)
        sc = jnp.dot(q, kv.T, preferred_element_type=jnp.float32,
                     precision=lax.Precision.DEFAULT) * scale
        sc = jnp.where(visible, sc, _NEG_INF)
        m_prev = m_ref[h]               # (rows,)
        l_prev = l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[:, None])        # (rows, page_size) f32
        alpha = jnp.exp(m_prev - m_new)
        m_ref[h] = m_new
        l_ref[h] = l_prev * alpha + jnp.sum(p, axis=-1)
        # [p·k | p·v]: the caller keeps lanes [D, 2D)
        acc_ref[h] = acc_ref[h] * alpha[:, None] + jnp.dot(
            p.astype(kv.dtype), kv, preferred_element_type=jnp.float32,
            precision=lax.Precision.DEFAULT)


def _finalize(o_ref, m_ref, l_ref, acc_ref, heads):
    for h in range(heads):
        m = m_ref[h]
        l_safe = jnp.maximum(l_ref[h], 1e-30)
        # rows that never accumulated (a length-0 slot, a dead verify
        # slot, padded chunk rows past every accumulated page): m never
        # left _NEG_INF — emit exactly zero, the masked-row contract
        # shared with the training kernels (ops.pallas_attention).
        # Negated-compare form so a NaN running max (poisoned K/V page)
        # fails the dead-row test and PROPAGATES instead of being
        # silently zeroed — the serving engine's non-finite guard
        # depends on corruption staying visible in the output.
        # (the compare runs on the already-expanded f32 column:
        # Mosaic cannot reshape an i1 vector)
        row_ok = ~(m[:, None] <= _NEG_INF / 2)
        o_ref[0, h] = jnp.where(row_ok, acc_ref[h] / l_safe[:, None],
                                0.0).astype(o_ref.dtype)


def _paged_call(kernel, name, grid, prefetch, q4, kv_pool, q_map, kv_map,
                interpret):
    """The one ``pallas_call`` shape of the three kernels: ``prefetch``
    int32 page table / lengths (and f32 page scales) in SMEM, queries
    ``q4`` (G, H, rows, D) blocked by ``q_map``, the pool blocked a
    page at a time by ``kv_map`` (which dereferences the prefetched
    page table, so the kernel never sees a gather), online-softmax
    state in VMEM scratch. Returns (G, H, rows, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    D = q4.shape[-1]
    q4 = _pad_lanes(q4)
    _, H, rows, lanes = q4.shape
    page_size = kv_pool.shape[2]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, H, rows, lanes), q_map),
            pl.BlockSpec((1, H, page_size, lanes), kv_map),
        ],
        out_specs=pl.BlockSpec((1, H, rows, lanes), q_map),
        scratch_shapes=[
            pltpu.VMEM((H, rows), jnp.float32),         # m
            pltpu.VMEM((H, rows), jnp.float32),         # l
            pltpu.VMEM((H, rows, lanes), jnp.float32),  # acc
        ],
    )
    out = pl.pallas_call(
        kernel,
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        interpret=interpret,
    )(*prefetch, q4, kv_pool)
    return out[..., D:]


def _scale_prefetch(k_scale, v_scale):
    """Per-page scales join the scalar-prefetch set (quantized pools)."""
    if k_scale is None:
        return ()
    return (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))


def _ragged_kernel(pt_ref, ln_ref, *refs, scale, page_size, n_pages,
                   heads):
    from jax.experimental import pallas as pl

    *scale_refs, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    s = pl.program_id(0)
    j = pl.program_id(1)
    length = ln_ref[s]                          # live tokens this slot

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    @pl.when(j * page_size < length)
    def _accumulate():
        valid = (j * page_size + lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)) < length
        pos = j * page_size + lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        _accumulate_page(
            q_ref, kv_ref, m_ref, l_ref, acc_ref, heads=heads,
            scale=scale, valid=valid, visible=pos < length,
            page_scale=_page_scale(scale_refs, pt_ref[s, j],
                                   kv_ref.shape[-1]))

    @pl.when(j == n_pages - 1)
    def _fin():
        _finalize(o_ref, m_ref, l_ref, acc_ref, heads)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_pallas(q, kv_pool, page_table, lengths, scale, interpret,
                   k_scale=None, v_scale=None):
    """q: (S, H, D); kv_pool: (P, H, page_size, 2D); page_table:
    (S, max_pages) int32; lengths: (S,) int32; k_scale/v_scale: (P,)
    f32 per-page scales of a quantized pool, or None. Returns
    (S, H, D)."""
    S, H, _ = q.shape
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=kv_pool.shape[2],
        n_pages=n_pages, heads=H)
    out = _paged_call(
        kernel, "mxtpu_ragged_decode" + ("_q" if quant else ""),
        (S, n_pages),
        (page_table.astype(jnp.int32), lengths.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q[:, :, None, :], kv_pool,
        lambda s, j, *_: (s, 0, 0, 0),
        lambda s, j, pt, *_: (pt[s, j], 0, 0, 0), interpret)
    return out[:, :, 0, :]


def _gather_window(kv_pool, page_table, k_scale=None, v_scale=None):
    """Dense (S, H, K, D) key and value windows of each slot's pages —
    the expensive gather over the pool's page axis, shared by the
    reference paths. ``k_scale``/``v_scale`` (P,) dequantize a
    quantized pool inline with the gather (per-page broadcast) — the
    f32 oracle's quantized arm."""
    S, n_pages = page_table.shape
    _, H, page_size, _ = kv_pool.shape

    def window(g, pscale):              # (S, n_pages, H, ps, D)
        if pscale is not None:
            g = g.astype(jnp.float32) * \
                pscale[page_table][:, :, None, None, None]
        g = jnp.moveaxis(g, 2, 1)       # (S, H, n_pages, ps, D)
        return g.reshape(S, H, n_pages * page_size, -1)

    k, v = _split(kv_pool[page_table])
    return window(k, k_scale), window(v, v_scale)


def _reference_core(q, k, v, lengths, sc):
    """Masked online-softmax attention over a pre-gathered window.
    q: (S, H, D); k/v: (S, H, K, D). Factored out so the verify
    reference can reuse ONE gather across its W query rows while each
    row runs bitwise the same computation as the decode reference."""
    S, H, D = q.shape
    K = k.shape[2]
    s = jnp.einsum("shd,shkd->shk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    pos = lax.broadcasted_iota(jnp.int32, (S, K), 1)
    valid = pos < lengths.astype(jnp.int32)[:, None]
    s = jnp.where(valid[:, None, :], s, _NEG_INF)
    # select masked positions out of V: a reused page may carry
    # non-finite garbage past this slot's length and 0 * NaN = NaN
    # would leak it through the weighted sum (same contract as the
    # Pallas kernel)
    v = jnp.where(valid[:, None, :, None], v, 0.0)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("shk,shkd->shd", p, v.astype(jnp.float32)) / \
        jnp.maximum(l, 1e-30)[..., None]
    # negated compare: length-0 slots → zero, but a NaN max (poisoned
    # page) PROPAGATES so the engine's non-finite guard can see it
    row_ok = ~(m <= _NEG_INF / 2)
    return jnp.where(row_ok[..., None], out, 0.0).astype(q.dtype)


def ragged_attention_reference(q, kv_pool, page_table, lengths,
                               scale=None, k_scale=None, v_scale=None):
    """Pure-jnp oracle and CPU serving path: gather each slot's pages to
    dense (S, H, K, D) key and value windows (the pool's two lane
    halves), mask positions >= length, softmax with f32 accumulation.
    Jit-friendly (static shapes; the gather is an XLA gather over the
    pool's page axis). ``k_scale``/``v_scale`` (P,) dequantize
    quantized pools at the gather (per-page broadcast) — past that
    point the math is BITWISE the unquantized reference, which is what
    makes this the quantization accuracy oracle's denominator."""
    D = q.shape[-1]
    sc = D ** -0.5 if scale is None else scale
    k, v = _gather_window(kv_pool, page_table, k_scale, v_scale)
    return _reference_core(q, k, v, lengths, sc)


def ragged_paged_attention(q, kv_pool, page_table, lengths, scale=None,
                           interpret=None, k_scale=None, v_scale=None):
    """Decode attention for one new token per slot against the paged KV
    pool. q: (S, H, D); kv_pool: (num_pages, H, page_size, 2 * D), keys
    in lanes [0, D) and values in lanes [D, 2 * D); page_table:
    (S, max_pages) int32 (dead entries 0 = null page); lengths: (S,)
    int32 — number of live KV tokens INCLUDING the one just written for
    this step. Returns (S, H, D).

    ``k_scale``/``v_scale`` (P,) f32 mark the pool QUANTIZED (int8 /
    fp8 codes with per-page symmetric scales, one for the key half and
    one for the value half — serve/paged_kv.py): the Pallas path
    prefetches them next to the page table and dequantizes inline at
    the DMA boundary; the jnp path dequantizes at the gather. None (the
    default) is the unquantized path.

    Dispatch is static (``ops.pallas_attention.pallas_path``): the
    Mosaic kernel on TPU (or an error — never the reference); off TPU
    the jnp gather reference (the CPU serving path), or the kernel in
    the interpreter under ``MXTPU_FLASH_INTERPRET=1`` /
    ``interpret=True``. Both paths share the masked-row contract."""
    if interpret is None:
        interpret = _pa._env_interpret()
    sc = q.shape[-1] ** -0.5 if scale is None else scale
    if _pa.pallas_path(interpret):
        return _ragged_pallas(q, kv_pool, page_table, lengths, sc,
                              interpret, k_scale, v_scale)
    return ragged_attention_reference(q, kv_pool, page_table, lengths,
                                      sc, k_scale, v_scale)


# --------------------------------------------------------------------- #
# prefill over a paged prefix (the chunked-prefill attention variant)
# --------------------------------------------------------------------- #

def _ragged_prefill_kernel(pr_ref, qi_ref, *refs, scale, page_size,
                           n_pages, heads, chunk):
    from jax.experimental import pallas as pl

    *scale_refs, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    j = pl.program_id(0)
    start = qi_ref[0]                # first query's absolute position
    n_real = qi_ref[1]               # live queries in the chunk

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    # pages whose first key position is past the last real query's
    # position contribute nothing to any live row — skip them, and
    # (dead entries all indexing the null page) skip their re-DMA too
    @pl.when(j * page_size < start + n_real)
    def _accumulate():
        # positions past the last real query's view are masked for
        # EVERY row — select them out of the tile so reused-page
        # garbage (possibly non-finite) cannot leak through 0-weight
        # terms
        valid = (j * page_size + lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)) < start + n_real
        pos_k = j * page_size + lax.broadcasted_iota(
            jnp.int32, (chunk, page_size), 1)
        pos_q = start + lax.broadcasted_iota(
            jnp.int32, (chunk, page_size), 0)
        # per-query prefix mask: query i (absolute pos start + i) sees
        # keys [0, start + i] — the paged prefix AND the causal
        # intra-chunk part in one predicate (the chunk's own K/V is
        # already scattered into these pages)
        _accumulate_page(
            q_ref, kv_ref, m_ref, l_ref, acc_ref, heads=heads,
            scale=scale, valid=valid, visible=pos_k <= pos_q,
            page_scale=_page_scale(scale_refs, pr_ref[j],
                                   kv_ref.shape[-1]))

    # every live query attends at least position 0, so only rows that
    # saw no page at all (possible when padded rows extend past every
    # accumulated page) stay at _NEG_INF and emit zero
    @pl.when(j == n_pages - 1)
    def _fin():
        _finalize(o_ref, m_ref, l_ref, acc_ref, heads)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_prefill_pallas(q, kv_pool, page_row, qinfo, scale, interpret,
                           k_scale=None, v_scale=None):
    """q: (C, H, D) chunk queries of ONE slot; kv_pool: (P, H, ps, 2D);
    page_row: (max_pages,) int32; qinfo: (2,) int32 = [q_start,
    n_real]; k_scale/v_scale: (P,) f32 or None. Returns (C, H, D)."""
    C, H, _ = q.shape
    n_pages = page_row.shape[0]
    quant = k_scale is not None
    kernel = functools.partial(
        _ragged_prefill_kernel, scale=scale, page_size=kv_pool.shape[2],
        n_pages=n_pages, heads=H, chunk=C)
    out = _paged_call(
        kernel, "mxtpu_ragged_prefill" + ("_q" if quant else ""),
        (n_pages,),
        (page_row.astype(jnp.int32), qinfo.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q.transpose(1, 0, 2)[None], kv_pool,            # (1, H, C, D)
        lambda j, *_: (0, 0, 0, 0),
        lambda j, pr, *_: (pr[j], 0, 0, 0), interpret)
    return out[0].transpose(1, 0, 2)


def ragged_prefill_reference(q, kv_pool, page_row, q_start, scale=None,
                             n_real=None, k_scale=None, v_scale=None):
    """Pure-jnp oracle and CPU serving path for chunked prefill: gather
    the slot's whole page window dense, apply the per-query prefix mask
    ``pos_k <= q_start + i``, softmax with f32 accumulation. Same
    numerics discipline as ``ragged_attention_reference``; jit-friendly
    (``q_start`` is traced data). ``n_real`` is the count of live
    (non-padded) chunk rows, default C."""
    C, H, D = q.shape
    page_size = kv_pool.shape[2]
    n_pages = page_row.shape[0]
    K = n_pages * page_size
    sc = D ** -0.5 if scale is None else scale
    if n_real is None:
        n_real = C

    def window(g, pscale):                      # (n_pages, H, ps, D)
        if pscale is not None:                  # per-page dequant
            g = g.astype(jnp.float32) * \
                pscale[page_row][:, None, None, None]
        g = jnp.moveaxis(g, 1, 0)               # (H, n_pages, ps, D)
        return g.reshape(H, K, D)

    k, v = _split(kv_pool[page_row])
    k = window(k, k_scale)
    v = window(v, v_scale)
    s = jnp.einsum("chd,hkd->chk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sc
    pos_k = lax.broadcasted_iota(jnp.int32, (C, K), 1)
    pos_q = q_start + lax.broadcasted_iota(jnp.int32, (C, K), 0)
    s = jnp.where((pos_k <= pos_q)[:, None, :], s, _NEG_INF)
    # select positions no LIVE query may see out of V (reused-page
    # garbage must not leak through 0-weight terms — see the decode
    # reference): a live row i < n_real reads positions
    # <= q_start + i <= q_start + n_real - 1, all freshly written, so
    # zeroing from q_start + n_real changes no live row's math. The
    # bound must be n_real, not C: on a PARTIAL chunk the positions in
    # [q_start + n_real, q_start + C) are UNWRITTEN — a recycled page
    # can carry a quarantined slot's non-finite K/V there, and
    # 0 * NaN = NaN would poison every live row of this chunk (found
    # by the chaos corrupt_page scenario under speculation, whose
    # wide verify writes NaN into more offsets of the victim's pages
    # before quarantine frees them). Same rule as the Pallas kernel's
    # ``pos < start + n_real`` select. Positions a later LIVE query
    # legitimately reads stay as-is: if they are poisoned, that query
    # is poisoned, which is the point; padded rows may now read zeros,
    # but their output was already contractually garbage.
    never_read = lax.broadcasted_iota(jnp.int32, (K,), 0) >= \
        q_start + n_real
    v = jnp.where(never_read[None, :, None], 0.0, v)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    out = jnp.einsum("chk,hkd->chd", p, v.astype(jnp.float32)) / \
        jnp.maximum(l, 1e-30)[..., None]
    # negated compare: padded rows → zero, NaN propagates (see decode)
    row_ok = ~(m <= _NEG_INF / 2)
    return jnp.where(row_ok[..., None], out, 0.0).astype(q.dtype)


# --------------------------------------------------------------------- #
# multi-query verify over a paged prefix (the speculative-decoding
# draft-then-verify attention variant)
# --------------------------------------------------------------------- #

def _ragged_verify_kernel(pt_ref, ln_ref, dl_ref, *refs, scale,
                          page_size, n_pages, heads, window):
    """Decode kernel generalized to ``window`` queries per slot: query
    row r of slot s sits at absolute position ``lengths[s] - 1 + r``
    (row 0 IS the ordinary decode query) and attends keys
    ``[0, lengths[s] - 1 + r]`` — the slot's paged prefix plus the
    causal intra-window part in one predicate, exactly the
    chunked-prefill masking with a per-SLOT dynamic start. Same
    online-softmax scratch carried across the page axis, same
    dead-page skip via the repeated-null-page index, same NaN
    propagation / masked-tile-select contract as the decode kernel."""
    from jax.experimental import pallas as pl

    *scale_refs, q_ref, kv_ref, o_ref, m_ref, l_ref, acc_ref = refs
    s = pl.program_id(0)
    j = pl.program_id(1)
    length = ln_ref[s]               # keys visible to query row 0
    dl = dl_ref[s]                   # slot's REAL draft count this step

    @pl.when(j == 0)
    def _init():
        _init_state(m_ref, l_ref, acc_ref)

    # the last CONSUMED row (row dl — accepted drafts + the
    # bonus/correction) sees keys up to length + dl - 1, and that is
    # also the last position freshly written this step; pages wholly
    # past it (and every page of a dead slot) contribute nothing —
    # dead entries all index the null page, so skipping also skips the
    # re-DMA
    @pl.when((length > 0) & (j * page_size < length + dl))
    def _accumulate():
        # positions no CONSUMED row may ever see are selected out of
        # the tile so reused-page garbage (possibly non-finite) cannot
        # leak through 0-weight terms. The bound must be the slot's
        # real written extent length + dl, NOT length + window - 1:
        # when a slot drafts fewer than window - 1 tokens, positions in
        # [length + dl, length + window - 1) are UNWRITTEN — a recycled
        # page can carry a quarantined slot's non-finite K/V there, and
        # 0 * NaN = NaN would poison every consumed row, falsely
        # quarantining a healthy slot (same rule as the chunked-prefill
        # kernel's n_real bound). Rows past dl may now read fewer
        # positions than their nominal visibility; their output is
        # discarded by the engine and never feeds acceptance (the op's
        # documented PRECONDITION).
        valid = (j * page_size + lax.broadcasted_iota(
            jnp.int32, (page_size, 1), 0)) < length + dl
        pos_k = j * page_size + lax.broadcasted_iota(
            jnp.int32, (window, page_size), 1)
        row = lax.broadcasted_iota(jnp.int32, (window, page_size), 0)
        # row r (absolute position length - 1 + r) sees keys
        # [0, length - 1 + r]: prefix + causal intra-window in one
        # predicate
        _accumulate_page(
            q_ref, kv_ref, m_ref, l_ref, acc_ref, heads=heads,
            scale=scale, valid=valid, visible=pos_k < length + row,
            page_scale=_page_scale(scale_refs, pt_ref[s, j],
                                   kv_ref.shape[-1]))

    @pl.when(j == n_pages - 1)
    def _fin():
        _finalize(o_ref, m_ref, l_ref, acc_ref, heads)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_verify_pallas(q, kv_pool, page_table, lengths, draft_len,
                          scale, interpret, k_scale=None, v_scale=None):
    """q: (S, W, H, D) — W verify queries per slot; kv_pool:
    (P, H, page_size, 2D); page_table: (S, max_pages) int32; lengths:
    (S,) int32 = keys visible to query row 0 (0 = dead slot);
    draft_len: (S,) int32 = the slot's real draft count (index of its
    last consumed row, bounding the freshly-written extent);
    k_scale/v_scale: (P,) f32 or None. Returns (S, W, H, D)."""
    S, W, H, _ = q.shape
    n_pages = page_table.shape[1]
    quant = k_scale is not None
    kernel = functools.partial(
        _ragged_verify_kernel, scale=scale, page_size=kv_pool.shape[2],
        n_pages=n_pages, heads=H, window=W)
    out = _paged_call(
        kernel, "mxtpu_ragged_verify" + ("_q" if quant else ""),
        (S, n_pages),
        (page_table.astype(jnp.int32), lengths.astype(jnp.int32),
         draft_len.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q.transpose(0, 2, 1, 3), kv_pool,               # (S, H, W, D)
        lambda s, j, *_: (s, 0, 0, 0),
        lambda s, j, pt, *_: (pt[s, j], 0, 0, 0), interpret)
    return out.transpose(0, 2, 1, 3)


def ragged_verify_reference(q, kv_pool, page_table, lengths, scale=None,
                            k_scale=None, v_scale=None):
    """Pure-jnp verify path: one ``ragged_attention_reference`` call
    per query offset — query row r of slot s attends
    ``lengths[s] + r`` keys (0 for dead slots). DELIBERATELY a loop of
    the decode reference at identical per-call shapes rather than a
    wider einsum: on the CPU serving path each verify position then
    reproduces the single-query decode numerics BITWISE, which is what
    the engine's greedy speculative-vs-sequential token parity rests
    on. The expensive part — the pool gather — is row-INDEPENDENT, so
    it runs ONCE and all W rows share the window (the values each row
    sees are identical to a fresh gather, so per-row numerics are
    unchanged); only the cheap mask + softmax + einsums repeat per
    row. This keeps the zero-agreement floor of the W-wide verify
    program near the single-query decode program's cost instead of
    W x it."""
    W = q.shape[1]
    D = q.shape[-1]
    sc = D ** -0.5 if scale is None else scale
    lengths = lengths.astype(jnp.int32)
    k, v = _gather_window(kv_pool, page_table, k_scale, v_scale)
    outs = []
    for r in range(W):
        lr = jnp.where(lengths > 0, lengths + r, 0)
        outs.append(_reference_core(q[:, r], k, v, lr, sc))
    return jnp.stack(outs, axis=1)


def ragged_verify_attention(q, kv_pool, page_table, lengths,
                            draft_len=None, scale=None, interpret=None,
                            k_scale=None, v_scale=None):
    """Multi-query decode (speculative verify) attention: W queries per
    slot — row 0 is the ordinary decode query at position
    ``lengths[s] - 1``, row r sits at position ``lengths[s] - 1 + r``
    and attends the slot's paged prefix plus the causal intra-window
    part (keys ``[0, lengths[s] - 1 + r]``). q: (S, W, H, D);
    kv_pool: (num_pages, H, page_size, 2 * D), keys | values;
    page_table: (S, max_pages) int32 (dead entries 0 = null page);
    lengths: (S,) int32 = keys visible to row 0, i.e. the slot's pre-step KV length
    PLUS ONE for the token written this step (0 = dead slot → exactly
    zero output, the masked-row contract). Returns (S, W, H, D).

    PRECONDITION (the engine's contract): K/V for every position a
    LIVE row may read — [0, lengths[s] - 1 + r] for the rows whose
    output is consumed — are already scattered into the slot's pages.
    Rows past the slot's real draft window may read stale/garbage tail
    positions; their output is discarded by the caller and never
    feeds acceptance (see serve/engine.py).

    ``draft_len`` (S,) int32 gives each slot's real draft count — the
    index of its last consumed row. The Pallas kernel uses it to bound
    the tile select at the slot's freshly-written extent
    ``lengths[s] + draft_len[s]`` so stale non-finite garbage past it
    (a recycled page from a quarantined slot) cannot leak into
    consumed rows through 0-weight terms; the jnp reference is per-row
    exact and needs no bound. Default None = W - 1 for every slot
    (every window position freshly written — callers that fill the
    whole window).

    Dispatch is static (mirrors ``ragged_paged_attention``): the
    Mosaic kernel on TPU; off TPU the per-position jnp reference loop
    (the CPU serving path and oracle) or the kernel in the interpreter
    under ``MXTPU_FLASH_INTERPRET=1`` / ``interpret=True``."""
    if interpret is None:
        interpret = _pa._env_interpret()
    sc = q.shape[-1] ** -0.5 if scale is None else scale
    if draft_len is None:
        draft_len = jnp.full((q.shape[0],), q.shape[1] - 1, jnp.int32)
    if _pa.pallas_path(interpret):
        return _ragged_verify_pallas(q, kv_pool, page_table, lengths,
                                     jnp.asarray(draft_len), sc,
                                     interpret, k_scale, v_scale)
    return ragged_verify_reference(q, kv_pool, page_table, lengths, sc,
                                   k_scale, v_scale)


def ragged_prefill_attention(q, kv_pool, page_row, q_start, n_real=None,
                             scale=None, interpret=None, k_scale=None,
                             v_scale=None):
    """Chunked-prefill attention for ONE slot: C chunk queries at
    absolute positions ``q_start + i`` attend the slot's paged prefix
    plus the causal intra-chunk part. q: (C, H, D); kv_pool:
    (num_pages, H, page_size, 2 * D), keys | values; page_row:
    (max_pages,) int32 (dead entries 0 = null page); q_start: scalar int32; n_real: live queries
    (trailing padded rows emit garbage the caller discards — defaults
    to C). Returns (C, H, D).

    PRECONDITION (the engine's contract): the chunk's own K/V rows are
    already scattered into the slot's pages, and every page covering
    positions [0, q_start + n_real) is live. Dispatch is static
    (mirrors ``ragged_paged_attention``): the Mosaic kernel on TPU;
    off TPU the jnp gather reference (the CPU serving path) or the
    kernel in the interpreter under ``MXTPU_FLASH_INTERPRET=1`` /
    ``interpret=True``."""
    if interpret is None:
        interpret = _pa._env_interpret()
    sc = q.shape[-1] ** -0.5 if scale is None else scale
    if n_real is None:
        n_real = q.shape[0]
    if _pa.pallas_path(interpret):
        qinfo = jnp.stack([jnp.asarray(q_start, jnp.int32),
                           jnp.asarray(n_real, jnp.int32)])
        return _ragged_prefill_pallas(q, kv_pool, page_row, qinfo, sc,
                                      interpret, k_scale, v_scale)
    return ragged_prefill_reference(q, kv_pool, page_row, q_start, sc,
                                    n_real=n_real, k_scale=k_scale,
                                    v_scale=v_scale)

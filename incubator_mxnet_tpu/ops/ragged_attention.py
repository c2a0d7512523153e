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
  - grid (S,) under a ``PrefetchScalarGridSpec``: ONE PROGRAM A SLOT.
    The page table and per-slot lengths are scalar-prefetched; the pool
    operand stays in HBM as it lies (``memory_space=pl.ANY``) and the
    program fetches its own pages, ``pool[page_table[s, j]]``, by
    ``pltpu.make_async_copy`` — the kernel never sees a gather.
  - the program WALKS ONLY WHAT IS LIVE: ``cdiv(length, G *
    page_size)`` turns of a ``lax.fori_loop``, each over one BLOCK of
    G pages laid one under another in a VMEM buffer; block b + 1 is
    in flight into the buffer's other half while block b is consumed.
    A slot at length L pays ceil(L / (G * page_size)) blocks; a slot
    at length 0 pays a grid step and a store of zeros. (The grid used
    to be (S, max_pages), one page a step, dead steps skipped by
    ``pl.when``: on the chip the skipped steps were half of the
    kernel's time at a tenth of the slots live; PERF.md, PR 35.)
  - G = min(max(1, 128 // page_size), max_pages), read off the shapes:
    8 pages of 16, so that a head's score row fills the 128 lanes and
    the online-softmax state (m, l, acc in VMEM scratch) is updated
    once a head a BLOCK, with a 128-deep ``p · kv`` contraction — not
    once a head a 16-key page.
  - inside the last live block the table's entries past the last live
    page are fetched with the rest (null-page entries by the table's
    contract) and SELECTED out with the tail of the partial page, once,
    in the buffer, before any head reads it: what they hold, NaN
    included, never matters.
  - one decode query per slot, so a head's scores are ONE row: the
    scores of a GROUP of heads (``_head_group``: all of them at a
    decode or verify step) lie one under another and take one softmax
    update together — 25 single-row heads fill four vregs where they
    would half-fill 25, and the lane reductions and ``exp`` run four
    times a block, not 25 (on the chip the live part of the decode
    kernel fell from 19 to 5 us a layer a slot at a context of 586;
    PERF.md, PR 35). Dot operands stay in the input dtype,
    accumulation is f32 via ``preferred_element_type`` (same dtype
    discipline as the training kernels). Decode attention is a prefix
    mask — the query IS position ``length - 1`` — so no causal triangle
    is needed.
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
covers both. The same walk as decode (``_walk_live_blocks``, the one
body of the three kernels: one program over the slot's row, as many
blocks as ``q_start + n_real`` keys fill), with C query rows per head
instead of one; same jnp gather fallback as CPU path and oracle.
``ragged_verify_attention`` is decode with W query rows a slot.
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


def _block_scale(scale_refs, pages, valid, page_size, lanes):
    """(block, lanes) f32 inline-dequant scales of one block of
    quantized pages: page g's rows by its own pair, lanes [0, D) by its
    key scale and lanes [D, 2 * D) by its value scale, the rows no
    consumed query may read (``valid`` false: a dead entry's scale may
    be anything) by zero; None for an unquantized pool (no scale
    refs). The (P,) per-page scale arrays
    ride the SAME scalar-prefetch path as the page table: the loop turn
    that consumes a block reads its pages' scales from SMEM and
    dequantizes the int8/fp8 block on its way out of the DMA buffer —
    the pool never materializes in float anywhere."""
    if not scale_refs:
        return None
    ks_ref, vs_ref = scale_refs
    block = len(pages) * page_size
    row = lax.broadcasted_iota(jnp.int32, (block, 1), 0)
    lane = lax.broadcasted_iota(jnp.int32, (1, lanes), 1)
    out = jnp.zeros((block, lanes), jnp.float32)
    for g, page in enumerate(pages):
        pair = jnp.where(lane < lanes // 2, ks_ref[page], vs_ref[page])
        out = jnp.where(row >= g * page_size, pair, out)
    return jnp.where(valid, out, 0.0)


def _head_group(heads, rows):
    """Heads a loop turn of the kernels takes together: as many as
    keep the turn's scores, one head's under another's, within 512
    rows (all of them for a decode or verify step, 5 of 25 at a
    64-row chunk, one from 256 rows on), and a divisor of ``heads``.
    Read off the shapes, like the block."""
    return _pa._largest_divisor(heads, max(1, 512 // rows))


def _init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _for_turns(m_ref, turn):
    """``turn(g)`` for every group of heads (``m_ref``: a row a
    group)."""
    if m_ref.shape[0] == 1:
        turn(0)
    else:
        lax.fori_loop(0, m_ref.shape[0], lambda g, c: (turn(g), c)[1], 0)


def _accumulate_block(q_ref, kv_ref, m_ref, l_ref, acc_ref, *, scale,
                      visible, block_scale):
    """One block's online-softmax update, a group of heads a loop turn
    and ONE update a group: the scores of the group's heads lie one
    under another, (group * rows, block), so that a decode step's
    25 single-row heads fill four vregs where they would half-fill 25,
    and the row maxima, the two ``exp`` and the row sums are taken
    once. ``kv_ref`` (H, block, 2D): the block's pages one under
    another, positions no consumed row may read already selected out;
    ``visible`` (group * rows, block): the per-row prefix mask;
    ``block_scale`` (block, 2 * D) f32 or None (unquantized pool)."""
    rows = q_ref.shape[2]
    group = q_ref.shape[1] // m_ref.shape[0]

    def tile(h):
        q = q_ref[0, h]                 # (rows, 2D), lanes [D, 2D) zero
        kv = kv_ref[h]                  # (block, 2D): keys | values
        if block_scale is not None:     # inline dequant
            q = q.astype(jnp.float32)
            kv = kv.astype(jnp.float32) * block_scale
        return q, kv

    def turn(g):
        heads = [g * group + u for u in range(group)]
        sc = jnp.concatenate([
            jnp.dot(q, kv.T, preferred_element_type=jnp.float32,
                    precision=lax.Precision.DEFAULT)
            for q, kv in map(tile, heads)], axis=0) * scale
        sc = jnp.where(visible, sc, _NEG_INF)
        m_prev = m_ref[g][:, None]      # (group * rows, 1)
        l_prev = l_ref[g][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)         # (group * rows, block) f32
        alpha = jnp.exp(m_prev - m_new)
        m_ref[g] = m_new[:, 0]
        l_ref[g] = (l_prev * alpha +
                    jnp.sum(p, axis=-1, keepdims=True))[:, 0]
        for u, h in enumerate(heads):
            _, kv = tile(h)
            mine = slice(u * rows, (u + 1) * rows)
            # [p·k | p·v]: the caller keeps lanes [D, 2D)
            acc_ref[h] = acc_ref[h] * alpha[mine] + jnp.dot(
                p[mine].astype(kv.dtype), kv,
                preferred_element_type=jnp.float32,
                precision=lax.Precision.DEFAULT)

    _for_turns(m_ref, turn)


def _finalize(o_ref, m_ref, l_ref, acc_ref):
    rows = o_ref.shape[2]
    group = o_ref.shape[1] // m_ref.shape[0]

    def turn(g):
        m = m_ref[g][:, None]
        l_safe = jnp.maximum(l_ref[g][:, None], 1e-30)
        # rows that never accumulated (a length-0 slot, a dead verify
        # slot, padded chunk rows past every accumulated block): m never
        # left _NEG_INF — emit exactly zero, the masked-row contract
        # shared with the training kernels (ops.pallas_attention).
        # Negated-compare form so a NaN running max (poisoned K/V page)
        # fails the dead-row test and PROPAGATES instead of being
        # silently zeroed — the serving engine's non-finite guard
        # depends on corruption staying visible in the output.
        # (the compare runs on the already-expanded f32 column:
        # Mosaic cannot reshape an i1 vector)
        row_ok = ~(m <= _NEG_INF / 2)
        for u in range(group):
            h, mine = g * group + u, slice(u * rows, (u + 1) * rows)
            o_ref[0, h] = jnp.where(row_ok[mine], acc_ref[h] / l_safe[mine],
                                    0.0).astype(o_ref.dtype)

    _for_turns(m_ref, turn)


def _walk_live_blocks(refs, page_at, live_keys, visible, *, scale,
                      page_size, n_pages, rep=1):
    """The body the three kernels share: one program walks ONE page-
    table row, and only as far as it is live. ``page_at(j)`` reads the
    row's j-th entry from SMEM, ``live_keys`` (traced scalar) is the
    count of key positions some consumed query row may read, and
    ``visible(pos, row)`` is the kernel's own prefix mask over a
    (1, block) row of key positions and a (n, 1) column of query-row
    indices.

    A block is ``per_block`` pages one under another in a VMEM buffer,
    a lane-full row of keys where the page size allows; the program's
    own DMAs fetch block b + 1 into the buffer's other half while
    block b is consumed. No block past the last live one is fetched or
    visited; inside the last live block the entries past the last live
    page (null-page entries by the table's contract) are fetched with
    the rest and selected out like the tail of a partial page. Zero
    live keys: zero turns and exact zeros out.

    ``rep`` (static) is the count of query heads that read one
    key-value head (grouped queries): the pool has H key-value heads
    and the queries arrive with a head's ``rep`` query heads' rows one
    under another, (H, rep * rows, lanes), so the stack of rows under
    one key-value head's block is its query heads' rows and every dot
    stays a head's. ``visible`` is asked by a row's index within its
    own query head. At ``rep`` 1 nothing here differs from the plain
    multi-head walk."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    *scale_refs, q_ref, pool_ref, o_ref, m_ref, l_ref, acc_ref, \
        buf_ref, sem_ref = refs
    heads, rows = q_ref.shape[1], q_ref.shape[2]
    block = buf_ref.shape[2]
    per_block = block // page_size
    live_keys = jnp.minimum(live_keys, n_pages * page_size)
    n_blocks = pl.cdiv(live_keys, block)
    # the query row of each score row of a loop turn's stack of heads
    row = lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    if rep > 1:
        row = lax.rem(row, rows // rep)
    row = jnp.concatenate([row] * (heads // m_ref.shape[0]), axis=0)

    def pages_of(b):
        # the table's last block may be short of ``per_block`` entries
        return [page_at(jnp.minimum(b * per_block + g, n_pages - 1))
                for g in range(per_block)]

    def fetch(b, half):
        return [pltpu.make_async_copy(
            pool_ref.at[page],
            buf_ref.at[half, :, pl.ds(g * page_size, page_size)],
            sem_ref.at[half]) for g, page in enumerate(pages_of(b))]

    def turn(b, carry):
        half = lax.rem(b, 2)

        @pl.when(b + 1 < n_blocks)
        def _next():
            for dma in fetch(b + 1, 1 - half):
                dma.start()

        for dma in fetch(b, half):
            dma.wait()
        valid = (b * block + lax.broadcasted_iota(
            jnp.int32, (block, 1), 0)) < live_keys
        block_scale = _block_scale(scale_refs, pages_of(b), valid,
                                   page_size, buf_ref.shape[-1])

        # SELECT masked rows out of the tile (not just zero-weight
        # them): a freed page can be reused carrying non-finite garbage
        # in positions past the new owner's length, and 0 * NaN = NaN
        # would leak it through the weighted sum (and, keys and values
        # sharing the tile, through the padded query's zero lanes) —
        # masked reads must never matter, even poisoned ones (a
        # quantized pool's NaN channel is the page SCALE — the select
        # covers it the same way). Only the last live block holds such
        # positions, so the select runs once a walk, in the buffer,
        # before any head reads it
        @pl.when((b + 1) * block > live_keys)
        def _tail():
            for h in range(heads):
                buf_ref[half, h] = jnp.where(
                    valid, buf_ref[half, h], 0).astype(buf_ref.dtype)

        pos = b * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
        _accumulate_block(
            q_ref, buf_ref.at[half], m_ref, l_ref, acc_ref, scale=scale,
            visible=visible(pos, row), block_scale=block_scale)
        return carry

    @pl.when(n_blocks == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n_blocks > 0)
    def _live():
        _init_state(m_ref, l_ref, acc_ref)
        for dma in fetch(0, 0):
            dma.start()
        lax.fori_loop(0, n_blocks, turn, 0)
        _finalize(o_ref, m_ref, l_ref, acc_ref)


def _block_pages(page_size, n_pages):
    """Pages a block: as many as fill the 128 lanes of a score row with
    keys (8 at a page of 16), no more than the table has. Read off the
    shapes; nothing else chooses it."""
    return min(max(1, 128 // page_size), n_pages)


def _paged_vmem_limit(H, rows, lanes, block, stack, q_itemsize,
                      kv_itemsize):
    """Scoped-VMEM request for one paged program: the query and output
    blocks double-buffered by Pallas, the f32 accumulator, m and l, the
    two halves of the DMA buffer, eight (stack, block) f32 tiles for a
    loop turn's scores and probabilities and 4 MiB of slack. Mosaic's
    default scoped limit is 16 MiB (the chunk-prefill kernel at 25
    heads needs more from 512 rows on); the request is a cap, not an
    allocation."""
    rows8 = -(-rows // 8) * 8
    need = H * rows8 * lanes * (4 * q_itemsize + 4) \
        + 2 * 8 * H * rows8 * 4 \
        + 2 * H * block * lanes * kv_itemsize \
        + 8 * max(stack, 8) * max(block, 128) * 4 + (4 << 20)
    return min(max(need, 16 << 20), 100 << 20)


def _paged_call(kernel, name, scale, prefetch, q4, kv_pool, interpret):
    """The one ``pallas_call`` shape of the three kernels: a program a
    page-table row (grid ``(q4.shape[0],)``: a slot, or the one slot of
    a chunk), ``prefetch`` int32 page table (first) / lengths (and f32
    page scales) in SMEM, queries ``q4`` (S, Hq, rows, D) a row's block at
    a time, the pool left in HBM as it lies for the program's own DMAs
    (so the kernel never sees a gather, and no grid step is spent on a
    page nobody holds), online-softmax state and the two-block DMA
    buffer in VMEM scratch. Against a pool of H < Hq key-value heads a
    head's Hq / H query heads' rows go one under another, (S, H, rep *
    rows, D), and come back apart. Returns (S, Hq, rows, D)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q_shape = q4.shape
    D = q4.shape[-1]
    rep = q4.shape[1] // kv_pool.shape[1]
    if rep > 1:
        q4 = q4.reshape(q_shape[0], kv_pool.shape[1], rep * q_shape[2], D)
    q4 = _pad_lanes(q4)
    S, H, rows, lanes = q4.shape
    page_size = kv_pool.shape[2]
    n_pages = prefetch[0].shape[-1]
    block = _block_pages(page_size, n_pages) * page_size
    group = _head_group(H, rows)

    def q_map(s, *_):
        return (s, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, rows, lanes), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, H, rows, lanes), q_map),
        scratch_shapes=[
            pltpu.VMEM((H // group, group * rows), jnp.float32),    # m
            pltpu.VMEM((H // group, group * rows), jnp.float32),    # l
            pltpu.VMEM((H, rows, lanes), jnp.float32),  # acc
            pltpu.VMEM((2, H, block, lanes), kv_pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    out = pl.pallas_call(
        functools.partial(kernel, scale=scale, page_size=page_size,
                          n_pages=n_pages, rep=rep),
        name=name,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q4.shape, q4.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_paged_vmem_limit(
                H, rows, lanes, block, group * rows, q4.dtype.itemsize,
                kv_pool.dtype.itemsize)),
        interpret=interpret,
    )(*prefetch, q4, kv_pool)
    return out[..., D:].reshape(q_shape)


def _scale_prefetch(k_scale, v_scale):
    """Per-page scales join the scalar-prefetch set (quantized pools)."""
    if k_scale is None:
        return ()
    return (k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))


def _ragged_kernel(pt_ref, ln_ref, *refs, **static):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    length = ln_ref[s]                          # live tokens this slot
    # decode attention is a prefix mask: the query IS position
    # length - 1
    _walk_live_blocks(refs, lambda j: pt_ref[s, j], length,
                      lambda pos, row: pos < length, **static)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_pallas(q, kv_pool, page_table, lengths, scale, interpret,
                   k_scale=None, v_scale=None):
    """q: (S, H, D); kv_pool: (P, H, page_size, 2D); page_table:
    (S, max_pages) int32; lengths: (S,) int32; k_scale/v_scale: (P,)
    f32 per-page scales of a quantized pool, or None. Returns
    (S, H, D)."""
    quant = k_scale is not None
    out = _paged_call(
        _ragged_kernel, "mxtpu_ragged_decode" + ("_q" if quant else ""),
        scale,
        (page_table.astype(jnp.int32), lengths.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q[:, :, None, :], kv_pool, interpret)
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


def _per_query_head(k, v, heads, axis):
    """Key and value windows repeated to one a QUERY head (grouped
    queries: query head j reads key-value head j // rep); as they are
    at equal head counts."""
    rep = heads // k.shape[axis]
    if rep == 1:
        return k, v
    return jnp.repeat(k, rep, axis=axis), jnp.repeat(v, rep, axis=axis)


def _reference_core(q, k, v, lengths, sc):
    """Masked online-softmax attention over a pre-gathered window.
    q: (S, H, D); k/v: (S, H, K, D). Factored out so the verify
    reference can reuse ONE gather across its W query rows while each
    row runs bitwise the same computation as the decode reference."""
    S, H, D = q.shape
    K = k.shape[2]
    k, v = _per_query_head(k, v, H, 1)
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

def _ragged_prefill_kernel(pr_ref, qi_ref, *refs, **static):
    start = qi_ref[0]                # first query's absolute position
    n_real = qi_ref[1]               # live queries in the chunk

    def visible(pos, row):
        # per-query prefix mask: query i (absolute pos start + i) sees
        # keys [0, start + i] — the paged prefix AND the causal
        # intra-chunk part in one predicate (the chunk's own K/V is
        # already scattered into these pages)
        return pos <= start + row

    # blocks whose first key position is past the last real query's
    # position contribute nothing to any live row and are not walked;
    # positions past that query's view are masked for EVERY row, so
    # they are selected out of the tile and reused-page garbage
    # (possibly non-finite) cannot leak through 0-weight terms. Every
    # live query attends at least position 0, so only rows that saw no
    # block at all (padded rows past every walked block) stay at
    # _NEG_INF and emit zero
    _walk_live_blocks(refs, lambda j: pr_ref[j], start + n_real, visible,
                      **static)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_prefill_pallas(q, kv_pool, page_row, qinfo, scale, interpret,
                           k_scale=None, v_scale=None):
    """q: (C, H, D) chunk queries of ONE slot; kv_pool: (P, H, ps, 2D);
    page_row: (max_pages,) int32; qinfo: (2,) int32 = [q_start,
    n_real]; k_scale/v_scale: (P,) f32 or None. Returns (C, H, D)."""
    quant = k_scale is not None
    out = _paged_call(
        _ragged_prefill_kernel,
        "mxtpu_ragged_prefill" + ("_q" if quant else ""), scale,
        (page_row.astype(jnp.int32), qinfo.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q.transpose(1, 0, 2)[None], kv_pool,            # (1, H, C, D)
        interpret)
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
        return g.reshape(g.shape[0], K, D)

    k, v = _split(kv_pool[page_row])
    k, v = _per_query_head(window(k, k_scale), window(v, v_scale), H, 0)
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

def _ragged_verify_kernel(pt_ref, ln_ref, dl_ref, *refs, **static):
    """Decode kernel generalized to W queries per slot: query
    row r of slot s sits at absolute position ``lengths[s] - 1 + r``
    (row 0 IS the ordinary decode query) and attends keys
    ``[0, lengths[s] - 1 + r]`` — the slot's paged prefix plus the
    causal intra-window part in one predicate, exactly the
    chunked-prefill masking with a per-SLOT dynamic start. Same walk of
    the slot's live blocks, same NaN propagation / masked-tile-select
    contract as the decode kernel."""
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    length = ln_ref[s]               # keys visible to query row 0
    dl = dl_ref[s]                   # slot's REAL draft count this step

    def visible(pos, row):
        # row r (absolute position length - 1 + r) sees keys
        # [0, length - 1 + r]: prefix + causal intra-window in one
        # predicate
        return pos < length + row

    # the last CONSUMED row (row dl — accepted drafts + the
    # bonus/correction) sees keys up to length + dl - 1, and that is
    # also the last position freshly written this step; blocks wholly
    # past it (and every block of a dead slot) are not walked.
    # Positions no CONSUMED row may ever see are selected out of the
    # tile so reused-page garbage (possibly non-finite) cannot leak
    # through 0-weight terms. The bound must be the slot's real written
    # extent length + dl, NOT length + window - 1: when a slot drafts
    # fewer than window - 1 tokens, positions in
    # [length + dl, length + window - 1) are UNWRITTEN — a recycled
    # page can carry a quarantined slot's non-finite K/V there, and
    # 0 * NaN = NaN would poison every consumed row, falsely
    # quarantining a healthy slot (same rule as the chunked-prefill
    # kernel's n_real bound). Rows past dl may now read fewer
    # positions than their nominal visibility; their output is
    # discarded by the engine and never feeds acceptance (the op's
    # documented PRECONDITION).
    _walk_live_blocks(refs, lambda j: pt_ref[s, j],
                      jnp.where(length > 0, length + dl, 0), visible,
                      **static)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def _ragged_verify_pallas(q, kv_pool, page_table, lengths, draft_len,
                          scale, interpret, k_scale=None, v_scale=None):
    """q: (S, W, H, D) — W verify queries per slot; kv_pool:
    (P, H, page_size, 2D); page_table: (S, max_pages) int32; lengths:
    (S,) int32 = keys visible to query row 0 (0 = dead slot);
    draft_len: (S,) int32 = the slot's real draft count (index of its
    last consumed row, bounding the freshly-written extent);
    k_scale/v_scale: (P,) f32 or None. Returns (S, W, H, D)."""
    quant = k_scale is not None
    out = _paged_call(
        _ragged_verify_kernel,
        "mxtpu_ragged_verify" + ("_q" if quant else ""), scale,
        (page_table.astype(jnp.int32), lengths.astype(jnp.int32),
         draft_len.astype(jnp.int32),
         *_scale_prefetch(k_scale, v_scale)),
        q.transpose(0, 2, 1, 3), kv_pool,               # (S, H, W, D)
        interpret)
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

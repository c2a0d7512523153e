"""Ragged paged-KV decode attention tests.

Reference test idiom §4.2 (cross-backend consistency): the Pallas
kernel runs in INTERPRET mode on CPU and must match (a) the pure-jnp
gather reference and (b) the repo's existing dense masked SDPA — the
same masked-row contract as ops.pallas_attention, now over a paged
pool with arbitrary (shuffled) page tables."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.ragged_attention import (
    _ragged_pallas, _ragged_prefill_pallas, _ragged_verify_pallas,
    ragged_attention_reference, ragged_paged_attention,
    ragged_prefill_attention, ragged_prefill_reference,
    ragged_verify_attention, ragged_verify_reference)


def _fuse(k_pool, v_pool):
    """The pool as the program holds it: a head's keys | values side by
    side on the last axis. The cases below build the two halves apart
    (their oracles read them apart) and fuse at the call."""
    return jnp.concatenate([jnp.asarray(k_pool), jnp.asarray(v_pool)], -1)


def _make_case(rng, S, H, D, page_size, max_pages, lengths,
               num_pages=None, dtype=np.float32):
    """Random pools + a SHUFFLED page table (non-identity page order —
    the thing a paged cache must get right) for the given lengths."""
    lengths = np.asarray(lengths, np.int32)
    n_live = [-(-int(l) // page_size) for l in lengths]
    if num_pages is None:
        num_pages = 1 + sum(n_live)
    q = rng.randn(S, H, D).astype(dtype)
    k_pool = rng.randn(num_pages, H, page_size, D).astype(dtype)
    v_pool = rng.randn(num_pages, H, page_size, D).astype(dtype)
    perm = rng.permutation(np.arange(1, num_pages))  # page 0 = null
    pt = np.zeros((S, max_pages), np.int32)
    used = 0
    for s in range(S):
        pt[s, :n_live[s]] = perm[used:used + n_live[s]]
        used += n_live[s]
    return (jnp.asarray(q), jnp.asarray(k_pool), jnp.asarray(v_pool),
            jnp.asarray(pt), jnp.asarray(lengths))


def _dense_sdpa_oracle(q, k_pool, v_pool, pt, lengths):
    """Gather each slot's pages into a dense (S, K, H, D) window and run
    the repo's dense masked SDPA — the equivalence target the ISSUE
    names (the serving kernel must agree with the training-side
    attention math)."""
    from incubator_mxnet_tpu.ops.attention import _sdpa_dense
    S, H, D = q.shape
    ps = k_pool.shape[2]
    K = pt.shape[1] * ps
    k = jnp.moveaxis(k_pool[pt], 2, 1).reshape(S, H, K, D)
    v = jnp.moveaxis(v_pool[pt], 2, 1).reshape(S, H, K, D)
    mask = (jnp.arange(K)[None, :] <
            lengths[:, None])[:, None, None, :]          # (S,1,1,K)
    # _sdpa_dense wants (B, T, H, D); one query row per slot
    out = _sdpa_dense(q[:, None], k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), mask, D ** -0.5)
    return out[:, 0]                                     # (S, H, D)


LENGTH_CASES = [
    # the ISSUE's required row lengths: {0, 1, page_size, page_size+1,
    # Tmax} and mixed occupancy, page boundaries included
    [0, 1, 8, 9, 32],
    [0, 0, 0, 0, 0],        # empty batch: all rows masked
    [32, 32, 32, 32, 32],   # full batch at Tmax
    [7, 8, 9, 15, 16],      # straddling page boundaries
]


@pytest.mark.parametrize("lengths", LENGTH_CASES)
@pytest.mark.parametrize("impl", ["pallas_interpret", "jnp"])
def test_ragged_matches_dense_sdpa(lengths, impl):
    rng = np.random.RandomState(0)
    S, H, D, ps = len(lengths), 3, 8, 8
    max_pages = 4                                       # Tmax = 32
    q, kp, vp, pt, ln = _make_case(rng, S, H, D, ps, max_pages, lengths)
    if impl == "pallas_interpret":
        got = _ragged_pallas(q, _fuse(kp, vp), pt, ln, D ** -0.5, True)
    else:
        got = ragged_attention_reference(q, _fuse(kp, vp), pt, ln)
    ref = _dense_sdpa_oracle(q, kp, vp, pt, ln)
    # fully-masked rows: exactly zero (kernel contract); _sdpa_dense
    # emits the uniform mean of V there, so compare only live rows
    # against the oracle and pin dead rows to zero explicitly
    got_np, ref_np = np.asarray(got), np.asarray(ref)
    for s, l in enumerate(lengths):
        if l == 0:
            np.testing.assert_array_equal(got_np[s], 0.0)
        else:
            np.testing.assert_allclose(got_np[s], ref_np[s],
                                       rtol=2e-5, atol=2e-5)


def test_pallas_interpret_matches_jnp_reference_exhaustive():
    """Kernel vs jnp reference agree everywhere (both contracts include
    the zero-row rule, so no row exclusions), across odd page sizes and
    a pool with unused pages."""
    rng = np.random.RandomState(1)
    for ps, lengths in [(4, [0, 1, 4, 5, 13]), (16, [16, 1, 0, 33, 48])]:
        max_pages = -(-max(lengths) // ps) if max(lengths) else 1
        q, kp, vp, pt, ln = _make_case(rng, len(lengths), 2, 16, ps,
                                       max_pages, lengths,
                                       num_pages=64)
        a = _ragged_pallas(q, _fuse(kp, vp), pt, ln, 16 ** -0.5, True)
        b = ragged_attention_reference(q, _fuse(kp, vp), pt, ln)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_null_page_contents_never_leak():
    """Dead page-table entries point at page 0; poisoning page 0 with
    huge values must not change any output — the null-page invariant
    the whole serve/ design rests on."""
    rng = np.random.RandomState(2)
    ps = 8
    q, kp, vp, pt, ln = _make_case(rng, 4, 2, 8, ps, 4, [0, 3, 8, 20])
    base = ragged_attention_reference(q, _fuse(kp, vp), pt, ln)
    kp2 = kp.at[0].set(1e9)
    vp2 = vp.at[0].set(-1e9)
    poisoned = ragged_attention_reference(q, _fuse(kp2, vp2), pt, ln)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))
    a = _ragged_pallas(q, _fuse(kp2, vp2), pt, ln, 8 ** -0.5, True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(base),
                               rtol=2e-5, atol=2e-5)


def test_partial_tail_page_masked():
    """Tokens past ``length`` inside the last live page must not attend:
    rewriting the tail of that page changes nothing."""
    rng = np.random.RandomState(3)
    ps = 8
    q, kp, vp, pt, ln = _make_case(rng, 2, 2, 8, ps, 2, [5, 11])
    base = np.asarray(_ragged_pallas(q, _fuse(kp, vp), pt, ln, 8 ** -0.5,
                                     True))
    # slot 0's only page is pt[0,0]; positions 5..7 are dead
    page = int(pt[0, 0])
    kp2 = kp.at[page, :, 5:, :].set(123.0)
    vp2 = vp.at[page, :, 5:, :].set(-321.0)
    got = np.asarray(_ragged_pallas(q, _fuse(kp2, vp2), pt, ln, 8 ** -0.5,
                                    True))
    np.testing.assert_array_equal(base, got)


def test_dispatcher_and_dtype():
    """The public dispatcher runs the jnp path on the CPU backend (and
    the kernel under MXTPU_FLASH_INTERPRET=1 — parity covered above);
    bf16 inputs accumulate in f32 and track the f32 result."""
    rng = np.random.RandomState(4)
    q, kp, vp, pt, ln = _make_case(rng, 3, 2, 8, 8, 3, [1, 9, 24])
    out = ragged_paged_attention(q, _fuse(kp, vp), pt, ln)
    ref = ragged_attention_reference(q, _fuse(kp, vp), pt, ln)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)
    b16 = ragged_paged_attention(q.astype(jnp.bfloat16),
                                 _fuse(kp, vp).astype(jnp.bfloat16), pt, ln)
    assert b16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(b16, np.float32),
                               np.asarray(ref), rtol=0.05, atol=0.05)


# --------------------------------------------------------------------- #
# prefill over a paged prefix (the chunked-prefill variant)
# --------------------------------------------------------------------- #

def _make_prefill_case(rng, H, D, ps, T, pages, num_pages=16,
                       dtype=np.float32):
    """A single slot's paged K/V for a T-token prompt laid out through
    the (shuffled) ``pages`` list, plus the dense per-token rows for the
    numpy oracle. The null page is poisoned — its contents must never
    matter."""
    kp = np.zeros((num_pages, H, ps, D), dtype)
    vp = np.zeros((num_pages, H, ps, D), dtype)
    tok_k = rng.randn(T, H, D).astype(dtype)
    tok_v = rng.randn(T, H, D).astype(dtype)
    for t in range(T):
        kp[pages[t // ps], :, t % ps, :] = tok_k[t]
        vp[pages[t // ps], :, t % ps, :] = tok_v[t]
    kp[0] = 1e9
    vp[0] = -1e9
    return kp, vp, tok_k, tok_v


def _prefill_oracle(q, tok_k, tok_v, q_start, n_real):
    """Per-query dense softmax over keys [0, q_start + i] — plain numpy,
    independent of every jnp code path."""
    C, H, D = q.shape
    out = np.zeros((C, H, D), np.float32)
    for i in range(n_real):
        L = q_start + i + 1
        for h in range(H):
            s = tok_k[:L, h].astype(np.float32) @ \
                q[i, h].astype(np.float32) * (D ** -0.5)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[i, h] = p @ tok_v[:L, h].astype(np.float32)
    return out


@pytest.mark.parametrize("q_start,C", [
    (0, 8),        # first chunk, page-aligned
    (8, 8),        # chunk starting at a page boundary
    (13, 8),       # chunk starting mid-page (partial-copy resume)
    (16, 5),       # odd tail chunk
])
@pytest.mark.parametrize("impl", ["pallas_interpret", "jnp"])
def test_prefill_matches_dense_causal_oracle(q_start, C, impl):
    """Chunk queries at absolute positions q_start+i over a shuffled
    page table must match the dense per-query causal softmax, for both
    the kernel (interpret mode) and the jnp gather reference."""
    rng = np.random.RandomState(10)
    H, D, ps = 3, 16, 8
    T = q_start + C
    pages = [5, 2, 7][:-(-T // ps)]
    row = np.zeros((4,), np.int32)
    row[:len(pages)] = pages
    kp, vp, tok_k, tok_v = _make_prefill_case(rng, H, D, ps, T, pages)
    q = rng.randn(C, H, D).astype(np.float32)
    if impl == "pallas_interpret":
        got = _ragged_prefill_pallas(
            jnp.asarray(q), _fuse(kp, vp),
            jnp.asarray(row), jnp.asarray([q_start, C], jnp.int32),
            D ** -0.5, True)
    else:
        got = ragged_prefill_reference(
            jnp.asarray(q), _fuse(kp, vp),
            jnp.asarray(row), np.int32(q_start))
    ref = _prefill_oracle(q, tok_k, tok_v, q_start, C)
    np.testing.assert_allclose(np.asarray(got), ref, rtol=2e-5,
                               atol=2e-5)


def test_prefill_chunk_composition_matches_single_shot():
    """Processing a prompt as {1-page, 2-page, odd-tail} chunks must
    reproduce the single-shot full-prompt call row for row — the
    composition property chunked prefill rests on (each chunk sees
    earlier chunks only through the pages they populated)."""
    rng = np.random.RandomState(11)
    H, D, ps = 2, 16, 8
    T = 21                                   # 2 full pages + odd tail
    pages = [3, 9, 6]
    row = np.zeros((4,), np.int32)
    row[:3] = pages
    kp, vp, tok_k, tok_v = _make_prefill_case(rng, H, D, ps, T, pages)
    q = rng.randn(T, H, D).astype(np.float32)
    full = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(row), np.int32(0)))
    for splits in ([8, 8, 5], [16, 5], [8, 13]):
        start = 0
        rows = []
        for n in splits:
            rows.append(np.asarray(ragged_prefill_reference(
                jnp.asarray(q[start:start + n]), _fuse(kp, vp),
                jnp.asarray(row), np.int32(start))))
            start += n
        np.testing.assert_allclose(np.concatenate(rows), full,
                                   rtol=2e-5, atol=2e-5)


def test_prefill_padded_rows_do_not_affect_real_rows():
    """The engine pads chunks to pow2-page buckets: the padded trailing
    queries must not change any real row, for both implementations
    (real rows compare against the unpadded call)."""
    rng = np.random.RandomState(12)
    H, D, ps = 2, 8, 8
    T, n_real, Cpad = 19, 6, 16              # chunk [13, 19) padded to 16
    q_start = 13
    pages = [4, 1, 8]
    row = np.zeros((3,), np.int32)
    row[:3] = pages
    kp, vp, _, _ = _make_prefill_case(rng, H, D, ps, T, pages,
                                      num_pages=12)
    q = rng.randn(Cpad, H, D).astype(np.float32)
    exact_ref = np.asarray(ragged_prefill_reference(
        jnp.asarray(q[:n_real]), _fuse(kp, vp),
        jnp.asarray(row), np.int32(q_start)))
    padded_ref = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(row), np.int32(q_start)))
    np.testing.assert_array_equal(padded_ref[:n_real], exact_ref)
    padded_pal = np.asarray(_ragged_prefill_pallas(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(row), jnp.asarray([q_start, n_real], jnp.int32),
        D ** -0.5, True))
    np.testing.assert_allclose(padded_pal[:n_real], exact_ref,
                               rtol=2e-5, atol=2e-5)


def test_partial_chunk_unwritten_tail_nan_does_not_poison_live_rows():
    """Regression (chaos corrupt_page under speculation): a PARTIAL
    final chunk (n_real < Cpad) attends a page whose offsets past the
    chunk's written extent still hold a previous owner's NON-FINITE
    K/V — a quarantined slot's pages are freed mid-poison and recycled
    (speculation widens the poison: the verify step writes NaN K/V
    into the whole draft window before quarantine). Masked 0-weight
    terms must SELECT those positions out of V (0 * NaN = NaN
    otherwise) bounded at q_start + n_real — NOT q_start + Cpad, which
    left the unwritten gap [q_start + n_real, q_start + Cpad) leaking
    NaN into every live row. Both implementations."""
    rng = np.random.RandomState(21)
    H, D, ps = 2, 8, 8
    T, n_real, Cpad = 19, 3, 8               # chunk [16, 19) padded to 8
    q_start = 16
    pages = [4, 1, 8]
    # the slot's row carries its WORST-CASE reservation: a 4th page is
    # mapped but entirely unwritten (positions 24..31)
    row = np.zeros((4,), np.int32)
    row[:3] = pages
    row[3] = 9
    kp, vp, _, _ = _make_prefill_case(rng, H, D, ps, T, pages,
                                      num_pages=12)
    q = rng.randn(Cpad, H, D).astype(np.float32)
    clean = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(row), np.int32(q_start), n_real=np.int32(n_real)))
    # poison the unwritten tail of the chunk's own page AND the whole
    # reserved (recycled) next page — positions >= q_start + n_real = 19
    kp2, vp2 = kp.copy(), vp.copy()
    pg, off = pages[T // ps], T % ps
    kp2[pg, :, off:], vp2[pg, :, off:] = np.nan, np.nan
    kp2[9], vp2[9] = np.nan, np.nan
    dirty = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(row), np.int32(q_start), n_real=np.int32(n_real)))
    assert np.isfinite(dirty[:n_real]).all(), \
        "unwritten-tail NaN leaked into live chunk rows (reference)"
    np.testing.assert_array_equal(dirty[:n_real], clean[:n_real])
    pal = np.asarray(_ragged_prefill_pallas(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(row), jnp.asarray([q_start, n_real], jnp.int32),
        D ** -0.5, True))
    assert np.isfinite(pal[:n_real]).all(), \
        "unwritten-tail NaN leaked into live chunk rows (kernel)"
    np.testing.assert_allclose(pal[:n_real], clean[:n_real],
                               rtol=2e-5, atol=2e-5)


def test_prefill_null_page_contents_never_leak():
    """Dead page-row entries (and padded-token scatter targets) point at
    page 0 — repoisoning it must not change any real output row."""
    rng = np.random.RandomState(13)
    H, D, ps = 2, 8, 8
    T = 11
    pages = [7, 2]
    row = np.zeros((4,), np.int32)           # entries 2, 3 are dead
    row[:2] = pages
    kp, vp, _, _ = _make_prefill_case(rng, H, D, ps, T, pages)
    q = rng.randn(T, H, D).astype(np.float32)
    base = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(row), np.int32(0)))
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[0], vp2[0] = -3e8, 3e8               # different poison
    again = np.asarray(ragged_prefill_reference(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(row), np.int32(0)))
    np.testing.assert_array_equal(base, again)
    pal = np.asarray(_ragged_prefill_pallas(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(row), jnp.asarray([0, T], jnp.int32),
        D ** -0.5, True))
    np.testing.assert_allclose(pal, base, rtol=2e-5, atol=2e-5)


def test_prefill_dispatcher_and_dtype():
    """The public dispatcher runs the jnp path on the CPU backend; bf16
    inputs keep f32 accumulation and track the f32 result."""
    rng = np.random.RandomState(14)
    H, D, ps = 2, 8, 8
    T = 13
    pages = [5, 3]
    row = np.zeros((2,), np.int32)
    row[:2] = pages
    kp, vp, tok_k, tok_v = _make_prefill_case(rng, H, D, ps, T, pages)
    q = rng.randn(T, H, D).astype(np.float32)
    out = ragged_prefill_attention(jnp.asarray(q), _fuse(kp, vp),
                                   jnp.asarray(row), np.int32(0))
    ref = _prefill_oracle(q, tok_k, tok_v, 0, T)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                               atol=2e-5)
    b16 = ragged_prefill_attention(
        jnp.asarray(q, jnp.bfloat16), _fuse(kp, vp).astype(jnp.bfloat16),
        jnp.asarray(row), np.int32(0))
    assert b16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(b16, np.float32), ref,
                               rtol=0.06, atol=0.06)


# --------------------------------------------------------------------- #
# multi-query verify over a paged prefix (the speculative-decoding
# draft-then-verify variant)
# --------------------------------------------------------------------- #

def _make_verify_case(rng, H, D, ps, L, W, pages, num_pages=16,
                      dtype=np.float32):
    """One slot's paged K/V populated through the L + W - 1 positions a
    verify window over ``lengths = L`` may read (row r sees keys
    [0, L - 1 + r]); the null page is poisoned — its contents must
    never matter. Returns the pool plus the dense per-position rows for
    the numpy oracle."""
    T = L + W - 1
    kp = np.zeros((num_pages, H, ps, D), dtype)
    vp = np.zeros((num_pages, H, ps, D), dtype)
    tok_k = rng.randn(T, H, D).astype(dtype)
    tok_v = rng.randn(T, H, D).astype(dtype)
    for t in range(T):
        kp[pages[t // ps], :, t % ps, :] = tok_k[t]
        vp[pages[t // ps], :, t % ps, :] = tok_v[t]
    kp[0] = 1e9
    vp[0] = -1e9
    return kp, vp, tok_k, tok_v


def _verify_oracle(q, tok_k, tok_v, L):
    """Dense causal oracle for ONE slot's verify window: row r softmaxes
    over keys [0, L + r) — plain numpy, independent of every jnp code
    path."""
    W, H, D = q.shape
    out = np.zeros((W, H, D), np.float32)
    for r in range(W):
        n = L + r
        for h in range(H):
            s = tok_k[:n, h].astype(np.float32) @ \
                q[r, h].astype(np.float32) * (D ** -0.5)
            p = np.exp(s - s.max())
            p /= p.sum()
            out[r, h] = p @ tok_v[:n, h].astype(np.float32)
    return out


@pytest.mark.parametrize("L,W", [
    (1, 4),        # fresh slot: row 0 sees only the just-written token
    (8, 3),        # row 0 at a page boundary, window spills into page 2
    (13, 4),       # mid-page window crossing into the next page
    (6, 1),        # W=1: plain decode
])
@pytest.mark.parametrize("impl", ["pallas_interpret", "jnp"])
def test_verify_matches_dense_causal_oracle(L, W, impl):
    """Each verify row r (absolute position L - 1 + r) must match the
    dense causal softmax over its visible prefix — kernel (interpret
    mode) and jnp reference alike, over a shuffled page table."""
    rng = np.random.RandomState(20)
    H, D, ps = 3, 16, 8
    pages = [5, 2, 7][:-(-(L + W - 1) // ps)]
    pt = np.zeros((1, 4), np.int32)
    pt[0, :len(pages)] = pages
    kp, vp, tok_k, tok_v = _make_verify_case(rng, H, D, ps, L, W, pages)
    q = rng.randn(1, W, H, D).astype(np.float32)
    if impl == "pallas_interpret":
        got = _ragged_verify_pallas(
            jnp.asarray(q), _fuse(kp, vp),
            jnp.asarray(pt), jnp.asarray([L], jnp.int32),
            jnp.asarray([W - 1], jnp.int32), D ** -0.5, True)
    else:
        got = ragged_verify_reference(
            jnp.asarray(q), _fuse(kp, vp),
            jnp.asarray(pt), jnp.asarray([L], jnp.int32))
    ref = _verify_oracle(q[0], tok_k, tok_v, L)
    np.testing.assert_allclose(np.asarray(got)[0], ref, rtol=2e-5,
                               atol=2e-5)


def test_verify_w1_matches_decode_reference_bitwise():
    """A 1-wide verify window IS the decode step: the reference path
    must reproduce ``ragged_attention_reference`` BITWISE (the greedy
    speculative-vs-sequential token parity rests on this), and the
    kernel must agree numerically."""
    rng = np.random.RandomState(21)
    lengths = [0, 1, 8, 9, 24]
    q, kp, vp, pt, ln = _make_case(rng, len(lengths), 2, 16, 8, 3,
                                   lengths)
    dec = np.asarray(ragged_attention_reference(q, _fuse(kp, vp), pt, ln))
    ver = np.asarray(ragged_verify_reference(q[:, None], _fuse(kp, vp), pt,
                                             ln))
    np.testing.assert_array_equal(ver[:, 0], dec)
    pal = np.asarray(_ragged_verify_pallas(
        q[:, None], _fuse(kp, vp), pt, ln,
        jnp.zeros((len(lengths),), jnp.int32), 16 ** -0.5, True))
    for s, l in enumerate(lengths):      # dead rows: exactly zero
        if l == 0:
            np.testing.assert_array_equal(pal[s], 0.0)
    np.testing.assert_allclose(pal[:, 0], dec, rtol=2e-5, atol=2e-5)


def test_verify_pallas_matches_jnp_reference_mixed_slots():
    """Kernel vs jnp reference over a mixed batch — dead slots, ragged
    lengths, shuffled pages, window widths past page boundaries — agree
    everywhere (both contracts zero dead rows)."""
    rng = np.random.RandomState(22)
    S, W, H, D, ps, max_pages = 5, 4, 2, 16, 8, 4
    lengths = np.asarray([0, 1, 8, 13, 29], np.int32)
    # populate FULL pools so every window position holds data
    num_pages = 32
    q = rng.randn(S, W, H, D).astype(np.float32)
    kp = rng.randn(num_pages, H, ps, D).astype(np.float32)
    vp = rng.randn(num_pages, H, ps, D).astype(np.float32)
    perm = rng.permutation(np.arange(1, num_pages))
    pt = np.zeros((S, max_pages), np.int32)
    used = 0
    for s in range(S):
        n_live = -(-(int(lengths[s]) + W - 1) // ps) if lengths[s] else 0
        pt[s, :n_live] = perm[used:used + n_live]
        used += n_live
    a = np.asarray(_ragged_verify_pallas(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(pt), jnp.asarray(lengths),
        jnp.full((S,), W - 1, jnp.int32), 16 ** -0.5, True))
    b = np.asarray(ragged_verify_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(pt), jnp.asarray(lengths)))
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)


def test_verify_causal_window_masking():
    """Row r must not see keys past position L - 1 + r: rewriting key
    L + r0 changes nothing for rows <= r0 (and positions past the whole
    window never matter to anyone)."""
    rng = np.random.RandomState(23)
    H, D, ps, L, W = 2, 8, 8, 5, 4
    pages = [3, 6]
    pt = np.zeros((1, 2), np.int32)
    pt[0, :2] = pages
    kp, vp, _, _ = _make_verify_case(rng, H, D, ps, L, W, pages)
    q = rng.randn(1, W, H, D).astype(np.float32)

    def run(kparr, vparr):
        return np.asarray(_ragged_verify_pallas(
            jnp.asarray(q), _fuse(kparr, vparr),
            jnp.asarray(pt), jnp.asarray([L], jnp.int32),
            jnp.asarray([W - 1], jnp.int32), D ** -0.5, True))

    base = run(kp, vp)
    # poison position L + 1: row r sees keys [0, L - 1 + r], so rows
    # 0..1 must be bit-unchanged and rows 2.. must move
    r0 = 1
    t = L + r0
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[pages[t // ps], :, t % ps, :] = 77.0
    vp2[pages[t // ps], :, t % ps, :] = -77.0
    got = run(kp2, vp2)
    np.testing.assert_array_equal(got[0, :r0 + 1], base[0, :r0 + 1])
    assert not np.array_equal(got[0, r0 + 1:], base[0, r0 + 1:])
    # positions past the window's last visible key never matter
    kp3, vp3 = kp.copy(), vp.copy()
    t = L + W - 1                         # first position nobody sees
    kp3[pages[t // ps], :, t % ps, :] = 1e6
    vp3[pages[t // ps], :, t % ps, :] = -1e6
    np.testing.assert_array_equal(run(kp3, vp3), base)
    # jnp reference: same two properties
    refb = np.asarray(ragged_verify_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32)))
    refg = np.asarray(ragged_verify_reference(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32)))
    np.testing.assert_array_equal(refg[0, :r0 + 1], refb[0, :r0 + 1])


def test_verify_nan_propagates():
    """A NaN K/V at a position the window can read must POISON the
    output instead of being masked away (the non-finite guard's
    detection path). The jnp reference — the CPU serving path the
    engine's acceptance actually consumes — is per-ROW exact: only rows
    whose causal window includes the position go NaN. The kernel's
    granularity is the WINDOW (a 0-weight x NaN product in the shared
    p @ v contraction can spill to earlier rows — same contract as the
    chunked-prefill kernel): the rows that DO see the position must be
    NaN; the engine's guard reduces per slot, so either granularity
    quarantines exactly the poisoned slot."""
    rng = np.random.RandomState(24)
    H, D, ps, L, W = 2, 8, 8, 4, 3
    pages = [2]
    pt = np.zeros((1, 1), np.int32)
    pt[0, 0] = 2
    kp, vp, _, _ = _make_verify_case(rng, H, D, ps, L, W, pages)
    q = rng.randn(1, W, H, D).astype(np.float32)
    t = L                                 # visible to rows 1, 2 only
    vp2 = vp.copy()
    vp2[pages[0], :, t % ps, :] = np.nan
    ref = np.asarray(ragged_verify_reference(
        jnp.asarray(q), _fuse(kp, vp2),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32)))
    assert np.isfinite(ref[0, 0]).all()   # row 0 cannot see position L
    assert np.isnan(ref[0, 1:]).all()
    pal = np.asarray(_ragged_verify_pallas(
        jnp.asarray(q), _fuse(kp, vp2),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32),
        jnp.asarray([W - 1], jnp.int32), D ** -0.5, True))
    assert np.isnan(pal[0, 1:]).all()     # seeing rows must be poisoned


def test_verify_unwritten_tail_nan_does_not_poison_consumed_rows():
    """Regression: a slot drafting FEWER than window - 1 tokens leaves
    positions [L + draft_len, L + window - 1) UNWRITTEN this step — a
    recycled page can carry a quarantined slot's non-finite K/V there.
    The kernel's V-select must bound at the slot's real written extent
    L + draft_len (NOT L + window - 1, which let 0 * NaN poison every
    consumed row and falsely quarantine a healthy slot — found by
    review against the jnp reference, which is per-row exact and was
    never affected)."""
    rng = np.random.RandomState(26)
    H, D, ps, L, W = 2, 8, 8, 4, 3
    pages = [2]
    pt = np.zeros((1, 1), np.int32)
    pt[0, 0] = 2
    kp, vp, _, _ = _make_verify_case(rng, H, D, ps, L, W, pages)
    q = rng.randn(1, W, H, D).astype(np.float32)
    dl = 0                                # no drafts: only row 0 consumed
    ref = np.asarray(ragged_verify_reference(
        jnp.asarray(q), _fuse(kp, vp),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32)))
    # poison every position past the written extent L - 1 + dl
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[pages[0], :, L + dl:, :] = np.nan
    vp2[pages[0], :, L + dl:, :] = np.nan
    pal = np.asarray(_ragged_verify_pallas(
        jnp.asarray(q), _fuse(kp2, vp2),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32),
        jnp.asarray([dl], jnp.int32), D ** -0.5, True))
    assert np.isfinite(pal[0, :dl + 1]).all(), \
        "unwritten-tail NaN leaked into consumed verify rows (kernel)"
    np.testing.assert_allclose(pal[0, :dl + 1], ref[0, :dl + 1],
                               rtol=2e-5, atol=2e-5)
    # a partial draft (dl = 1 of W - 1 = 2) behaves the same
    dl = 1
    kp3, vp3 = kp.copy(), vp.copy()
    kp3[pages[0], :, L + dl:, :] = np.nan
    vp3[pages[0], :, L + dl:, :] = np.nan
    pal = np.asarray(_ragged_verify_pallas(
        jnp.asarray(q), _fuse(kp3, vp3),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32),
        jnp.asarray([dl], jnp.int32), D ** -0.5, True))
    assert np.isfinite(pal[0, :dl + 1]).all()
    np.testing.assert_allclose(pal[0, :dl + 1], ref[0, :dl + 1],
                               rtol=2e-5, atol=2e-5)


def test_verify_dispatcher_and_dtype():
    """The public dispatcher runs the jnp path on the CPU backend; bf16
    inputs keep f32 accumulation and track the f32 result."""
    rng = np.random.RandomState(25)
    H, D, ps, L, W = 2, 8, 8, 9, 3
    pages = [5, 3]
    pt = np.zeros((1, 2), np.int32)
    pt[0, :2] = pages
    kp, vp, tok_k, tok_v = _make_verify_case(rng, H, D, ps, L, W, pages)
    q = rng.randn(1, W, H, D).astype(np.float32)
    out = ragged_verify_attention(jnp.asarray(q), _fuse(kp, vp),
                                  jnp.asarray(pt),
                                  jnp.asarray([L], jnp.int32))
    ref = _verify_oracle(q[0], tok_k, tok_v, L)
    np.testing.assert_allclose(np.asarray(out)[0], ref, rtol=2e-5,
                               atol=2e-5)
    b16 = ragged_verify_attention(
        jnp.asarray(q, jnp.bfloat16), _fuse(kp, vp).astype(jnp.bfloat16),
        jnp.asarray(pt), jnp.asarray([L], jnp.int32))
    assert b16.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(b16, np.float32)[0], ref,
                               rtol=0.06, atol=0.06)


def test_kernel_page_table_permutation_invariance():
    """Two page tables describing the same token sequence through
    different physical pages must give identical outputs (pages are
    identity-free — the slot-reuse guarantee)."""
    rng = np.random.RandomState(5)
    S, H, D, ps, max_pages = 1, 2, 8, 4, 3
    tokens_k = rng.randn(12, H, D).astype(np.float32)
    tokens_v = rng.randn(12, H, D).astype(np.float32)
    q = jnp.asarray(rng.randn(S, H, D).astype(np.float32))
    outs = []
    for pages in ([1, 2, 3], [5, 2, 7]):
        kp = np.zeros((8, H, ps, D), np.float32)
        vp = np.zeros((8, H, ps, D), np.float32)
        for j, p in enumerate(pages):
            kp[p] = tokens_k[j * ps:(j + 1) * ps].transpose(1, 0, 2)
            vp[p] = tokens_v[j * ps:(j + 1) * ps].transpose(1, 0, 2)
        pt = jnp.asarray(np.asarray([pages], np.int32))
        outs.append(np.asarray(_ragged_pallas(
            q, _fuse(kp, vp), pt,
            jnp.asarray([12], np.int32), D ** -0.5, True)))
    np.testing.assert_array_equal(outs[0], outs[1])


# ------------------------------------------------------------------- #
# quantized pools: int8 pages + per-page scales, dequant at the DMA
# boundary (serve/paged_kv.py quantized layout; the f32 jnp reference
# is the accuracy ORACLE — bit-parity is replaced by a measured
# tolerance bounded by the pages' quantization quanta)
# ------------------------------------------------------------------- #

def _quantize_pools(k_pool, v_pool):
    """Quantize whole f32 pools page-by-page through the serving write
    path (fresh per-page scales), returning int8 pools + scale arrays."""
    from incubator_mxnet_tpu.serve.paged_kv import (kv_quant_spec,
                                                    page_scales,
                                                    write_prompt_kv_q)
    spec = kv_quant_spec("int8")
    P, H, ps, D = k_pool.shape
    pages = jnp.arange(P, dtype=jnp.int32)
    rows = jnp.moveaxis(_fuse(k_pool, v_pool), 1, 2).reshape(P * ps, H,
                                                             2 * D)
    pool, kam, vam = write_prompt_kv_q(
        jnp.zeros((P, H, ps, 2 * D), spec.dtype), jnp.zeros((P,)),
        jnp.zeros((P,)), rows, pages, spec)
    return (pool[..., :D], pool[..., D:], page_scales(kam, spec),
            page_scales(vam, spec), spec)


def _quant_tol(k_pool, v_pool):
    """A loose end-to-end bound: attention output error is dominated by
    the V quantum (output is a convex combination of V rows) plus a
    softmax-reweighting term from the K quantum."""
    qk = np.abs(np.asarray(k_pool)).max() / 127.0
    qv = np.abs(np.asarray(v_pool)).max() / 127.0
    return 4.0 * (qk + qv)


@pytest.mark.parametrize("lengths", [[0, 1, 8, 9, 32], [7, 8, 9, 15, 16]])
def test_quantized_decode_matches_f32_oracle(lengths):
    rng = np.random.RandomState(31)
    q, k_pool, v_pool, pt, ln = _make_case(rng, 5, 2, 8, 8, 4, lengths)
    kq, vq, ks, vs, _ = _quantize_pools(k_pool, v_pool)
    oracle = np.asarray(ragged_attention_reference(q, _fuse(k_pool, v_pool),
                                                   pt, ln))
    got = np.asarray(ragged_attention_reference(q, _fuse(kq, vq), pt, ln,
                                                k_scale=ks, v_scale=vs))
    assert np.abs(got - oracle).max() <= _quant_tol(k_pool, v_pool)
    # the masked-row contract survives quantization: length-0 slots
    # emit exactly zero
    for s, l in enumerate(lengths):
        if l == 0:
            np.testing.assert_array_equal(got[s], 0.0)


def test_quantized_decode_pallas_interpret_matches_reference():
    """The kernel's inline scalar-prefetch dequant must agree with the
    jnp gather-dequant reference to float rounding — the same
    cross-backend contract as the unquantized kernel, at the quantized
    operand dtypes."""
    rng = np.random.RandomState(32)
    q, k_pool, v_pool, pt, ln = _make_case(rng, 4, 2, 8, 8, 4,
                                           [0, 5, 16, 27])
    kq, vq, ks, vs, _ = _quantize_pools(k_pool, v_pool)
    ref = np.asarray(ragged_attention_reference(q, _fuse(kq, vq), pt, ln,
                                                k_scale=ks, v_scale=vs))
    got = np.asarray(_ragged_pallas(q, _fuse(kq, vq), pt, ln, 8 ** -0.5,
                                    True, ks, vs))
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)


def test_quantized_prefill_matches_f32_oracle_and_kernel():
    rng = np.random.RandomState(33)
    _, k_pool, v_pool, pt, _ = _make_case(rng, 1, 2, 8, 8, 4, [32])
    kq, vq, ks, vs, _ = _quantize_pools(k_pool, v_pool)
    C = 8
    qc = jnp.asarray(rng.randn(C, 2, 8).astype(np.float32))
    row = pt[0]
    oracle = np.asarray(ragged_prefill_reference(
        qc, _fuse(k_pool, v_pool), row, jnp.int32(16), n_real=6))
    got = np.asarray(ragged_prefill_reference(
        qc, _fuse(kq, vq), row, jnp.int32(16), n_real=6, k_scale=ks,
        v_scale=vs))
    assert np.abs(got[:6] - oracle[:6]).max() <= \
        _quant_tol(k_pool, v_pool)
    kern = np.asarray(_ragged_prefill_pallas(
        qc, _fuse(kq, vq), row, jnp.asarray([16, 6], dtype=jnp.int32),
        8 ** -0.5, True, ks, vs))
    np.testing.assert_allclose(kern[:6], got[:6], rtol=2e-5, atol=2e-5)


def test_quantized_verify_matches_f32_oracle_and_kernel():
    rng = np.random.RandomState(34)
    _, k_pool, v_pool, pt, _ = _make_case(rng, 3, 2, 8, 8, 4,
                                          [5, 17, 0])
    kq, vq, ks, vs, _ = _quantize_pools(k_pool, v_pool)
    W = 3
    qv = jnp.asarray(rng.randn(3, W, 2, 8).astype(np.float32))
    ln = jnp.asarray(np.array([3, 9, 0], np.int32))
    dl = jnp.asarray(np.array([2, 2, 0], np.int32))
    oracle = np.asarray(ragged_verify_reference(qv, _fuse(k_pool, v_pool),
                                                pt, ln))
    got = np.asarray(ragged_verify_reference(qv, _fuse(kq, vq), pt, ln,
                                             k_scale=ks, v_scale=vs))
    assert np.abs(got - oracle).max() <= _quant_tol(k_pool, v_pool)
    np.testing.assert_array_equal(got[2], 0.0)    # dead slot stays zero
    kern = np.asarray(_ragged_verify_pallas(qv, _fuse(kq, vq), pt, ln, dl,
                                            8 ** -0.5, True, ks, vs))
    # consumed rows (<= dl) must match; later rows are contractually
    # discarded by the engine
    for s in range(3):
        d = int(np.asarray(dl)[s])
        np.testing.assert_allclose(kern[s, :d + 1], got[s, :d + 1],
                                   rtol=2e-5, atol=2e-5)


def test_poisoned_page_scale_propagates_and_isolates():
    """int8 payloads cannot carry NaN — the page SCALE is the
    corruption channel: a NaN scale on one live page must make exactly
    the slots reading that page non-finite (so the serving guard can
    quarantine them) while every other slot stays bit-identical."""
    rng = np.random.RandomState(35)
    q, k_pool, v_pool, pt, ln = _make_case(rng, 3, 2, 8, 8, 4,
                                           [16, 16, 8])
    kq, vq, ks, vs, _ = _quantize_pools(k_pool, v_pool)
    clean = np.asarray(ragged_attention_reference(
        q, _fuse(kq, vq), pt, ln, k_scale=ks, v_scale=vs))
    page = int(np.asarray(pt)[0, 0])              # slot 0's first page
    ks_bad = ks.at[page].set(jnp.nan)
    got = np.asarray(ragged_attention_reference(
        q, _fuse(kq, vq), pt, ln, k_scale=ks_bad, v_scale=vs))
    assert np.isnan(got[0]).all()                 # poisoned slot visible
    np.testing.assert_array_equal(got[1], clean[1])
    np.testing.assert_array_equal(got[2], clean[2])


# ------------------------------------------------------------------- #
# the fused pool at every head size the engines run: keys | values on
# 2 * D lanes (32 to 256), odd and even head counts, bf16 pages of 16
# ------------------------------------------------------------------- #

@pytest.mark.parametrize("H", [3, 4])
@pytest.mark.parametrize("D", [16, 32, 64, 128])
def test_fused_pool_kernels_match_reference_at_head_sizes(D, H):
    """The three kernels (interpreted) against the jnp references over
    one bf16 pool of (P, H, 16, 2 * D): a dead slot emits exactly zero,
    and the null page, poisoned with NaN in both lane halves, is never
    read unmasked: the padded query's zero lanes meet the value half of
    every tile, so a masked row that leaked would show as NaN."""
    rng = np.random.RandomState(40 + D + H)
    ps, max_pages, W = 16, 4, 3
    lengths = [0, 1, 16, 17, 45]
    S = len(lengths)
    q, kp, vp, pt, ln = _make_case(rng, S, H, D, ps, max_pages, lengths,
                                   num_pages=24)
    pool = _fuse(kp, vp).astype(jnp.bfloat16).at[0].set(jnp.nan)
    assert pool.shape == (24, H, ps, 2 * D)
    q = q.astype(jnp.bfloat16)
    tol = dict(rtol=3e-2, atol=3e-2)

    got = np.asarray(_ragged_pallas(q, pool, pt, ln, D ** -0.5, True),
                     np.float32)
    ref = np.asarray(ragged_attention_reference(q, pool, pt, ln),
                     np.float32)
    assert got.shape == (S, H, D) and np.isfinite(got).all()
    np.testing.assert_array_equal(got[0], 0.0)
    np.testing.assert_allclose(got, ref, **tol)

    # verify: rows 0..dl of each slot are consumed; the window's last
    # positions are written (every table entry a slot reads is live)
    qv = jnp.asarray(rng.randn(S, W, H, D), jnp.bfloat16)
    lv = jnp.asarray([0, 1, 14, 17, 40], jnp.int32)
    dl = jnp.asarray([0, 2, 2, 1, 2], jnp.int32)
    got = np.asarray(_ragged_verify_pallas(qv, pool, pt, lv, dl,
                                           D ** -0.5, True), np.float32)
    ref = np.asarray(ragged_verify_reference(qv, pool, pt, lv),
                     np.float32)
    np.testing.assert_array_equal(got[0], 0.0)
    for s in range(1, S):
        d = int(dl[s])
        np.testing.assert_allclose(got[s, :d + 1], ref[s, :d + 1], **tol)

    # chunk prefill over the longest slot's row: 13 live rows of 16
    qc = jnp.asarray(rng.randn(16, H, D), jnp.bfloat16)
    got = np.asarray(_ragged_prefill_pallas(
        qc, pool, pt[4], jnp.asarray([32, 13], jnp.int32), D ** -0.5,
        True), np.float32)
    ref = np.asarray(ragged_prefill_reference(
        qc, pool, pt[4], jnp.int32(32), n_real=13), np.float32)
    assert np.isfinite(got[:13]).all()
    np.testing.assert_allclose(got[:13], ref[:13], **tol)


# ------------------------------------------------------------------- #
# the walk of the live blocks: a program visits ceil(live keys /
# (G * page_size)) blocks of G pages and no more (G = 8 at a page of
# 16: a lane-full row of 128 keys). The edges of that walk, for the
# three kernels, against the jnp references
# ------------------------------------------------------------------- #

_WALK_PS, _WALK_PAGES = 16, 11          # one whole block and a short one
_WALK_BLOCK, _WALK_SPARE = 128, 40
_WALK_EDGES = [0, 1, _WALK_BLOCK - 1, _WALK_BLOCK, _WALK_BLOCK + 1,
               _WALK_PAGES * _WALK_PS]


def _walk_pool(rng, live, H=2, D=8):
    """Random pools and a SHUFFLED table of ``_WALK_PAGES`` entries a
    slot, each slot's row mapping the pages that hold its ``live`` key
    positions; page 0 is the null page, and it and the last page,
    ``_WALK_SPARE``, which no slot maps, are filled with NaN."""
    _, kp, vp, pt, _ = _make_case(rng, len(live), H, D, _WALK_PS,
                                  _WALK_PAGES, live,
                                  num_pages=_WALK_SPARE)
    pool = jnp.pad(_fuse(kp, vp), [(0, 1), (0, 0), (0, 0), (0, 0)])
    return pool.at[0].set(jnp.nan).at[_WALK_SPARE].set(jnp.nan), pt


def _walk(kernel, rng, pool, pt, live, scales=(), C=8):
    """``kernel`` (interpreted) and its jnp reference over slots whose
    consumed query rows read ``live[s]`` key positions. Returns
    ``(got, ref, rows)``: both outputs with a leading slot axis, and
    each slot's count of consumed query rows."""
    H, D = pool.shape[1], pool.shape[-1] // 2
    live = np.asarray(live, np.int32)
    S, sc, kw = len(live), D ** -0.5, {}
    if scales:
        kw = dict(k_scale=scales[0], v_scale=scales[1])
    if kernel == "decode":
        q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
        got = _ragged_pallas(q, pool, pt, jnp.asarray(live), sc, True,
                             *scales)
        ref = ragged_attention_reference(q, pool, pt, jnp.asarray(live),
                                         **kw)
        rows = [1] * S
        got, ref = got[:, None], ref[:, None]
    elif kernel == "verify":
        W = 3
        dl = np.clip(live - 1, 0, W - 1)    # live = lengths + draft_len
        q = jnp.asarray(rng.randn(S, W, H, D), jnp.float32)
        got = _ragged_verify_pallas(q, pool, pt, jnp.asarray(live - dl),
                                    jnp.asarray(dl), sc, True, *scales)
        ref = ragged_verify_reference(q, pool, pt, jnp.asarray(live - dl),
                                      **kw)
        rows = [int(d) + 1 if n else W for d, n in zip(dl, live)]
    else:                                   # one slot: its table row
        n_real = min(C, int(live[0]))       # live = q_start + n_real
        q = jnp.asarray(rng.randn(C, H, D), jnp.float32)
        got = _ragged_prefill_pallas(
            q, pool, pt[0], jnp.asarray([live[0] - n_real, n_real],
                                        jnp.int32), sc, True, *scales)[None]
        ref = ragged_prefill_reference(
            q, pool, pt[0], jnp.int32(live[0] - n_real), n_real=n_real,
            **kw)[None]
        rows = [n_real]
    return np.asarray(got), np.asarray(ref), rows


def _assert_walk(got, ref, rows, live):
    for s, n in enumerate(live):
        assert np.isfinite(got[s, :rows[s]]).all(), (s, n)
        if n == 0:                          # the masked-row contract
            np.testing.assert_array_equal(got[s], 0.0)
        else:
            np.testing.assert_allclose(got[s, :rows[s]], ref[s, :rows[s]],
                                       rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("edge,quant", [(e, False) for e in _WALK_EDGES] +
                         [(_WALK_BLOCK + 1, True)])
@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_live_block_walk_edges(kernel, edge, quant):
    """Live keys at 0, 1, a block less one, a block, a block and one,
    the whole table (11 pages: not a multiple of the 8 a block holds),
    through shuffled tables, beside slots that end elsewhere."""
    rng = np.random.RandomState(50 + edge)
    live = [edge] if kernel == "prefill" else [37, edge, 150]
    pool, pt = _walk_pool(rng, live)
    scales = ()
    if quant:
        # a NaN page has no scale
        pool = pool.at[0].set(0.0).at[_WALK_SPARE].set(0.0)
        kq, vq, ks, vs, _ = _quantize_pools(*np.split(np.asarray(pool),
                                                      2, -1))
        pool, scales = _fuse(kq, vq), (ks, vs)
    got, ref, rows = _walk(kernel, rng, pool, pt, live, scales)
    _assert_walk(got, ref, rows, live)


@pytest.mark.parametrize("edge", [5, _WALK_BLOCK + 1, 140])
@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_dead_entries_and_unwritten_tail_change_no_output_bit(kernel,
                                                              edge):
    """What a walk fetches and may not use: the dead entries of the
    last live block, pointed at a NaN-filled page that is NOT the null
    page, and the last live page's positions past the live keys, NaN
    as a recycled page may leave them. Every consumed row stays finite,
    equals the reference, and is bit for bit what the same walk gives
    over null-page entries and a clean tail."""
    live = [edge] if kernel == "prefill" else [edge, 0, 33]
    pool, pt = _walk_pool(np.random.RandomState(60), live)
    clean = _walk(kernel, np.random.RandomState(61), pool, pt, live)
    _assert_walk(*clean, live)
    pt2, pool2 = np.asarray(pt).copy(), np.asarray(pool).copy()
    for s, n in enumerate(live):
        held = -(-n // _WALK_PS)
        pt2[s, held:] = _WALK_SPARE
        if n % _WALK_PS:
            pool2[pt2[s, held - 1], :, n % _WALK_PS:] = np.nan
    dirty = _walk(kernel, np.random.RandomState(61), jnp.asarray(pool2),
                  jnp.asarray(pt2), live)
    _assert_walk(*dirty, live)
    for s, n in enumerate(live):
        np.testing.assert_array_equal(dirty[0][s, :dirty[2][s]],
                                      clean[0][s, :clean[2][s]])


@pytest.mark.parametrize("kernel", ["decode", "verify"])
def test_dead_slot_between_live_slots_emits_exact_zeros(kernel):
    """A length-0 slot runs zero turns of the walk: exact zeros out,
    whatever its table row points at and whatever the live slots before
    and after it left in the kernel's buffers."""
    live = [_WALK_BLOCK + 9, 0, 61]
    pool, pt = _walk_pool(np.random.RandomState(70), live)
    pt = jnp.asarray(pt).at[1].set(_WALK_SPARE)
    got, ref, rows = _walk(kernel, np.random.RandomState(71), pool, pt,
                           live)
    np.testing.assert_array_equal(got[1], 0.0)
    _assert_walk(got, ref, rows, live)


def test_chunk_walk_takes_the_heads_a_group_a_loop_turn():
    """From 256 query rows on the kernels take the heads one a loop
    turn (``_head_group``: a turn's stack of scores stays within 512
    rows), where every smaller case here takes them all at once: the
    rolled loop, its dynamic head index and the per-group state rows
    against the reference, padded rows past the live keys included."""
    from incubator_mxnet_tpu.ops.ragged_attention import _head_group
    assert _head_group(3, 256) == 1 and _head_group(25, 64) == 5
    assert _head_group(25, 1) == 25 and _head_group(4, 8) == 4
    rng = np.random.RandomState(80)
    live = [_WALK_PAGES * _WALK_PS]
    pool, pt = _walk_pool(rng, live, H=3)
    got, ref, rows = _walk("prefill", rng, pool, pt, live, C=256)
    assert rows == live
    _assert_walk(got, ref, rows, live)


# --------------------------------------------------------------------- #
# grouped queries: Hq query heads over H key-value heads of the pool
# --------------------------------------------------------------------- #

def _repeat_heads(kp, vp, rep):
    """The pool a plain multi-head model would hold if every query head
    had its own copy of its key-value head: the grouped-query oracle."""
    return _fuse(jnp.repeat(jnp.asarray(kp), rep, axis=1),
                 jnp.repeat(jnp.asarray(vp), rep, axis=1))


@pytest.mark.parametrize("Hq,H", [(4, 2), (8, 2), (32, 8), (3, 3)],
                         ids=["4over2", "8over2", "32over8", "equal"])
@pytest.mark.parametrize("kernel", ["decode", "verify", "prefill"])
def test_grouped_query_kernels_match_their_references(kernel, Hq, H):
    """Query head j reads key-value head j // (Hq / H), at a scale that is
    not ``D ** -0.5``: the interpreted kernels against the jnp references
    on the H-head pool, and the references against the equal-heads path on
    a pool with every key-value head repeated (at equal heads that is the
    same call: the old case). float32: 2e-5 covers the online softmax's
    other order of sums."""
    rng = np.random.RandomState(70 + Hq + H)
    D, ps, max_pages, scale = 32, 8, 5, 1.0 / 64
    rep = Hq // H
    lengths = [0, 1, 8, 9, 37]
    S = len(lengths)
    _, kp, vp, pt, ln = _make_case(rng, S, H, D, ps, max_pages, lengths,
                                   num_pages=24)
    pool, wide = _fuse(kp, vp), _repeat_heads(kp, vp, rep)
    tol = dict(rtol=2e-5, atol=2e-5)
    if kernel == "decode":
        q = jnp.asarray(rng.randn(S, Hq, D), jnp.float32)
        got = _ragged_pallas(q, pool, pt, ln, scale, True)
        ref = ragged_attention_reference(q, pool, pt, ln, scale)
        old = ragged_attention_reference(q, wide, pt, ln, scale)
        assert got.shape == (S, Hq, D)
        np.testing.assert_array_equal(np.asarray(got)[0], 0.0)
    elif kernel == "verify":
        W = 3
        q = jnp.asarray(rng.randn(S, W, Hq, D), jnp.float32)
        lv = jnp.asarray([0, 1, 6, 9, 35], jnp.int32)
        dl = jnp.full((S,), W - 1, jnp.int32)
        got = _ragged_verify_pallas(q, pool, pt, lv, dl, scale, True)
        ref = ragged_verify_reference(q, pool, pt, lv, scale)
        old = ragged_verify_reference(q, wide, pt, lv, scale)
        assert got.shape == (S, W, Hq, D)
    else:
        C, start, n_real = 16, 21, 13       # a chunk after 21 cached
        q = jnp.asarray(rng.randn(C, Hq, D), jnp.float32)
        row = pt[4]
        got = _ragged_prefill_pallas(q, pool, row, jnp.asarray(
            [start, n_real], jnp.int32), scale, True)[:n_real]
        ref = ragged_prefill_reference(q, pool, row, start, scale,
                                       n_real=n_real)[:n_real]
        old = ragged_prefill_reference(q, wide, row, start, scale,
                                       n_real=n_real)[:n_real]
        assert got.shape == (n_real, Hq, D)
    np.testing.assert_allclose(got, ref, **tol)
    np.testing.assert_allclose(ref, old, **tol)
    # the scale is read: at D ** -0.5 the answer is another
    if kernel == "decode":
        other = ragged_attention_reference(q, pool, pt, ln)
        assert np.abs(np.asarray(other) - np.asarray(ref)).max() > 1e-3


def test_grouped_query_dispatchers_take_the_pool_heads_from_the_pool():
    """The public entries at 4 query heads over a 2-head pool: the jnp path
    and the interpreted kernel agree, decode and chunk."""
    rng = np.random.RandomState(77)
    _, kp, vp, pt, ln = _make_case(rng, 3, 2, 32, 8, 4, [5, 0, 30],
                                   num_pages=12)
    pool = _fuse(kp, vp)
    q = jnp.asarray(rng.randn(3, 4, 32), jnp.float32)
    a = ragged_paged_attention(q, pool, pt, ln, scale=0.1, interpret=False)
    b = ragged_paged_attention(q, pool, pt, ln, scale=0.1, interpret=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)
    qc = jnp.asarray(rng.randn(8, 4, 32), jnp.float32)
    a = ragged_prefill_attention(qc, pool, pt[2], 22, n_real=8, scale=0.1,
                                 interpret=False)
    b = ragged_prefill_attention(qc, pool, pt[2], 22, n_real=8, scale=0.1,
                                 interpret=True)
    np.testing.assert_allclose(a, b, rtol=2e-5, atol=2e-5)

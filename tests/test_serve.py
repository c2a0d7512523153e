"""Continuous-batching engine tests (serve/).

The load-bearing claims: (1) paged-cache decode emits EXACTLY the
tokens of the dense-cache ``cached_generate`` path, per request, even
when requests share a batch at mixed occupancy; (2) occupancy churn
(prefill-insert, EOS-eviction, slot reuse) never retraces the decode
step; (3) pages are fully reclaimed; (4) per-slot sampling params are
isolated; (5) tp pool sharding through parallel.mesh preserves
tokens."""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models import gpt as g
from incubator_mxnet_tpu.serve import InferenceEngine, Request
from incubator_mxnet_tpu.serve.paged_kv import (NULL_PAGE, PageAllocator,
                                                PrefixIndex)


@pytest.fixture(scope="module")
def model():
    mx.random.seed(0)
    m = g.gpt_mini(vocab_size=64, max_length=64)
    m.initialize()
    return m


def _solo_reference(model, prompt, max_new):
    """Per-request oracle: the dense KV-cache decode path."""
    out = g.cached_generate(model, nd.array(prompt[None, :],
                                            dtype="int32"),
                            max_new_tokens=max_new).asnumpy()
    return out[0, prompt.size:]


def test_single_request_matches_cached_generate(model):
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 64, size=(7,)).astype(np.int32)
    ref = _solo_reference(model, prompt, 12)
    eng = InferenceEngine(model, num_slots=4, page_size=8, max_len=64)
    req = Request(prompt, max_new_tokens=12)
    eng.run([req])
    np.testing.assert_array_equal(np.asarray(req.token_ids, np.int32),
                                  ref)
    assert eng.decode_trace_count == 1


class _SeamOnly:
    """Of a model, what the engine's constructor and the seam name
    (docs/SERVING.md "What the engine asks of a model"); any other read
    fails the test."""
    _ASKED = {"cache_layout", "cached_forward", "collect_params",
              "vocab_size", "max_length", "_dtype"}

    def __init__(self, model):
        self._model = model

    def __getattr__(self, name):
        if name not in self._ASKED:
            raise AssertionError(f"the engine read model.{name}")
        return getattr(self._model, name)


@pytest.mark.parametrize("engine_kw", [
    {}, {"chunk_pages": 1}, {"kv_quant": "int8", "spec_k": 2}],
    ids=["monolithic", "chunked", "int8-verify"])
def test_engine_reads_a_model_through_its_seam_only(model, engine_kw):
    """An engine over a proxy that hides the model's insides serves the
    tokens of the engine over the model itself: the scheduler knows
    slots, pages and sampling, and nothing of blocks, norms or heads."""
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in (5, 19, 11)]
    served = []
    for m in (model, _SeamOnly(model)):
        eng = InferenceEngine(m, num_slots=2, page_size=8, max_len=64,
                              **engine_kw)
        reqs = [Request(p, max_new_tokens=6 + i, seed=i,
                        temperature=0.0 if i < 2 else 0.7)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        eng.audit_pages()
        served.append([list(r.token_ids) for r in reqs])
    assert served[0] == served[1]
    assert all(len(t) == 6 + i for i, t in enumerate(served[1]))


@pytest.mark.slow   # 13-21s (round-10 tier-1 budget repair); ci stage_unit runs it
def test_mixed_occupancy_no_cross_contamination_and_slot_reuse(model):
    """5 ragged requests through 3 slots with staggered arrivals: every
    request's tokens must equal its SOLO dense-cache decode (continuous
    batching is invisible to each request), the decode step compiles
    once across all the insert/evict churn, and every page returns to
    the allocator (slot + page reuse)."""
    rng = np.random.RandomState(2)
    lens = (3, 9, 17, 5, 12)
    news = (10, 6, 14, 8, 12)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in lens]
    refs = [_solo_reference(model, p, k) for p, k in zip(prompts, news)]
    eng = InferenceEngine(model, num_slots=3, page_size=8, max_len=64,
                          num_pages=20)
    reqs = [Request(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    eng.run(reqs, arrival_times=[0.0, 0.0, 0.01, 0.02, 0.03])
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)
    assert eng.decode_trace_count == 1, \
        "decode step retraced under occupancy churn"
    # every page is either on the free list or retained by the prefix
    # index (full prompt pages stay cached for reuse) — nothing leaked
    eng.audit_pages()
    assert eng._alloc.free_count == eng.num_pages - 1 - len(eng._prefix)
    assert len(eng._prefix) > 0          # the full prompt pages cached
    assert (eng._page_table == NULL_PAGE).all()
    assert (eng._lengths == 0).all()


def test_eos_eviction_truncates_and_frees(model):
    rng = np.random.RandomState(3)
    prompt = rng.randint(0, 64, size=(6,)).astype(np.int32)
    ref = _solo_reference(model, prompt, 14)
    eos = int(ref[3])
    stop = int(np.argmax(ref == eos))       # first occurrence
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    req = Request(prompt, max_new_tokens=14, eos_id=eos)
    eng.run([req])
    np.testing.assert_array_equal(np.asarray(req.token_ids, np.int32),
                                  ref[:stop + 1])
    assert req.finish_time is not None
    assert eng.active_count == 0
    assert eng._alloc.free_count == eng.num_pages - 1


def test_per_slot_sampling_isolation(model):
    """A greedy request and a temperature>0 request share the decode
    batch; the greedy one's tokens must be bit-identical to its solo
    run — per-slot sampling params must not leak across slots."""
    rng = np.random.RandomState(4)
    p_greedy = rng.randint(0, 64, size=(8,)).astype(np.int32)
    p_hot = rng.randint(0, 64, size=(11,)).astype(np.int32)
    ref = _solo_reference(model, p_greedy, 10)
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    r1 = Request(p_greedy, max_new_tokens=10, temperature=0.0)
    r2 = Request(p_hot, max_new_tokens=10, temperature=1.3)
    eng.run([r1, r2])
    np.testing.assert_array_equal(np.asarray(r1.token_ids, np.int32),
                                  ref)
    assert len(r2.token_ids) == 10
    assert all(0 <= t < 64 for t in r2.token_ids)


@pytest.mark.slow   # 10s (round-11 tier-1 budget repair); admission /
                    # reclaim tier-1 coverage stays via the churn-audit
                    # and unservable tests; ci stage_unit runs it
def test_admission_control_waits_for_pages(model):
    """A pool too small for two concurrent requests serializes them
    (second waits for eviction) instead of corrupting the cache; a pool
    too small for ANY request fails THAT request with the
    FAILED_UNSERVABLE terminal outcome — regression for the old
    behavior where run() raised RuntimeError/MXNetError out of the
    serving loop and took every other in-flight request down with it."""
    from incubator_mxnet_tpu.serve import Outcome
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 64, size=(8,)).astype(np.int32)
               for _ in range(2)]
    refs = [_solo_reference(model, p, 8) for p in prompts]
    # each request needs ceil(16/8)=2 pages; 3 non-null pages admit one
    # at a time only
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          num_pages=4)
    reqs = [Request(p, max_new_tokens=8) for p in prompts]
    eng.run(reqs)
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)
    # the old crash path: a request that can NEVER fit the pool, mixed
    # with one that can — the doomed one fails loudly (terminal outcome,
    # detail naming the capacity), the other is served to completion
    tiny = InferenceEngine(model, num_slots=1, page_size=8, max_len=64,
                           num_pages=3)
    doomed = Request(prompts[0], max_new_tokens=16)   # needs 3 > 2 pages
    servable = Request(prompts[1], max_new_tokens=8)  # needs 2 pages
    tiny.run([doomed, servable])
    assert doomed.outcome == Outcome.FAILED_UNSERVABLE
    assert "pages" in doomed.detail
    assert servable.outcome is not None and servable.outcome.ok
    np.testing.assert_array_equal(
        np.asarray(servable.token_ids, np.int32), refs[1])
    assert tiny.unservable == 1
    tiny.audit_pages()


def test_decode_shapes_independent_of_occupancy(model):
    """Drain a batch where every step changes occupancy (different
    max_new per request) — still one decode trace, and prefill traces
    are bounded by the bucket family, not the request count."""
    rng = np.random.RandomState(6)
    reqs = [Request(rng.randint(0, 64, size=(1 + 2 * i,)).astype(
        np.int32), max_new_tokens=3 + i) for i in range(6)]
    eng = InferenceEngine(model, num_slots=4, page_size=8, max_len=64)
    eng.run(reqs)
    assert eng.decode_trace_count == 1
    assert eng.prefill_trace_count <= 3     # pow2 page buckets: 1, 2, 4
    assert all(len(r.token_ids) == 3 + i for i, r in enumerate(reqs))


@pytest.mark.slow   # 13-21s (round-10 tier-1 budget repair); ci stage_unit runs it
def test_tp_sharded_pools_token_parity(model):
    """Pools sharded over the tp mesh axis (H dim) through
    parallel.mesh must reproduce the unsharded tokens exactly — the
    engine is mesh-agnostic data-flow, sharding is placement only."""
    from incubator_mxnet_tpu.parallel.mesh import build_mesh
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the virtual multi-device mesh")
    mesh = build_mesh(axis_sizes={"tp": 2})
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in (5, 13)]
    refs = [_solo_reference(model, p, 9) for p in prompts]
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          mesh=mesh)
    reqs = [Request(p, max_new_tokens=9) for p in prompts]
    eng.run(reqs)
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)


def test_warm_restart_swaps_weights_without_retrace(model, tmp_path):
    """Elastic-checkpointing serve integration: warm_start pushes NEW
    weights into a LIVE engine — tokens must match a fresh engine built
    on those weights (proof the swap took effect) while the decode step
    keeps its single compile (weights are traced inputs, not closure
    constants)."""
    from incubator_mxnet_tpu import checkpoint as ckpt

    mx.random.seed(1234)
    model_b = g.gpt_mini(vocab_size=64, max_length=64)
    model_b.initialize()
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, 64, size=(7,)).astype(np.int32)
    ref_b = _solo_reference(model_b, prompt, 10)

    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    r0 = Request(prompt.copy(), max_new_tokens=10)
    eng.run([r0])
    assert eng.decode_trace_count == 1
    prefills_before = eng.prefill_trace_count

    # ship model_b's weights through a committed checkpoint, then warm
    # restart the live engine from it
    mgr = ckpt.CheckpointManager(str(tmp_path), keep=1)
    eng_b = InferenceEngine(model_b, num_slots=2, page_size=8,
                            max_len=64)
    eng_b.save_checkpoint(mgr, block=True)
    eng.warm_start(manager=mgr)
    r1 = Request(prompt.copy(), max_new_tokens=10)
    eng.run([r1])
    np.testing.assert_array_equal(np.asarray(r1.token_ids, np.int32),
                                  ref_b)
    assert eng.decode_trace_count == 1, "warm restart retraced decode"
    assert eng.prefill_trace_count == prefills_before, \
        "warm restart retraced prefill"
    assert eng.warm_restarts == 1
    mgr.close()


def test_warm_restart_accepts_full_training_capsule_tree(model):
    """Regression: a TRAINING capsule also carries opt/<i>/<j> and
    rng/key entries; warm_start must use only the param/ entries
    instead of letting the extra keys break positional-key detection
    (the advertised train-to-serve path)."""
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    tree = {f"param/{i}": p.data().asnumpy()
            for i, p in enumerate(eng._eng_params)}
    tree["opt/0/0"] = np.zeros((1,), np.float32)
    tree["rng/key"] = np.zeros((2,), np.uint32)
    eng.warm_start(params=tree)
    assert eng.warm_restarts == 1
    assert eng.decode_trace_count == 0   # still nothing traced


def test_warm_restart_rejects_shape_mismatch(model):
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    bad = {str(i): np.zeros((1, 1), np.float32)
           for i in range(len(eng._eng_params))}
    with pytest.raises(MXNetError, match="shape/dtype"):
        eng.warm_start(params=bad)


def test_page_allocator_invariants():
    a = PageAllocator(5)
    assert a.free_count == 4                 # page 0 reserved
    got = {a.alloc() for _ in range(4)}
    assert NULL_PAGE not in got
    with pytest.raises(MXNetError):
        a.alloc()
    a.free(got)
    assert a.free_count == 4
    with pytest.raises(MXNetError):
        a.free([NULL_PAGE])
    with pytest.raises(MXNetError):
        PageAllocator(1)


def test_page_allocator_refcount_hardening():
    """Free-list corruption is refused loudly: freeing the null page,
    double-freeing a page already back on the free list, and dropping a
    refcount below zero all raise instead of silently double-granting
    pages later."""
    a = PageAllocator(6)
    # double free: the second decref finds refcount 0
    p = a.alloc()
    assert a.refcount(p) == 1
    a.free([p])
    assert a.refcount(p) == 0
    with pytest.raises(MXNetError, match="double free"):
        a.free([p])
    assert a.free_count == 5                 # free list not corrupted
    # refcount below zero through the sharing path
    p = a.alloc()
    a.incref(p)
    assert a.refcount(p) == 2
    assert not a.decref(p)                   # still live (a sharer left)
    assert a.decref(p)                       # last ref → free list
    with pytest.raises(MXNetError, match="double free"):
        a.decref(p)
    # the null page is never freeable or shareable
    with pytest.raises(MXNetError, match="null page"):
        a.decref(NULL_PAGE)
    with pytest.raises(MXNetError, match="null page"):
        a.incref(NULL_PAGE)
    # sharing a free page would hand it to two owners
    with pytest.raises(MXNetError, match="incref on free page"):
        a.incref(p)
    # a page freed by its last sharer reappears exactly once
    q = a.alloc()
    a.incref(q)
    a.free([q, q])
    assert sorted(a._free).count(q) == 1


def test_prefix_index_radix_siblings_and_partial():
    """Two prompt families diverging at the SAME depth must both stay
    cached (radix siblings, not last-writer-wins), and a prompt ending
    mid-page matches the boundary page as a partial COPY capped at
    t0 - 1 tokens (the last token's logits must be recomputed)."""
    ps = 4
    a = PageAllocator(16)
    ix = PrefixIndex(ps)
    fam1 = np.arange(8, dtype=np.int32)              # pages [0-3],[4-7]
    fam2 = np.arange(100, 108, dtype=np.int32)       # diverges at page 0
    pg1 = [a.alloc(), a.alloc()]
    pg2 = [a.alloc(), a.alloc()]
    assert ix.insert(fam1, pg1, a) == 2
    assert ix.insert(fam2, pg2, a) == 2              # sibling kept
    # full-page match for a longer prompt of family 1
    shared, partial, cached = ix.match(np.arange(16, dtype=np.int32))
    assert shared == pg1 and partial is None and cached == 8
    # family 2 still matchable (the sibling survived)
    shared, partial, cached = ix.match(
        np.arange(100, 116, dtype=np.int32))
    assert shared == pg2 and cached == 8
    # prompt ending mid-page: boundary page is a partial-copy source
    shared, partial, cached = ix.match(np.arange(7, dtype=np.int32))
    assert shared == [pg1[0]]
    assert partial == (pg1[1], 2) and cached == 6    # capped < t0 = 7
    # a prompt that IS entirely cached still leaves its last token:
    # 8 tokens = 2 full pages, but only page 0 may be shared and the
    # boundary page contributes at most t0 - 1 - ps = 3 tokens
    shared, partial, cached = ix.match(np.arange(8, dtype=np.int32))
    assert shared == [pg1[0]]
    assert partial == (pg1[1], 3) and cached == 7
    # no match at all
    shared, partial, cached = ix.match(
        np.arange(500, 512, dtype=np.int32))
    assert shared == [] and partial is None and cached == 0


def test_prefix_index_reclaim_lru_and_flush():
    """reclaim frees LRU index-only pages (live-slot pages are skipped),
    evicting a parent cascades its unreachable descendants, and flush
    drops everything while slot-held pages survive via the slot refs."""
    ps = 4
    a = PageAllocator(16)
    ix = PrefixIndex(ps)
    fam1 = np.arange(8, dtype=np.int32)
    fam2 = np.arange(100, 108, dtype=np.int32)
    pg1 = [a.alloc(), a.alloc()]
    pg2 = [a.alloc(), a.alloc()]
    ix.insert(fam1, pg1, a)
    # touch family 1 so family 2 becomes the LRU chain
    ix.match(np.arange(12, dtype=np.int32))
    ix.insert(fam2, pg2, a)
    ix.match(np.arange(12, dtype=np.int32))
    # drop the slots' own refs — pages now held only by the index
    a.free(pg1 + pg2)
    free0 = a.free_count
    assert ix.reclaimable(a) == 4
    freed = ix.reclaim(1, a)
    # fam2's root page was LRU; evicting it cascades its child
    assert freed == 2 and a.free_count == free0 + 2
    assert ix.match(np.arange(100, 112, dtype=np.int32))[0] == []
    assert ix.match(np.arange(12, dtype=np.int32))[0] == pg1
    # a page still referenced by a live slot is not reclaimable
    a.incref(pg1[0])
    assert ix.reclaimable(a) == 1            # only the depth-1 page
    ix.flush(a)
    assert len(ix) == 0 and ix.flushes == 1
    assert a.refcount(pg1[0]) == 1           # the slot's ref survived
    a.free([pg1[0]])
    assert a.free_count == a.num_pages - 1


@pytest.mark.slow
def test_prefix_cache_hit_token_parity(model):
    """Requests sharing a persona prefix: later admissions must match
    the cached pages (hit counted, suffix-only prefill) and emit
    EXACTLY their solo tokens — shared pages are read-only, the
    boundary page is copied, so sharing is invisible to every request."""
    rng = np.random.RandomState(21)
    persona = rng.randint(0, 64, size=(20,)).astype(np.int32)
    prompts = [np.concatenate([persona,
                               rng.randint(0, 64, size=(5,)).astype(
                                   np.int32)]) for _ in range(3)]
    refs = [_solo_reference(model, p, 8) for p in prompts]
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    reqs = [Request(p.copy(), max_new_tokens=8) for p in prompts]
    eng.run(reqs)
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)
    assert eng.prefix_hits >= 1, "no admission ever hit the cache"
    assert eng.prefix_hit_tokens >= 16      # >= 2 full shared pages
    assert eng.copy_trace_count <= 1        # COW copy compiled once
    assert eng.decode_trace_count == 1
    assert all(v == 1 for v in eng.prefill_trace_counts.values()), \
        f"a prefill bucket retraced: {eng.prefill_trace_counts}"
    eng.audit_pages()


@pytest.mark.slow
def test_shared_pages_cross_slot_isolation(model):
    """Two same-persona requests decode CONCURRENTLY with the persona
    pages mapped into both page tables (one read-only shared mapping):
    each must still emit exactly its solo tokens, and a greedy request
    next to a hot-sampling one stays bit-identical (sharing must not
    leak sampling state either)."""
    rng = np.random.RandomState(22)
    persona = rng.randint(0, 64, size=(16,)).astype(np.int32)
    p1 = np.concatenate([persona, rng.randint(0, 64, size=(4,)).astype(
        np.int32)])
    p2 = np.concatenate([persona, rng.randint(0, 64, size=(7,)).astype(
        np.int32)])
    refs = [_solo_reference(model, p, 10) for p in (p1, p2)]
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    r1 = Request(p1.copy(), max_new_tokens=10)
    r2 = Request(p2.copy(), max_new_tokens=10, temperature=1.1)
    # same _admit pass: r1 cold-prefills + publishes, r2 hits and maps
    # the SAME physical pages while r1 is still live
    eng.run([r1, r2])
    assert eng.prefix_hits == 1
    np.testing.assert_array_equal(np.asarray(r1.token_ids, np.int32),
                                  refs[0])
    assert len(r2.token_ids) == 10
    # greedy parity for the sharer too (own run, fresh engine: both
    # slots greedy, r2 shares r1's persona pages)
    eng2 = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    r1b = Request(p1.copy(), max_new_tokens=10)
    r2b = Request(p2.copy(), max_new_tokens=10)
    eng2.run([r1b, r2b])
    assert eng2.prefix_hits == 1
    np.testing.assert_array_equal(np.asarray(r2b.token_ids, np.int32),
                                  refs[1])
    eng2.audit_pages()


@pytest.mark.slow   # 13-21s (round-10 tier-1 budget repair); ci stage_unit runs it
def test_warm_start_flushes_prefix_cache(model):
    """SATELLITE: after a weight swap a previously-cached prefix must
    not be served from stale K/V — the index is flushed (asserted), the
    same prompt re-admitted under new weights emits the NEW model's
    tokens, and the decode step keeps its single compile."""
    mx.random.seed(77)
    model_b = g.gpt_mini(vocab_size=64, max_length=64)
    model_b.initialize()
    rng = np.random.RandomState(23)
    prompt = rng.randint(0, 64, size=(20,)).astype(np.int32)
    ref_a = _solo_reference(model, prompt, 8)
    ref_b = _solo_reference(model_b, prompt, 8)
    # distinguishable models (otherwise staleness would be invisible)
    assert not np.array_equal(ref_a, ref_b)

    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    r0 = Request(prompt.copy(), max_new_tokens=8)
    eng.run([r0])                            # publishes the prefix
    r1 = Request(prompt.copy(), max_new_tokens=8)
    eng.run([r1])                            # served WITH the cache
    assert eng.prefix_hits == 1
    np.testing.assert_array_equal(np.asarray(r1.token_ids, np.int32),
                                  ref_a)

    params_b = {str(i): p.data().asnumpy() for i, p in
                enumerate(model_b.collect_params().values())}
    eng.warm_start(params=params_b)
    assert eng.prefix_flushes == 1
    assert len(eng._prefix) == 0, "warm_start left stale prefix entries"
    hits_before = eng.prefix_hits

    r2 = Request(prompt.copy(), max_new_tokens=8)
    eng.run([r2])
    # stale K/V would reproduce ref_a here; the flush forces a cold
    # prefill under the new weights
    np.testing.assert_array_equal(np.asarray(r2.token_ids, np.int32),
                                  ref_b)
    assert eng.prefix_hits == hits_before    # the re-admission was a miss
    assert eng.decode_trace_count == 1, "weight swap retraced decode"
    eng.audit_pages()


def test_chunk_config_validation(model):
    with pytest.raises(MXNetError, match="power of two"):
        InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                        chunk_pages=3)
    with pytest.raises(MXNetError, match="token_budget"):
        InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                        chunk_pages=2, token_budget=8)


@pytest.mark.slow
def test_chunked_prefill_respects_token_budget_and_interleaves(model):
    """A long-prompt arrival under chunked prefill must never process
    more than token_budget prompt tokens per engine step, and decode
    for already-live slots must keep advancing BETWEEN its chunks (the
    TPOT-freeze fix — a monolithic prefill would run to completion
    inside one admission)."""
    rng = np.random.RandomState(24)
    shorts = [Request(rng.randint(0, 64, size=(4,)).astype(np.int32),
                      max_new_tokens=24) for _ in range(2)]
    long_req = Request(rng.randint(0, 64, size=(40,)).astype(np.int32),
                       max_new_tokens=4)
    eng = InferenceEngine(model, num_slots=3, page_size=8, max_len=64,
                          prefix_cache=False, chunk_pages=1)
    for r in shorts:
        eng.submit(r)
    while any(not r.token_ids for r in shorts):
        eng.step()                           # shorts admitted + decoding
    ds0 = eng.decode_steps
    eng.submit(long_req)
    while not long_req.token_ids:
        eng.step()
    # 40 tokens / (1 page * 8) budget = 5 chunks → >= 5 steps, and the
    # shorts decoded through every one of them
    assert eng.decode_steps - ds0 >= 5
    assert min(len(r.token_ids) for r in shorts) >= 5
    assert eng.max_step_prefill_tokens <= eng.token_budget
    while any(eng._slots):
        eng.step()
    ref_long = _solo_reference(model, long_req.prompt_ids, 4)
    np.testing.assert_array_equal(
        np.asarray(long_req.token_ids, np.int32), ref_long)
    for r in shorts:
        np.testing.assert_array_equal(
            np.asarray(r.token_ids, np.int32),
            _solo_reference(model, r.prompt_ids, 24))
    assert eng.decode_trace_count == 1
    eng.audit_pages()


@pytest.mark.slow
@pytest.mark.parametrize("chunk_pages", [1, 2])
def test_chunked_prefill_parity_across_chunk_sizes(model, chunk_pages):
    """SATELLITE: chunked processing must emit bit-identical tokens to
    the monolithic path across chunk sizes {1 page, 2 pages} and
    prompts covering {sub-page, exact-page, odd-tail} lengths, at mixed
    occupancy with staggered arrivals. The oracle is the solo
    dense-cache decode — the same bar the monolithic engine meets, so
    equality here IS first-token parity with PR 2 prefill."""
    rng = np.random.RandomState(25)
    lens = (3, 16, 17, 9, 26)                # odd tails + exact pages
    news = (10, 6, 12, 8, 9)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in lens]
    refs = [_solo_reference(model, p, k) for p, k in zip(prompts, news)]
    eng = InferenceEngine(model, num_slots=3, page_size=8, max_len=64,
                          prefix_cache=False, chunk_pages=chunk_pages)
    reqs = [Request(p, max_new_tokens=k) for p, k in zip(prompts, news)]
    eng.run(reqs, arrival_times=[0.0, 0.0, 0.01, 0.02, 0.03])
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)
    assert eng.decode_trace_count == 1
    assert all(k[0] == "chunk" for k in eng.prefill_trace_counts), \
        "chunked engine ran a dense prefill"
    assert all(v == 1 for v in eng.prefill_trace_counts.values()), \
        f"a chunk bucket retraced: {eng.prefill_trace_counts}"
    assert eng.max_step_prefill_tokens <= eng.token_budget
    eng.audit_pages()


@pytest.mark.slow
def test_prefix_churn_accounting_no_leak_no_double_grant(model):
    """SATELLITE: churn admissions/evictions with shared prefixes
    through a POOL SMALL ENOUGH TO FORCE RECLAIM and audit after every
    step: every page is at all times either live-referenced (slots +
    index, refcount exact) or on the free list — no leak, no double
    grant. Token parity holds for every request despite the sharing and
    index evictions."""
    rng = np.random.RandomState(26)
    personas = [rng.randint(0, 64, size=(16,)).astype(np.int32)
                for _ in range(3)]
    prompts = [np.concatenate([personas[i % 3],
                               rng.randint(0, 64, size=(3 + i % 5,))
                               .astype(np.int32)])
               for i in range(9)]
    news = [4 + (i % 3) for i in range(9)]
    refs = [_solo_reference(model, p, k) for p, k in zip(prompts, news)]
    # worst case per request: ceil((23+6)/8)=4 pages; 2 slots → up to 8
    # live pages; 9 usable pages leaves no headroom for the 6 persona
    # pages the index wants to retain → admissions must reclaim
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          num_pages=10)
    for p, k in zip(prompts, news):
        eng.submit(Request(p.copy(), max_new_tokens=k))
    reqs = [r for r in eng._queue]
    steps = 0
    while eng._queue or eng.active_count:
        eng.step()
        eng.audit_pages()                    # invariant holds mid-churn
        steps += 1
        assert steps < 2000
    for req, ref in zip(reqs, refs):
        np.testing.assert_array_equal(np.asarray(req.token_ids,
                                                 np.int32), ref)
    assert eng.prefix_hits > 0
    assert eng.prefix_reclaimed_pages > 0, \
        "pool never pressured the index — test is not exercising reclaim"
    assert eng.decode_trace_count == 1
    eng.audit_pages()


# --------------------------------------------------------------------- #
# the pool's layout: keys | values of a head side by side, one pool a
# layer; the host format (tiers, capsules) keeps the two apart
# --------------------------------------------------------------------- #

def test_pool_layout_is_reported(model):
    """``kv_page_shape`` is the counter that says the fused layout is in
    force; a capsule's signature still names a key page's shape."""
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    H, D = eng._H, eng._D
    snap = eng.health_snapshot()
    assert snap["kv_page_shape"] == (H, 8, 2 * D)
    assert len(eng._kvpools) == model.num_layers
    assert all(p.shape == (eng.num_pages, H, 8, 2 * D)
               for p in eng._kvpools)
    assert snap["kv_pool_bytes"] == \
        model.num_layers * eng.num_pages * H * 8 * 2 * D * 4
    assert eng.kv_wire_sig()[3] == (H, 8, D)


def test_gathered_page_payload_is_the_host_format(model):
    """A page's payload leaves the device as separate key and value
    arrays of (H, ps, D), byte for byte the cache's contents: what the
    model wrote (the dense forward's own K and V of that page) and what
    ``_promote_page`` puts back, crc and all."""
    from incubator_mxnet_tpu.serve.paged_kv import payload_crc
    from incubator_mxnet_tpu.models.gpt import _qkv_heads
    rng = np.random.RandomState(41)
    prompt = rng.randint(0, 64, size=(17,)).astype(np.int32)
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    eng.submit(Request(prompt, max_new_tokens=8))
    eng.step()                               # prefill: 3 pages written
    slot, = [i for i, sl in enumerate(eng._slots) if sl is not None]
    page = int(eng._page_table[slot, 0])
    assert page != NULL_PAGE
    k_pl, v_pl, kamax, vamax = eng.gather_page(page)
    H, D = eng._H, eng._D
    assert kamax is None and vamax is None
    assert len(k_pl) == len(v_pl) == model.num_layers
    assert all(a.shape == (H, 8, D) and a.dtype == np.float32
               for a in (*k_pl, *v_pl))
    # layer 0's keys and values of the first page, from the model itself
    x = model.word_embed(nd.array(prompt[None, :8], dtype="int32")) + \
        model.position_embed(nd.array(np.arange(8)[None], dtype="int32"))
    _, k, v = _qkv_heads(model.block0.attn, model.block0.ln1(x))
    np.testing.assert_allclose(k_pl[0], np.asarray(k[0]).transpose(1, 0, 2),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(v_pl[0], np.asarray(v[0]).transpose(1, 0, 2),
                               rtol=1e-5, atol=1e-6)
    # in the pool the two sit side by side on the lanes
    pool0 = np.asarray(eng._kvpools[0][page])
    assert pool0[..., :D].tobytes() == k_pl[0].tobytes()
    assert pool0[..., D:].tobytes() == v_pl[0].tobytes()
    # promote into another page and gather again: the same bytes
    dst = eng._alloc.alloc()
    eng._promote_page(k_pl, v_pl, None, None, dst)
    k2, v2, _, _ = eng.gather_page(dst)
    assert [a.tobytes() for a in (*k2, *v2)] == \
        [a.tobytes() for a in (*k_pl, *v_pl)]
    assert payload_crc(k2, v2, None, None) == \
        payload_crc(k_pl, v_pl, None, None)
    eng._alloc.decref(dst)


def test_chunk_prefill_writes_through_the_engine_modules_global(
        model, monkeypatch):
    """The chunk-prefill program reaches ``write_token_kv`` through
    ``serve.engine``'s own global (the benchmark plants its
    never-written-page fault by patching it there), once a layer, with
    keys and values fused on the last axis."""
    from incubator_mxnet_tpu.serve import engine as eng_mod
    real, seen = eng_mod.write_token_kv, []

    def spy(pool, new, pages, offsets):
        seen.append((pool.shape, new.shape, pages.shape, offsets.shape))
        return real(pool, new, pages, offsets)

    monkeypatch.setattr(eng_mod, "write_token_kv", spy)
    rng = np.random.RandomState(42)
    prompt = rng.randint(0, 64, size=(19,)).astype(np.int32)
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          chunk_pages=1)
    req = Request(prompt, max_new_tokens=3)
    eng.run([req])
    H, D = eng._H, eng._D
    assert len(seen) == model.num_layers * len(eng.prefill_trace_counts)
    assert all(s == ((eng.num_pages, H, 8, 2 * D), (8, H, 2 * D), (8,),
                     (8,)) for s in seen), seen
    np.testing.assert_array_equal(np.asarray(req.token_ids, np.int32),
                                  _solo_reference(model, prompt, 3))


# --------------------------------------------------------------------- #
# a model with state layers (models/granite_hybrid.py): recurrent rows a
# sequence beside the page pool, grouped queries in the ragged kernels
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def hybrid():
    """(config, weights, model): 7 layers m m a m m m a at width 128, the
    slow-decay draw, so that state hundreds of positions old reaches the
    logits (tests/granite_hybrid_util.py)."""
    from granite_hybrid_util import build_model, draw_weights, tiny_config
    cfg = tiny_config()
    w = draw_weights(cfg, 5)
    return cfg, w, build_model(cfg, w)


def _hybrid_engine(model, **kw):
    args = dict(num_slots=3, page_size=8, max_len=128, prefix_cache=False,
                chunk_pages=2, token_budget=16)
    args.update(kw)
    return InferenceEngine(model, **args)


def _hybrid_requests(seed=1):
    rng = np.random.default_rng(seed)
    # prompt lengths that are no multiple of a page (8) or a chunk (16),
    # one shorter than a page, one of exactly a chunk
    return [Request(rng.integers(0, 256, n).astype(np.int32),
                    max_new_tokens=k, temperature=0.0, eos_id=-1)
            for n, k in ((37, 9), (5, 20), (16, 6), (23, 12), (50, 5))]


def _drive_staggered(eng, reqs):
    """Two at once, a third at step 3, two more at step 6: slots admitted
    at different steps, chunks interleaved with decode steps, and slots
    reused after a finished request."""
    for r in reqs[:2]:
        eng.submit(r)
    step = 0
    while eng._queue or eng.active_count:
        eng.step()
        step += 1
        if step == 3:
            eng.submit(reqs[2])
        if step == 6:
            eng.submit(reqs[3])
            eng.submit(reqs[4])
    eng.audit_pages()


def _logit_error(cfg, w, model, eng, reqs, drive):
    """Drive ``reqs`` through ``eng`` and return how far, at the widest,
    the logits its programs computed lie from the plain reference's full
    forward (the recurrence as a recurrence, no cache), over every position
    a token was served from and every position a prefill chunk ended at,
    with the count of positions compared. The programs' logits leave the
    trace through an ordered debug callback on the model's
    ``cached_forward``; which request and position a program's rows belong
    to is read off its arguments at the dispatch."""
    import jax
    import jax.numpy as jnp
    import granite_hybrid_reference as ref
    from granite_hybrid_util import Ops
    from incubator_mxnet_tpu.serve import engine as eng_mod
    out, seen = [], []                  # program logits; (request, pos, row)
    real_forward = model.cached_forward

    def forward(*a, **k):
        logits = real_forward(*a, **k)
        jax.debug.callback(lambda x: out.append(np.asarray(x)), logits,
                           ordered=True)
        return logits

    real_dispatch = eng_mod.InferenceEngine._dispatch

    def dispatch(self, name, fn, *args):
        slots = list(self._slots)
        res = real_dispatch(self, name, fn, *args)
        jax.effects_barrier()
        logits = out.pop()
        assert not out
        if name == "decode":            # args[7]: lengths; the token fed
            for s, slot in enumerate(slots):    # sits at position length
                if slot is not None and args[7][s] > 0:
                    seen.append((slot.request, int(args[7][s]),
                                 logits[s, 0]))
        else:                           # one slot's chunk or whole prompt
            last = int(args[5]) - 1 if name[0] == "dense" \
                else int(args[5]) + int(args[6]) - 1
            seen.append((slots[int(args[-1])].request, last, logits[0, 0]))
        return res

    model.cached_forward = forward
    eng_mod.InferenceEngine._dispatch = dispatch
    try:
        drive(eng, reqs)
    finally:
        del model.cached_forward
        eng_mod.InferenceEngine._dispatch = real_dispatch
    want = {}
    for r in reqs:
        ids = np.concatenate([np.asarray(r.prompt_ids, np.int32),
                              np.asarray(r.token_ids[:-1], np.int32)])
        want[r.request_id] = np.asarray(ref.logits_at(
            w, jnp.asarray(ids), jnp.arange(ids.size), cfg, Ops))
    assert {r.request_id for r, _, _ in seen} == set(want)
    served = sum(len(r.token_ids) for r in reqs)
    assert len(seen) >= served          # and the chunks that ended no prompt
    return max(float(np.abs(row - want[r.request_id][pos]).max())
               for r, pos, row in seen), len(seen)


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernels-interpreted"])
def test_hybrid_engine_agrees_with_the_reference_at_every_served_position(
        hybrid, interpret):
    """Chunked prefill (prompt lengths that are no multiple of a page or a
    chunk), then decoding through the page pool and the state cache, several
    slots admitted at different steps: the programs' LOGITS, not their
    tokens, against the plain reference at every served position and every
    chunk's end. float32 on both sides: 2e-7 covers sums taken in another
    order (the chunked scan, the fused projections, the paged softmax) on
    logits whose spread is 0.0024; read 7e-9. A slot reused after a
    finished request serves what a fresh engine serves."""
    cfg, w, model = hybrid
    eng = _hybrid_engine(model, interpret=interpret)
    reqs = _hybrid_requests()
    err, n = _logit_error(cfg, w, model, eng, reqs, _drive_staggered)
    assert err < 2e-7 and n >= 52 + 6, (err, n)
    assert all(r.outcome.name == "MAX_TOKENS" for r in reqs)
    assert eng.decode_trace_count == 1
    assert eng.state_zero_trace_count == 1
    assert set(eng.prefill_trace_counts.values()) == {1}
    # five requests through three slots: two slots served two requests;
    # each of the late ones alone on a fresh engine serves the same tokens
    fresh = _hybrid_engine(model, interpret=interpret)
    again = _hybrid_requests()[3:]
    fresh.run(again)
    assert [r.token_ids for r in again] == [r.token_ids for r in reqs[3:]]


def test_hybrid_dense_prefill_serves_what_chunked_prefill_serves(hybrid):
    _, _, model = hybrid
    served = []
    for kw in ({"chunk_pages": None, "token_budget": None}, {}):
        eng = _hybrid_engine(model, **kw)
        reqs = _hybrid_requests(seed=2)
        eng.run(reqs)
        served.append([r.token_ids for r in reqs])
    assert served[0] == served[1]


def test_hybrid_dead_and_prefilling_slots_keep_their_state_bit_for_bit(
        hybrid):
    """A decode step runs every slot; the rows of a slot that is dead, or
    still prefilling, come back as they went in. The kernels interpreted:
    the state update skips what is not live."""
    _, _, model = hybrid
    eng = _hybrid_engine(model, interpret=True)
    rng = np.random.default_rng(3)
    eng.submit(Request(rng.integers(0, 256, 9).astype(np.int32),
                       max_new_tokens=8, temperature=0.0, eos_id=-1))
    eng.step()                            # admitted, prefilled: slot 0 live
    # poison slot 2 (dead) with a pattern; admit a long prompt into slot 1
    eng._states = tuple({k: a.at[2].set(7.25) for k, a in rows.items()}
                        for rows in eng._states)
    eng.submit(Request(rng.integers(0, 256, 60).astype(np.int32),
                       max_new_tokens=4, temperature=0.0, eos_id=-1))
    eng.step()                            # slot 1 mid-prefill, slot 0 decodes
    assert eng._slots[1] is not None and eng._slots[1].prefilling
    mid = [{k: np.asarray(a[1]) for k, a in rows.items()}
           for rows in eng._states]
    before0 = np.asarray(eng._states[0]["ssm"][0])
    tok = len(eng._slots[0].request.token_ids)
    # a step with no chunk budget left for slot 1: decode only
    eng._advance_prefill = lambda: 0
    eng.step()
    assert len(eng._slots[0].request.token_ids) == tok + 1
    assert not np.array_equal(np.asarray(eng._states[0]["ssm"][0]), before0)
    for rows, was in zip(eng._states, mid):
        for k, a in rows.items():
            np.testing.assert_array_equal(np.asarray(a[2]), 7.25)
            np.testing.assert_array_equal(np.asarray(a[1]), was[k])


@pytest.mark.parametrize("fault", ["never-zeroed", "carry-dropped"])
def test_hybrid_state_faults_show_in_the_logits(hybrid, monkeypatch, fault):
    """What the agreement test above can see: state left from a slot's last
    occupant, or a chunk that starts from nothing, moves a logit by more
    than ten thousand times that test's tolerance (read 6.4e-3 and 8.2e-3,
    more than the logits' spread)."""
    from incubator_mxnet_tpu.serve import engine as eng_mod
    cfg, w, model = hybrid
    if fault == "never-zeroed":
        monkeypatch.setattr(eng_mod.InferenceEngine, "_zero_state",
                            lambda self, slot_idx: False)
    else:
        import jax.numpy as jnp
        real = eng_mod.read_slot_rows
        monkeypatch.setattr(
            eng_mod, "read_slot_rows", lambda rows, slot: {
                k: jnp.zeros_like(a) for k, a in real(rows, slot).items()})
    eng = _hybrid_engine(model, num_slots=1)
    err, _ = _logit_error(cfg, w, model, eng, _hybrid_requests(),
                          lambda e, reqs: e.run(reqs))
    assert err > 1e-3, err


@pytest.mark.parametrize("interpret", [False, True],
                         ids=["jnp", "kernels-interpreted"])
def test_hybrid_state_kept_in_bfloat16_shows_in_the_logits(hybrid,
                                                           interpret):
    """``state_dtype`` is what the cache keeps the recurrent state in, and
    float32 is what the agreement above rests on: with the rows in bfloat16
    (the benchmark's bf16-state control, benchmark/tests/state_faults.py) the
    engine serves every request and its logits lie twenty times that test's
    tolerance from the reference: read 4.2e-6 on logits of spread 0.0024. That
    is a thousandth of what a lost state moves them by, and a hundredth of
    what bfloat16 matrices do: a check of served tokens under bfloat16
    matrices cannot tell this state from float32 (PERF.md, Findings, PR 36)."""
    from granite_hybrid_util import build_model
    cfg, w, _ = hybrid
    cfg = dict(cfg, state_dtype="bfloat16")
    model = build_model(cfg, w)
    eng = _hybrid_engine(model, interpret=interpret)
    assert eng.health_snapshot()["state_row_shapes"]["ssm"][1] == "bfloat16"
    reqs = _hybrid_requests()
    err, _ = _logit_error(cfg, w, model, eng, reqs, _drive_staggered)
    assert all(r.outcome.name == "MAX_TOKENS" for r in reqs)
    assert 1e-6 < err < 1e-4, err


@pytest.mark.parametrize("kw,why", [
    ({"prefix_cache": True}, "prefix_cache"),
    ({"spec_k": 2}, "spec_k"),
    ({"prefix_cache": True, "kv_tiers": {"dram_bytes": 1 << 20}},
     "prefix_cache"),
    ({"kv_tiers": {"dram_bytes": 1 << 20}}, "kv_tiers"),
    ({"kv_quant": "int8"}, "kv_quant"),
    ({"mesh": "tp2"}, "mesh"),
], ids=["prefix_cache", "spec_k", "prefix+tiers", "kv_tiers", "kv_quant",
        "mesh"])
def test_hybrid_engine_refuses_what_assumes_a_position_can_be_shared(
        hybrid, kw, why):
    _, _, model = hybrid
    if kw.get("mesh") == "tp2":
        import jax
        from incubator_mxnet_tpu.parallel import mesh as pmesh
        kw = {"mesh": pmesh.build_mesh(devices=jax.devices()[:2],
                                       axis_sizes={"tp": 2})}
    with pytest.raises(MXNetError, match="state layers.*" + why):
        _hybrid_engine(model, **kw)


def test_hybrid_engine_refuses_page_capsules(hybrid):
    """A capsule carries pages; a slot resumed without its state rows would
    serve wrong tokens silently, so capture and install raise."""
    from incubator_mxnet_tpu.serve.transport import PageTransport
    _, _, model = hybrid
    src, dst = _hybrid_engine(model), _hybrid_engine(model)
    req = Request(np.arange(9, dtype=np.int32), max_new_tokens=8,
                  temperature=0.0, eos_id=-1)
    src.submit(req)
    src.step()
    src.step()
    with pytest.raises(MXNetError, match="state layers"):
        PageTransport().capture(src, req.request_id)
    with pytest.raises(MXNetError, match="state layers"):
        dst.install_slot(req, [], 9, np.zeros(2, np.uint32))
    src.run([])
    src.audit_pages()


def test_hybrid_engine_reads_the_model_through_its_seam_only(hybrid):
    """The second user of the seam: ``cache_layout`` says which layers keep
    what, ``cached_forward`` takes ``attend``, ``state`` and ``real``."""
    _, _, model = hybrid
    served = []
    for m in (model, _SeamOnly(model)):
        eng = _hybrid_engine(m)
        reqs = _hybrid_requests(seed=4)[:3]
        eng.run(reqs)
        eng.audit_pages()
        served.append([list(r.token_ids) for r in reqs])
    assert served[0] == served[1]
    snap = eng.health_snapshot()
    assert snap["state_layers"] == 5 and snap["kv_page_shape"] == (2, 8, 64)
    assert snap["state_row_shapes"] == {"ssm": ((1, 16, 128), "float32"),
                                        "conv": ((3, 160), "float32")}
    assert snap["state_cache_bytes"] == 5 * 3 * (16 * 128 + 3 * 160) * 4


def test_gpt_engine_keeps_no_state_cache(model):
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    snap = eng.health_snapshot()
    assert (snap["state_layers"], snap["state_row_shapes"],
            snap["state_cache_bytes"]) == (0, {}, 0)
    assert model.cache_layout() == [
        {"kind": "kv", "kv_heads": 4, "head_dim": 32,
         "scale": 32 ** -0.5}] * model.num_layers


def test_hybrid_preempted_request_resumes_from_a_zeroed_state(hybrid):
    """A preempted request comes back by re-prefilling its prompt and the
    tokens it had, into a slot whose state is zeroed again: it ends with the
    tokens of a run that was never preempted."""
    from incubator_mxnet_tpu.serve import EventType
    _, _, model = hybrid
    rng = np.random.default_rng(6)
    prompt = rng.integers(0, 256, 21).astype(np.int32)
    served = []
    for preempt in (False, True):
        eng = _hybrid_engine(model, num_slots=1)
        req = Request(prompt, max_new_tokens=12, temperature=0.0, eos_id=-1)
        eng.submit(req)
        for _ in range(6):
            eng.step()
        assert 2 <= len(req.token_ids) < 12
        if preempt:
            eng._preempt(0, "test")
            assert eng.active_count == 0
        eng.run([])
        eng.audit_pages()
        served.append(list(req.token_ids))
        admits = eng.flight.events(etype=EventType.ADMIT)
        assert [e.data["state_zeroed"] for e in admits] == \
            [True] * (2 if preempt else 1)
    assert served[0] == served[1] and len(served[0]) == 12

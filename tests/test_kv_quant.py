"""Quantized KV-cache serving tests (serve/paged_kv.py quantized
layout + serve/engine.py ``kv_quant`` plumbing).

The load-bearing claims: (1) int8 pools serve greedy decode through
the SAME one-compile programs (decode/verify/prefill trace counts
unchanged); (2) prefix sharing, COW boundary-page copy, refcounts,
reclaim and ``audit_pages`` operate unchanged on quantized pages —
the per-page scale is page metadata, shared exactly like the page;
(3) a recycled page's scale is reset (a quarantined slot's poisoned
scale dies with the page); (4) ``warm_start`` still flushes (cached
quantized K/V is weight-dependent); (5) the guard quarantines a
poisoned SCALE — the quantized pool's non-finite channel — without
recording a garbage token; (6) the trainer's opt-in int8 allreduce
leaves the non-finite guard verdict intact."""

import numpy as np
import pytest

import jax.numpy as jnp

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models import gpt as g
from incubator_mxnet_tpu.serve import InferenceEngine, Request
from incubator_mxnet_tpu.serve.paged_kv import (NULL_PAGE, kv_quant_spec,
                                                page_scales,
                                                write_prompt_kv_q,
                                                write_token_kv_q)


@pytest.fixture(scope="module")
def model():
    mx.random.seed(0)
    m = g.gpt_mini(vocab_size=64, max_length=64)
    m.initialize()
    return m


def _eng(model, **kw):
    cfg = dict(num_slots=3, page_size=8, max_len=64, kv_quant="int8")
    cfg.update(kw)
    return InferenceEngine(model, **cfg)


def test_quantized_engine_single_request_contracts(model):
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, 64, size=(7,)).astype(np.int32)
    eng = _eng(model)
    req = Request(prompt, max_new_tokens=12)
    eng.run([req])
    assert req.outcome is not None and req.outcome.ok
    assert len(req.token_ids) == 12
    assert all(0 <= t < 64 for t in req.token_ids)
    assert eng.decode_trace_count == 1
    eng.audit_pages()
    snap = eng.health_snapshot()
    assert snap["kv_dtype"] == "int8" and snap["kv_quant"] == "int8"
    assert snap["kv_quantized_pages"] == \
        eng.num_pages - 1 - snap["free_pages"]


def test_quantized_cache_hit_reuses_shared_pages_bit_identically(model):
    """The SAME prompt twice on a chunked quantized engine: the second
    admission must hit the prefix index, map the cached int8 pages
    (and their scales) read-only, and compile NOTHING new (chunked
    mode so cold and hit share the chunk programs — the same warmup
    discipline serve_bench uses on the f32 engine). On this fixed
    seed the emissions also agree exactly — the contract gate is the
    hit + zero-compile pair; the token agreement documents that the
    cached codes serve the hit as well as a cold rewrite would."""
    rng = np.random.RandomState(2)
    prompt = rng.randint(0, 64, size=(19,)).astype(np.int32)
    eng = _eng(model, chunk_pages=1)
    r1 = Request(prompt, max_new_tokens=8)
    eng.run([r1])
    traces = (eng.decode_trace_count, eng.prefill_trace_count,
              eng.copy_trace_count)
    hits0 = eng.prefix_hits
    r2 = Request(prompt.copy(), max_new_tokens=8)
    eng.run([r2])
    assert eng.prefix_hits == hits0 + 1
    assert (eng.decode_trace_count, eng.prefill_trace_count,
            eng.copy_trace_count) == traces
    np.testing.assert_array_equal(np.asarray(r1.token_ids),
                                  np.asarray(r2.token_ids))
    eng.audit_pages()


def test_quantized_shared_page_read_only_under_concurrency(model):
    """Two live persona-sharing slots: the shared full prefix pages
    carry refcount >= 2 mid-flight (one scale serving both readers)
    and the first requester's tokens match its solo quantized run —
    a sharer's COW copy never perturbs the cached original."""
    rng = np.random.RandomState(3)
    head = rng.randint(0, 64, size=(16,)).astype(np.int32)  # 2 pages
    tail1 = rng.randint(0, 64, size=(5,)).astype(np.int32)
    tail2 = rng.randint(0, 64, size=(6,)).astype(np.int32)
    p1 = np.concatenate([head, tail1])
    p2 = np.concatenate([head, tail2])

    solo = _eng(model)
    s1 = Request(p1, max_new_tokens=8)
    solo.run([s1])

    eng = _eng(model)
    r1 = Request(p1, max_new_tokens=8)
    r2 = Request(p2, max_new_tokens=8)
    seen_shared = []

    def before(e, i):
        live = [s for s in e._slots if s is not None]
        if len(live) == 2:
            rcs = [e._alloc.refcount(int(p))
                   for s in live for p in s.row if int(p) != NULL_PAGE]
            seen_shared.append(max(rcs))

    eng.run([r1, r2], arrival_times=[0.0, 0.0], before_step=before)
    assert seen_shared and max(seen_shared) >= 2
    assert eng.prefix_hits >= 1          # r2 re-landed on r1's pages
    np.testing.assert_array_equal(np.asarray(r1.token_ids),
                                  np.asarray(s1.token_ids))
    eng.audit_pages()


def test_cow_partial_page_copy_requantizes_correctly():
    """The mechanics under the engine's COW path: copying a page's
    CODES verbatim with its scale preserves content exactly; suffix
    writes into the private copy grow the scale and requantize in
    place, leaving the copied prefix rows within the NEW quantum (the
    old rows pay at most one extra rounding, never saturation)."""
    spec = kv_quant_spec("int8")
    rng = np.random.RandomState(4)
    H, ps, D, P = 2, 8, 4, 6
    # the fused pool: key codes | value codes on the lanes, each half
    # of a page under its own scale; the values run 10x the keys, so a
    # shared scale would cost the keys three bits
    half = np.concatenate([np.ones(D), 10.0 * np.ones(D)]).astype(
        np.float32)
    pool = jnp.zeros((P, H, ps, 2 * D), spec.dtype)
    kamax = vamax = jnp.zeros((P,))
    # page 1: the cached boundary page, 5 of 8 rows meaningful
    rows = rng.randn(ps, H, 2 * D).astype(np.float32) * half
    pool, kamax, vamax = write_prompt_kv_q(
        pool, kamax, vamax, jnp.asarray(rows),
        jnp.asarray([1], jnp.int32), spec)
    assert float(vamax[1]) > 3 * float(kamax[1])

    def lane_scales(ka, va, page):
        sk = float(page_scales(jnp.asarray(ka), spec)[page])
        sv = float(page_scales(jnp.asarray(va), spec)[page])
        return np.concatenate([sk * np.ones(D), sv * np.ones(D)])

    # COW: codes copied verbatim, scales copied (engine._copy_page)
    pool = pool.at[2].set(pool[1])
    kamax, vamax = np.array(kamax), np.array(vamax)
    kamax[2], vamax[2] = kamax[1], vamax[1]
    s_before = lane_scales(kamax, vamax, 2)
    deq_before = np.asarray(pool[2], np.float32) * s_before
    np.testing.assert_array_equal(
        deq_before, np.asarray(pool[1], np.float32) * s_before)
    # suffix writes (rows 5..7) 4x hotter than the cached prefix
    suffix = (4.0 * rng.randn(3, H, 2 * D)).astype(np.float32) * half
    page1 = np.asarray(pool[1])
    pool, kamax2, vamax2 = write_token_kv_q(
        pool, jnp.asarray(kamax), jnp.asarray(vamax), jnp.asarray(suffix),
        jnp.asarray([2, 2, 2], jnp.int32),
        jnp.asarray([5, 6, 7], jnp.int32), spec)
    s_after = lane_scales(kamax2, vamax2, 2)
    assert (s_after >= s_before).all()
    deq_after = np.asarray(pool[2], np.float32) * s_after
    # prefix rows: original value ± (old quantum/2 + new quantum/2),
    # each half by its own quantum
    prefix_vals = np.moveaxis(rows[:5], 0, 1)     # (H, 5, 2D)
    assert (np.abs(deq_after[:, :5] - prefix_vals) <=
            s_before / 2 + s_after / 2 + 1e-6).all()
    # suffix rows: fresh quantization at the grown scales
    suffix_vals = np.moveaxis(suffix, 0, 1)       # (H, 3, 2D)
    assert (np.abs(deq_after[:, 5:] - suffix_vals) <=
            s_after / 2 + 1e-6).all()
    # the cached original is untouched
    np.testing.assert_array_equal(np.asarray(pool[1]), page1)
    assert kamax2[1] == kamax[1] and vamax2[1] == vamax[1]


def test_quantized_cow_boundary_page_end_to_end(model):
    """A prompt sharing a PARTIAL boundary page with a cached prompt:
    admission must COW-copy the boundary page (codes + scale), compile
    the copy program once, and both requests complete cleanly with
    exact page accounting."""
    rng = np.random.RandomState(5)
    head = rng.randint(0, 64, size=(12,)).astype(np.int32)  # 1.5 pages
    p1 = np.concatenate([head,
                         rng.randint(0, 64, size=(4,)).astype(np.int32)])
    p2 = np.concatenate([head,
                         rng.randint(0, 64, size=(6,)).astype(np.int32)])
    eng = _eng(model, chunk_pages=1)
    r1 = Request(p1, max_new_tokens=6)
    eng.run([r1])
    r2 = Request(p2, max_new_tokens=6)
    eng.run([r2])
    assert eng.copy_trace_count == 1     # the COW program, once
    assert eng.prefix_hits >= 1
    for r in (r1, r2):
        assert r.outcome is not None and r.outcome.ok
        assert len(r.token_ids) == 6
    eng.audit_pages()


def test_warm_start_flushes_quantized_prefix_cache(model):
    """Weights changed ⇒ every cached quantized page (and its scale)
    is stale: warm_start must flush the index exactly as on the f32
    engine, and serving must continue without retracing."""
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 64, size=(17,)).astype(np.int32)
    eng = _eng(model)
    eng.run([Request(prompt, max_new_tokens=6)])
    assert len(eng._prefix) > 0
    flushes0 = eng.prefix_flushes
    traces = (eng.decode_trace_count, eng.prefill_trace_count)
    params = {str(i): p.data().asnumpy()
              for i, p in enumerate(eng._eng_params)}
    eng.warm_start(params=params)
    assert eng.prefix_flushes == flushes0 + 1
    assert len(eng._prefix) == 0
    r = Request(prompt.copy(), max_new_tokens=6)
    eng.run([r])
    assert r.outcome is not None and r.outcome.ok
    assert (eng.decode_trace_count, eng.prefill_trace_count) == traces
    eng.audit_pages()


def test_corrupt_scale_quarantines_and_page_reuse_is_clean(model):
    """The quantized pool's corruption channel end-to-end: a NaN
    scale on a live page must quarantine exactly the mapping slot at
    its next decode step with NOTHING from the poisoned step recorded;
    the freed page's scale is reset on reallocation, so a later
    request reusing the page completes cleanly."""
    from incubator_mxnet_tpu.serve.chaos import (CorruptPageScale,
                                                 run_chaos)
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in (9, 13)]
    # 8 usable pages: both faulted requests fit concurrently (3 + 3
    # worst-case pages) and the follow-up request below must sweep the
    # WHOLE pool — the poisoned page cannot dodge reallocation
    kw = dict(num_slots=2, prefix_cache=False, num_pages=9)
    base_eng = _eng(model, **kw)
    base = [Request(p, max_new_tokens=10) for p in prompts]
    base_eng.run(base)
    baseline = [list(r.token_ids) for r in base]

    eng = _eng(model, **kw)
    reqs = [Request(p.copy(), max_new_tokens=10) for p in prompts]
    inj = CorruptPageScale(at_step=3, mode="nan", shared=False, seed=1)
    run_chaos(eng, reqs, [inj], audit_every_step=True)
    assert inj.fired
    assert eng.quarantined == len(inj.affected) >= 1
    aff = {id(r) for r in inj.affected}
    for r, toks in zip(reqs, baseline):
        if id(r) in aff:
            from incubator_mxnet_tpu.serve import Outcome
            assert r.outcome == Outcome.FAILED_NONFINITE
            # no garbage token: a clean prefix of the fault-free run
            assert list(r.token_ids) == toks[:len(r.token_ids)]
        else:
            assert r.outcome is not None and r.outcome.ok
            assert list(r.token_ids) == toks
    # the poisoned page is back on the free list with its NaN amax
    # still in place — harmless while unmapped, and it must be RESET
    # when reallocated: this request's worst case spans all 8 usable
    # pages, so admission reallocates the poisoned page too
    assert any(not np.isfinite(a[inj.page]) for a in eng._kamax)
    r3 = Request(rng.randint(0, 64, size=(32,)).astype(np.int32),
                 max_new_tokens=32)
    eng.run([r3])
    assert r3.outcome is not None and r3.outcome.ok
    eng.audit_pages()
    assert np.isfinite(np.concatenate(
        [a for a in eng._kamax] + [a for a in eng._vamax])).all()


def test_corrupt_scale_injector_refuses_unquantized_engine(model):
    from incubator_mxnet_tpu.serve.chaos import CorruptPageScale
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    inj = CorruptPageScale(at_step=0, mode="nan")
    with pytest.raises(MXNetError):
        inj.on_step(eng, 0)


def test_trainer_int8_allreduce_guard_verdict_unaffected():
    """A non-finite gradient through the int8-compressed bucketed
    pushpull must still skip the step (verdict on the DEQUANTIZED
    result) with every parameter bit-identical."""
    from incubator_mxnet_tpu import autograd, nd
    from incubator_mxnet_tpu.gluon import Trainer, nn
    from incubator_mxnet_tpu.train.outcomes import StepOutcome
    mx.random.seed(8)
    net = nn.HybridSequential()
    net.add(nn.Dense(8, activation="relu"), nn.Dense(1))
    net.initialize()
    x = nd.array(np.random.RandomState(0).randn(4, 3).astype(np.float32))
    y = nd.array(np.random.RandomState(1).randn(4, 1).astype(np.float32))
    tr = Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1},
                 kvstore="device", int8_allreduce=True, guard=True)
    # clean step: applied, grads travelled quantized
    with autograd.record():
        loss = ((net(x) - y) ** 2).mean()
    loss.backward()
    tr.step(1)
    assert tr.last_outcome is StepOutcome.APPLIED
    assert tr.int8_buckets >= 1
    # poisoned step: skipped, params untouched
    with autograd.record():
        loss = ((net(x) - y) ** 2).mean()
    loss.backward()
    p0 = list(net.collect_params().values())[0]
    before = {p.name: p.data().asnumpy().copy()
              for p in net.collect_params().values()}
    p0.grad()._data = p0.grad()._data.at[0, 0].set(jnp.nan)
    tr.step(1)
    assert tr.last_outcome is StepOutcome.SKIPPED_NONFINITE
    for p in net.collect_params().values():
        np.testing.assert_array_equal(before[p.name],
                                      p.data().asnumpy())


def test_kv_quant_spec_validation():
    assert kv_quant_spec(None) is None
    assert kv_quant_spec("none") is None
    assert kv_quant_spec("int8").qmax == 127.0
    with pytest.raises(MXNetError):
        kv_quant_spec("int4")

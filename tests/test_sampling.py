"""Sampling-menu tests (serve/sampling.py + the engine wiring).

The load-bearing claims (round 18, docs/SERVING.md "Sampling"):

  1. NEUTRAL IS IDENTITY — a request with top_k=0 / top_p=1.0 /
     penalties off / no bias / no mask emits tokens BIT-IDENTICAL to
     an engine that never saw a ``SamplingParams`` (greedy AND
     temperature paths), and ``constrain_logits`` itself is a value
     identity at neutral knobs;
  2. COMPILE DISCIPLINE — every parameter combination is pure
     per-slot data: decode/verify trace counts stay exactly 1 across
     mixed knob/grammar/penalty traffic (no retrace, ever);
  3. determinism — equal-seed engines emit identical tokens under
     every new knob, and a preempted request with penalties/stops
     resumes bit-identically;
  4. semantics — top-k=1 equals greedy, a strongly-biased-out token
     never appears, stop sequences truncate exactly and terminate
     with ``Outcome.STOP``, grammar-constrained output is always a
     sentence of the grammar (speculation on or off);
  5. DISTRIBUTION CORRECTNESS — under top-p-truncated targets with a
     point-mass draft proposal, the speculative engine's emission
     distribution matches the non-speculative engine's (the PR-6
     rejection-sampling theorem extended to truncated/masked
     proposals).
"""

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.base import MXNetError
from incubator_mxnet_tpu.models import gpt as g
from incubator_mxnet_tpu.serve import (InferenceEngine, Outcome,
                                       Request, SamplingParams,
                                       TokenFsm, choice_grammar)
from incubator_mxnet_tpu.serve.sampling import (constrain_logits,
                                                grammar_mask,
                                                match_stop)


@pytest.fixture(scope="module")
def model():
    mx.random.seed(0)
    m = g.gpt_mini(vocab_size=64, max_length=64)
    m.initialize()
    return m


def _eng(model, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_len", 64)
    kw.setdefault("recorder", False)
    return InferenceEngine(model, **kw)


def _run(eng, prompts, max_new=10, **req_kw):
    reqs = [Request(p, max_new_tokens=max_new, **req_kw)
            for p in prompts]
    eng.run(reqs)
    return reqs


# --------------------------------------------------------------------- #
# constrain_logits units (jnp, no engine)
# --------------------------------------------------------------------- #

def _neutral_args(shape, V):
    z = np.zeros(shape, np.float32)
    return dict(temps=np.float32(0.7) if shape == () else z + 0.7,
                counts=np.zeros(shape + (V,), np.int32),
                bias=np.zeros(shape + (V,), np.float32),
                mask=np.ones(shape + (V,), bool),
                top_k=np.zeros(shape, np.int32),
                top_p=np.ones(shape, np.float32),
                rep_pen=np.ones(shape, np.float32),
                pres_pen=np.zeros(shape, np.float32))


def test_constrain_logits_neutral_is_value_identity():
    rng = np.random.RandomState(0)
    for shape in ((), (3,), (2, 4)):
        logits = rng.randn(*(shape + (16,))).astype(np.float32)
        out = np.asarray(constrain_logits(logits,
                                          **_neutral_args(shape, 16)))
        assert np.array_equal(out, logits), shape


def test_constrain_logits_topk_and_topp_oracle():
    rng = np.random.RandomState(1)
    V = 16
    logits = rng.randn(V).astype(np.float32)
    args = _neutral_args((), V)
    # top-k: exactly the k largest survive
    for k in (1, 3, 7):
        a = dict(args, top_k=np.int32(k))
        out = np.asarray(constrain_logits(logits, **a))
        kept = np.nonzero(out > -1e29)[0]
        want = np.argsort(logits)[-k:]
        assert set(kept) == set(want), k
        assert np.array_equal(out[kept], logits[kept])
    # top-p: smallest prefix of descending probs with mass >= p
    temp = 0.7
    probs = np.exp(logits / temp) / np.exp(logits / temp).sum()
    order = np.argsort(-probs)
    for p in (0.3, 0.6, 0.9):
        a = dict(args, top_p=np.float32(p), temps=np.float32(temp))
        out = np.asarray(constrain_logits(logits, **a))
        kept = set(np.nonzero(out > -1e29)[0])
        csum = 0.0
        want = set()
        for t in order:
            want.add(int(t))
            csum += probs[t]
            if csum >= p:
                break
        assert kept == want, p


def test_constrain_logits_penalties_bias_and_mask():
    V = 8
    logits = np.array([2.0, 1.0, -1.0, 0.5, 0.0, -2.0, 3.0, 1.5],
                      np.float32)
    args = _neutral_args((), V)
    # repetition penalty: seen positive logits divided, negative
    # multiplied; unseen untouched
    counts = np.zeros((V,), np.int32)
    counts[[0, 2]] = 1
    a = dict(args, counts=counts, rep_pen=np.float32(2.0))
    out = np.asarray(constrain_logits(logits, **a))
    assert out[0] == pytest.approx(1.0)      # 2.0 / 2
    assert out[2] == pytest.approx(-2.0)     # -1.0 * 2
    assert np.array_equal(out[[1, 3, 4, 5, 6, 7]],
                          logits[[1, 3, 4, 5, 6, 7]])
    # presence penalty: flat subtraction from seen
    a = dict(args, counts=counts, pres_pen=np.float32(0.5))
    out = np.asarray(constrain_logits(logits, **a))
    assert out[0] == pytest.approx(1.5) and out[2] == pytest.approx(-1.5)
    # bias adds; mask wins over everything
    bias = np.zeros((V,), np.float32)
    bias[4] = 5.0
    mask = np.ones((V,), bool)
    mask[6] = False
    a = dict(args, bias=bias, mask=mask)
    out = np.asarray(constrain_logits(logits, **a))
    assert out[4] == pytest.approx(5.0)
    assert out[6] < -1e29


def test_grammar_mask_survives_topk_topp_truncation():
    """Review regression: the mask is applied BEFORE top-k/top-p, so
    both truncations operate within the legal set. Applied after, a
    grammar-forbidden argmax + top_k=1 floored the ENTIRE vocab at
    -1e30 and sampling collapsed to uniform garbage (categorical over
    a constant vector)."""
    V = 16
    logits = np.arange(V, dtype=np.float32)      # argmax = 15
    mask = np.zeros((V,), bool)
    mask[[2, 5]] = True                          # argmax forbidden
    args = _neutral_args((), V)
    # top_k=1: the single survivor must be the best LEGAL token
    out = np.asarray(constrain_logits(
        logits, **dict(args, mask=mask, top_k=np.int32(1))))
    assert list(np.nonzero(out > -1e29)[0]) == [5]
    assert out[5] == logits[5]
    # a nucleus smaller than the legal set: computed over legal mass
    out = np.asarray(constrain_logits(
        logits, **dict(args, mask=mask, top_p=np.float32(0.05),
                       temps=np.float32(1.0))))
    assert set(np.nonzero(out > -1e29)[0]) == {5}
    # k larger than the legal set: the whole legal set survives
    out = np.asarray(constrain_logits(
        logits, **dict(args, mask=mask, top_k=np.int32(8))))
    assert set(np.nonzero(out > -1e29)[0]) == {2, 5}


@pytest.mark.parametrize("spec_k", [0, 3])
def test_grammar_with_truncation_stays_in_language(model, spec_k):
    """Grammar combined with aggressive top-k/top-p (the combination
    the review found collapsing to uniform off-grammar emissions) must
    still emit a sentence of the grammar, with or without
    speculation."""
    sequences = [[1, 2, 3, 1, 2], [5, 6], [5, 7, 8]]
    gram = choice_grammar(sequences, 64)
    want = {tuple(s) for s in sequences}
    rng = np.random.RandomState(12)
    for sp in (SamplingParams(grammar=gram, top_k=1),
               SamplingParams(grammar=gram, top_p=0.05)):
        eng = _eng(model, num_slots=2, spec_k=spec_k)
        reqs = _run(eng,
                    [rng.randint(0, 64, size=(5 + i,)).astype(np.int32)
                     for i in range(2)],
                    max_new=10, eos_id=9, temperature=1.0, seed=21,
                    sampling=sp)
        for r in reqs:
            assert r.outcome is Outcome.EOS, (r.outcome, r.token_ids)
            assert tuple(r.token_ids[:-1]) in want, r.token_ids
        assert eng.decode_trace_count <= 1
        assert eng.verify_trace_count <= 1
        eng.audit_pages()


def test_grammar_primitives():
    gram = choice_grammar([[1, 2, 3], [1, 4]], vocab_size=8)
    st = gram.start()
    assert set(np.nonzero(gram.allowed(st))[0]) == {1}
    st = gram.advance(st, 1)
    assert set(np.nonzero(gram.allowed(st))[0]) == {2, 4}
    assert not gram.accepting(st)
    leaf = gram.advance(st, 4)
    assert gram.accepting(leaf)
    # leaf: no outgoing -> mask forces EOS
    m = grammar_mask(gram, leaf, eos_id=7)
    assert set(np.nonzero(m)[0]) == {7}
    # mid-state with eos disallowed (not accepting)
    m = grammar_mask(gram, st, eos_id=7)
    assert set(np.nonzero(m)[0]) == {2, 4}
    with pytest.raises(MXNetError):
        choice_grammar([], 8)
    with pytest.raises(MXNetError):
        TokenFsm(4, {0: {9: 0}})             # token outside vocab


def test_match_stop_and_params_validation():
    assert match_stop([1, 2, 3], [(2, 3)]) == 2
    assert match_stop([1, 2, 3], [(3,), (2, 3)]) == 2   # longest wins
    assert match_stop([1, 2], [(3, 1, 2, 9)]) == 0
    with pytest.raises(MXNetError):
        SamplingParams(top_p=0.0)
    with pytest.raises(MXNetError):
        SamplingParams(top_k=-1)
    with pytest.raises(MXNetError):
        SamplingParams(repetition_penalty=0.0)
    with pytest.raises(MXNetError):
        SamplingParams(stop_sequences=((),))
    # grammar requires eos on the request
    with pytest.raises(MXNetError):
        Request(np.array([1], np.int32),
                sampling=SamplingParams(
                    grammar=choice_grammar([[1]], 8)))
    # vocab mismatch is a fail-fast FAILED_UNSERVABLE at submit
    sp = SamplingParams(grammar=choice_grammar([[1]], 99))
    assert sp.validate_for(64, eos_id=3) is not None
    assert SamplingParams().neutral
    assert not SamplingParams(top_k=5).neutral


def test_stop_only_request_stays_on_zero_copy_path(model):
    """Stop matching is pure host-side bookkeeping — a request whose
    ONLY knob is a stop sequence must not flip the engine onto the
    table-shipping menu path (review regression: ``neutral`` gated
    ``menu_active``, so stop-only traffic paid the full (S, V)
    host-to-device copies every decode step for nothing)."""
    sp = SamplingParams(stop_sequences=((60, 61),))
    assert sp.logits_neutral and not sp.neutral
    assert not SamplingParams(top_k=3).logits_neutral
    eng = _eng(model, num_slots=1)
    req = Request(np.array([1, 2, 3], np.int32), max_new_tokens=4,
                  sampling=sp)
    assert eng.submit(req)
    slot = None
    while req.outcome is None:
        eng.step()
        slot = next((s for s in eng._slots if s is not None), slot)
    assert slot is not None and not slot.menu_active
    eng.audit_pages()


# --------------------------------------------------------------------- #
# engine: neutral bit-identity + compile discipline
# --------------------------------------------------------------------- #

def test_neutral_params_bit_identical_and_no_retrace(model):
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in (6, 11, 9, 7)]
    plain = _eng(model, num_slots=4)
    reqs_a = [Request(p, max_new_tokens=10, temperature=t, seed=100 + i)
              for i, (p, t) in enumerate(zip(prompts,
                                             (0.0, 0.9, 0.0, 1.2)))]
    plain.run(reqs_a)
    neutral = _eng(model, num_slots=4)
    reqs_b = [Request(p, max_new_tokens=10, temperature=t,
                      seed=100 + i, sampling=SamplingParams())
              for i, (p, t) in enumerate(zip(prompts,
                                             (0.0, 0.9, 0.0, 1.2)))]
    neutral.run(reqs_b)
    for a, b in zip(reqs_a, reqs_b):
        assert list(a.token_ids) == list(b.token_ids)
    # explicit neutral sentinels too: top_k=V / top_p=1.0 / rep=1.0
    explicit = _eng(model, num_slots=4)
    reqs_c = [Request(p, max_new_tokens=10, temperature=t,
                      seed=100 + i,
                      sampling=SamplingParams(top_k=64, top_p=1.0,
                                              repetition_penalty=1.0,
                                              presence_penalty=0.0))
              for i, (p, t) in enumerate(zip(prompts,
                                             (0.0, 0.9, 0.0, 1.2)))]
    explicit.run(reqs_c)
    for a, c in zip(reqs_a, reqs_c):
        assert list(a.token_ids) == list(c.token_ids)
    for e in (plain, neutral, explicit):
        assert e.decode_trace_count == 1
        e.audit_pages()


def test_mixed_knob_traffic_compiles_once(model):
    """Every parameter combination in one engine run — knobs are pure
    data, so ONE decode trace (and one verify trace when speculating)
    covers them all."""
    rng = np.random.RandomState(4)
    gram = choice_grammar([[1, 2, 3, 1], [5, 6]], 64)
    mk = [
        dict(temperature=0.0),
        dict(temperature=0.8,
             sampling=SamplingParams(top_k=5)),
        dict(temperature=1.1,
             sampling=SamplingParams(top_p=0.7,
                                     repetition_penalty=1.3)),
        dict(temperature=0.9,
             sampling=SamplingParams(presence_penalty=0.4,
                                     logit_bias={2: -3.0, 7: 1.0})),
        dict(temperature=0.0, eos_id=9,
             sampling=SamplingParams(grammar=gram)),
        dict(temperature=0.7,
             sampling=SamplingParams(stop_sequences=((11, 12), (4,)))),
    ]
    eng = _eng(model, num_slots=3, spec_k=3)
    reqs = [Request(rng.randint(0, 64, size=(5 + i,)).astype(np.int32),
                    max_new_tokens=8, seed=i, **kw)
            for i, kw in enumerate(mk)]
    eng.run(reqs)
    assert all(r.outcome is not None for r in reqs)
    assert eng.decode_trace_count <= 1
    assert eng.verify_trace_count <= 1
    assert eng.decode_trace_count + eng.verify_trace_count >= 1
    assert eng.constrained_requests == 1
    eng.audit_pages()


@pytest.mark.slow   # 16 s: three speculative engines; the neutral
                    # bit-identity + mixed-knob-compile tests keep the
                    # tier-1 coverage (stage_unit runs this)
def test_equal_seed_engines_identical_under_every_knob(model):
    rng = np.random.RandomState(5)
    prompts = [rng.randint(0, 64, size=(8,)).astype(np.int32)
               for _ in range(2)]
    sp = SamplingParams(top_k=12, top_p=0.85, repetition_penalty=1.2,
                        presence_penalty=0.2, logit_bias={3: -2.0})

    def serve(eng):
        reqs = [Request(p, max_new_tokens=10, temperature=1.0,
                        seed=77 + i, sampling=sp)
                for i, p in enumerate(prompts)]
        eng.run(reqs)
        return [list(r.token_ids) for r in reqs]

    a = serve(_eng(model, spec_k=2))
    b = serve(_eng(model, spec_k=2))
    assert a == b
    # occupancy-independence: solo == batched
    solo = Request(prompts[0], max_new_tokens=10, temperature=1.0,
                   seed=77, sampling=sp)
    e = _eng(model, spec_k=2)
    e.run([solo])
    assert list(solo.token_ids) == a[0]


# --------------------------------------------------------------------- #
# semantics
# --------------------------------------------------------------------- #

def test_top_k_one_equals_greedy(model):
    rng = np.random.RandomState(6)
    prompt = rng.randint(0, 64, size=(7,)).astype(np.int32)
    greedy = _run(_eng(model), [prompt], temperature=0.0)[0]
    k1 = _run(_eng(model), [prompt], temperature=1.5, seed=1,
              sampling=SamplingParams(top_k=1))[0]
    assert list(k1.token_ids) == list(greedy.token_ids)


@pytest.mark.slow   # 6 s: spec engine at temperature; bias semantics
                    # are unit-covered in the constrain_logits oracle
def test_logit_bias_bans_tokens(model):
    rng = np.random.RandomState(7)
    banned = {int(t): -1e9 for t in range(0, 64, 2)}   # ban all even
    eng = _eng(model, spec_k=2)
    reqs = _run(eng, [rng.randint(0, 64, size=(6,)).astype(np.int32)
                      for _ in range(3)],
                max_new=12, temperature=1.3, seed=9,
                sampling=SamplingParams(logit_bias=banned))
    for r in reqs:
        assert r.outcome is not None
        assert all(t % 2 == 1 for t in r.token_ids), r.token_ids
    assert eng.decode_trace_count <= 1 and eng.verify_trace_count <= 1


def _stop_reference(model, prompt, max_new, seed=None, temperature=0.0):
    req = _run(_eng(model), [prompt], max_new=max_new, seed=seed,
               temperature=temperature)[0]
    return list(req.token_ids)


@pytest.mark.parametrize("spec_k", [0, 3])
def test_stop_sequence_truncates_exactly(model, spec_k):
    """Pick a bigram from the unconstrained stream; rerunning with it
    as a stop sequence must stop there, truncate the match out, and
    record Outcome.STOP — speculation included (the match can land
    mid-verify-window)."""
    rng = np.random.RandomState(8)
    prompt = rng.randint(0, 64, size=(6,)).astype(np.int32)
    ref = _stop_reference(model, prompt, 16)
    stop = tuple(ref[6:8])
    # the match fires at the FIRST occurrence of the bigram in the
    # (repetitive) greedy stream — compute where that actually is
    cut = next(i for i in range(len(ref) - 1)
               if tuple(ref[i:i + 2]) == stop)
    eng = _eng(model, spec_k=spec_k)
    req = _run(eng, [prompt], max_new=16,
               sampling=SamplingParams(stop_sequences=(stop,)))[0]
    assert req.outcome is Outcome.STOP
    assert list(req.token_ids) == ref[:cut]
    assert eng.stop_hits == 1
    assert eng.completed == 1            # STOP is a success outcome
    eng.audit_pages()


@pytest.mark.parametrize("spec_k", [0, 3])
@pytest.mark.parametrize("temperature", [
    0.0,
    pytest.param(1.0, marks=pytest.mark.slow),   # greedy variants
])                                               # keep tier-1 honest
def test_grammar_output_is_always_in_language(model, spec_k,
                                              temperature):
    sequences = [[1, 2, 3, 1, 2], [5, 6], [5, 7, 8]]
    gram = choice_grammar(sequences, 64)
    rng = np.random.RandomState(9)
    eng = _eng(model, num_slots=3, spec_k=spec_k)
    reqs = _run(eng, [rng.randint(0, 64, size=(5 + i,)).astype(np.int32)
                      for i in range(3)],
                max_new=10, eos_id=9, temperature=temperature, seed=3,
                sampling=SamplingParams(grammar=gram))
    want = {tuple(s) for s in sequences}
    for r in reqs:
        assert r.outcome is Outcome.EOS, (r.outcome, r.token_ids)
        assert tuple(r.token_ids[:-1]) in want, r.token_ids
        assert r.token_ids[-1] == 9
    assert eng.decode_trace_count <= 1 and eng.verify_trace_count <= 1
    assert eng.constrained_requests == 3
    eng.audit_pages()


def test_single_legal_token_chain_force_accepts(model):
    """The degenerate rejection-sampling case: a grammar state with
    ONE legal token makes the residual empty (p̃ is a point mass) —
    the acceptance must force-accept instead of resampling from
    nothing, even at high temperature where naive thresholding of the
    scaled logits would misclassify the masked entries."""
    gram = choice_grammar([[1, 2, 3, 1, 2, 3, 1]], 64)
    eng = _eng(model, spec_k=3)
    reqs = [Request(np.array([1, 2, 3, 1, 2, 3], np.int32),
                    max_new_tokens=10, eos_id=9, temperature=8.0,
                    seed=s, sampling=SamplingParams(grammar=gram))
            for s in range(3)]
    eng.run(reqs)
    for r in reqs:
        assert list(r.token_ids) == [1, 2, 3, 1, 2, 3, 1, 9]
        assert r.outcome is Outcome.EOS
    assert eng.accepted_tokens == eng.drafted_tokens > 0
    eng.audit_pages()


def test_grammar_vocab_mismatch_fails_fast(model):
    gram = choice_grammar([[1, 2]], vocab_size=32)   # model vocab 64
    eng = _eng(model)
    req = Request(np.array([1, 2, 3], np.int32), max_new_tokens=4,
                  eos_id=9, sampling=SamplingParams(grammar=gram))
    assert not eng.submit(req)
    assert req.outcome is Outcome.FAILED_UNSERVABLE
    assert "vocab" in req.detail


def test_preemption_resume_bit_identical_with_sampling(model):
    """A BATCH request carrying penalties + a stop window, preempted
    mid-decode by a LATENCY admission, must resume and finish with
    EXACTLY the tokens of an unpreempted run — grammar state, counts
    and the stop tail are re-derived from the generated suffix at
    re-admission."""
    from incubator_mxnet_tpu.serve import Tier
    rng = np.random.RandomState(10)
    prompt = rng.randint(0, 64, size=(8,)).astype(np.int32)
    sp = SamplingParams(top_k=20, repetition_penalty=1.4,
                        presence_penalty=0.1,
                        stop_sequences=((63, 62, 61),))
    ref = Request(prompt, max_new_tokens=14, temperature=0.9, seed=55,
                  tier=Tier.BATCH, sampling=sp)
    e0 = _eng(model, num_slots=1)
    e0.run([ref])

    eng = _eng(model, num_slots=1)
    victim = Request(prompt.copy(), max_new_tokens=14, temperature=0.9,
                     seed=55, tier=Tier.BATCH, sampling=sp)
    eng.submit(victim)
    while len(victim.token_ids) < 4:
        eng.step()
    hi = Request(rng.randint(0, 64, size=(5,)).astype(np.int32),
                 max_new_tokens=3, tier=Tier.LATENCY)
    eng.submit(hi)
    while victim.outcome is None:
        eng.step()
    assert victim.preemptions >= 1
    assert list(victim.token_ids) == list(ref.token_ids)
    assert victim.outcome == ref.outcome
    eng.audit_pages()


# --------------------------------------------------------------------- #
# the sampling tail's two branches (PR 37): the sort under top-k /
# top-p and the draw under a temperature run only in a step that
# holds a live slot asking for them
# --------------------------------------------------------------------- #

def _unbranched_constrain_logits(logits, temps, counts, bias, mask,
                                 top_k, top_p, rep_pen, pres_pen):
    """``constrain_logits`` as it stood before PR 37, every stage in
    line: the oracle the branched function has to equal bit for bit."""
    import jax.numpy as jnp
    neg = -1e30
    V = logits.shape[-1]
    l = logits.astype(jnp.float32) + bias
    pen_on = (rep_pen != 1.0) | (pres_pen != 0.0)
    penalized = jnp.where(l > 0, l / rep_pen[..., None],
                          l * rep_pen[..., None]) - pres_pen[..., None]
    l = jnp.where(pen_on[..., None] & (counts > 0), penalized, l)
    l = jnp.where(mask, l, neg)
    k_on = (top_k > 0) & (top_k < V)
    srt = jnp.sort(l, axis=-1)
    kidx = jnp.clip(V - top_k, 0, V - 1)[..., None]
    kidx = jnp.broadcast_to(kidx, l.shape[:-1] + (1,))
    kth = jnp.take_along_axis(srt, kidx, axis=-1)
    l = jnp.where(k_on[..., None] & (l < kth), neg, l)
    p_on = top_p < 1.0
    safe_t = jnp.where(temps > 0, jnp.maximum(temps, 1e-6),
                       1.0)[..., None]
    srt2 = jnp.where(k_on[..., None] & (srt < kth), neg, srt)
    m = jnp.max(l, axis=-1, keepdims=True)
    e = jnp.exp(l / safe_t - m / safe_t)
    z = jnp.sum(e, axis=-1, keepdims=True)
    probs = e / z
    sp = (jnp.exp(srt2 / safe_t - m / safe_t) / z)[..., ::-1]
    csum = jnp.cumsum(sp, axis=-1)
    keep_sorted = (csum - sp) < top_p[..., None]
    thr = jnp.min(jnp.where(keep_sorted, sp, jnp.inf), axis=-1,
                  keepdims=True)
    return jnp.where(p_on[..., None] & (probs < thr), neg, l)


def _primitives(jaxpr, in_branch=False):
    """``(name, in_branch)`` of every primitive of ``jaxpr`` and of the
    jaxprs nested in its equations (jit, vmap and custom-call bodies):
    ``in_branch`` says whether it sits under a ``cond``'s branch, or on
    the path every call takes."""
    import jax
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, in_branch
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _primitives(
                sub, in_branch or eqn.primitive.name == "cond")


def test_constrain_logits_sorts_only_inside_a_branch():
    import jax
    a = _neutral_args((2, 4), 16)
    logits = np.zeros((2, 4, 16), np.float32)
    prims = list(_primitives(jax.make_jaxpr(
        lambda l, kw: constrain_logits(l, **kw))(logits, a).jaxpr))
    always = {n for n, branch in prims if not branch}
    assert "cond" in always
    assert not {"sort", "cumsum", "exp"} & always, sorted(always)
    assert ("sort", True) in prims


def test_decode_step_sorts_and_draws_only_inside_a_branch(model):
    """The W=1 decode program: no sort and no random draw on the path
    every step takes (a structural guard against either coming back:
    on the chip the sort alone was a fifth of a greedy step)."""
    import jax
    eng = _eng(model, num_slots=2)
    _run(eng, [np.array([1, 2, 3], np.int32)], max_new=3)
    _, args = eng._programs["decode"]
    prims = list(_primitives(
        jax.make_jaxpr(eng._decode_step_fn)(*args).jaxpr))
    always = [n for n, branch in prims if not branch]
    assert always.count("cond") == 2, always.count("cond")
    bad = {"sort", "random_bits", "threefry2x32", "random_fold_in"}
    assert not bad & set(always), sorted(bad & set(always))
    assert {("sort", True), ("random_bits", True)} <= set(prims)


@pytest.mark.parametrize("asks", ["top_k", "top_p", "both"])
@pytest.mark.parametrize("shape", [(), (3,), (2, 4)],
                         ids=["scalar", "rows3", "rows2x4"])
def test_one_asking_row_gets_the_unbranched_values(shape, asks):
    """Exactly one row asks for a truncation: the branch is taken for
    the whole batch, every row reads bit for bit what the unbranched
    function gives, and the rows that asked for nothing come back
    value-identical."""
    import jax
    V = 32
    rng = np.random.RandomState(len(shape) * 7 + len(asks))
    logits = rng.randn(*(shape + (V,))).astype(np.float32)
    a = _neutral_args(shape, V)
    a["counts"] = rng.randint(0, 2, size=shape + (V,)).astype(np.int32)
    one = tuple(d - 1 for d in shape)        # the last row asks
    if asks in ("top_k", "both"):
        a["top_k"] = np.array(a["top_k"])
        a["top_k"][one] = 5
    if asks in ("top_p", "both"):
        a["top_p"] = np.array(a["top_p"])
        a["top_p"][one] = 0.6
    got = np.asarray(jax.jit(constrain_logits)(logits, **a))
    want = np.asarray(jax.jit(_unbranched_constrain_logits)(logits, **a))
    assert np.array_equal(got, want)
    floored = int((got[one] < -1e29).sum())
    assert floored >= V - 5 if asks != "top_p" else floored > 0
    neutral = np.ones(shape, bool)
    neutral[one] = False
    assert np.array_equal(got[neutral], logits[neutral])


# a fixed-seed sampled request beside a greedy one, both engine widths:
# the tokens the PARENT of PR 37 served (recorded from its tree with
# these prompts, seeds and the module's model before the branches went in)
_P0 = [15, 43, 12, 59, 28, 53, 22]
_P1 = [58, 35, 3, 42, 21, 63, 24, 53, 23]
_GREEDY_ALONE = [17] * 12
_SAMPLED = {
    "top_p": (dict(temperature=0.9, seed=7,
                   sampling=SamplingParams(top_p=0.7)),
              {0: [40, 53, 33, 13, 4, 12, 48, 55, 62, 42, 25, 18],
               3: [40, 53, 33, 13, 4, 12, 48, 55, 62, 42, 25, 18]}),
    "top_k": (dict(temperature=0.8, seed=8,
                   sampling=SamplingParams(top_k=5)),
              {0: [11, 39, 56, 0, 17, 6, 54, 54, 54, 16, 17, 8],
               3: [11, 39, 56, 0, 17, 6, 54, 54, 15, 16, 17, 8]}),
    "both": (dict(temperature=1.1, seed=9,
                  sampling=SamplingParams(top_k=12, top_p=0.8,
                                          repetition_penalty=1.2)),
             {0: [17, 16, 33, 56, 4, 8, 13, 18, 17, 30, 48, 20],
              3: [17, 16, 33, 56, 4, 8, 13, 18, 17, 16, 16, 20]}),
    "temperature_only": (dict(temperature=0.9, seed=10),
                         {0: [46, 37, 30, 57, 8, 26, 5, 21, 39, 12, 59,
                              48],
                          3: [46, 37, 30, 57, 8, 26, 5, 21, 39, 12, 59,
                              48]}),
}


@pytest.mark.parametrize("spec_k", [0, 3])
@pytest.mark.parametrize("case", sorted(_SAMPLED))
def test_greedy_beside_a_sampled_request_and_the_parents_tokens(
        model, case, spec_k):
    """A greedy request that shares its steps with a sampled one (the
    steps take the branches) emits what it emits alone (where no step
    does); the sampled request emits the parent's tokens; one decode
    program and one verify program serve both kinds of step."""
    kw, want = _SAMPLED[case]
    alone = _eng(model, spec_k=spec_k)
    (ga,) = _run(alone, [np.array(_P0, np.int32)], max_new=12)
    assert list(ga.token_ids) == _GREEDY_ALONE
    assert alone.truncate_steps == 0 and alone.draw_steps == 0
    eng = _eng(model, spec_k=spec_k)
    greedy = Request(np.array(_P0, np.int32), max_new_tokens=12)
    sampled = Request(np.array(_P1, np.int32), max_new_tokens=12, **kw)
    eng.run([greedy, sampled])
    assert list(greedy.token_ids) == list(ga.token_ids)
    assert list(sampled.token_ids) == want[spec_k]
    assert eng.decode_trace_count <= 1 and eng.verify_trace_count <= 1
    assert eng.draw_steps > 0
    assert (eng.truncate_steps > 0) == ("sampling" in kw)
    eng.audit_pages()


def test_branch_counters_count_the_steps_that_held_such_a_slot(model):
    """``truncate_steps`` / ``draw_steps`` and ``DECODE_STEP``'s
    ``truncating`` / ``drawing``: 1 in exactly the steps in which a LIVE
    slot asked for top-k / top-p, or had a temperature. All three
    prompts prefill in the first ``step()``, the first token comes from
    the prefill, so a request of n tokens is live in decode steps
    1..n-1."""
    from incubator_mxnet_tpu.events import EventType
    eng = InferenceEngine(model, num_slots=3, page_size=8, max_len=64)
    rng = np.random.RandomState(14)
    prompts = [rng.randint(0, 64, size=(n,)).astype(np.int32)
               for n in (5, 6, 7)]
    reqs = [
        # the menu's path without a truncation: top_k == V is off
        Request(prompts[0], max_new_tokens=10,
                sampling=SamplingParams(top_k=64)),
        Request(prompts[1], max_new_tokens=4, temperature=0.9, seed=1,
                sampling=SamplingParams(top_p=0.7)),
        Request(prompts[2], max_new_tokens=6, temperature=0.8, seed=2),
    ]
    eng.run(reqs)
    steps = eng.flight.events(etype=EventType.DECODE_STEP)
    assert [e.data["live"] for e in steps] == [3] * 3 + [2] * 2 + [1] * 4
    assert [e.data["truncating"] for e in steps] == [1] * 3 + [0] * 6
    assert [e.data["drawing"] for e in steps] == [1] * 5 + [0] * 4
    assert (eng.truncate_steps, eng.draw_steps) == (3, 5)
    assert eng.decode_steps == 9 and eng.decode_trace_count == 1


# --------------------------------------------------------------------- #
# distribution correctness under truncated proposals
# --------------------------------------------------------------------- #

@pytest.mark.slow   # ~2 x 300 sequential seeded requests (stage_unit;
                    # the frontsmoke CI stage covers the fast contracts)
def test_rejection_sampling_distribution_under_topp_proposals(model):
    """Point-mass draft proposals against a top-p-truncated target:
    the speculative engine's (tok0, tok1) joint emission distribution
    over many seeds must match the non-speculative engine's (total
    variation), with both acceptance AND rejection branches actually
    exercised. Seeds are fixed, so this is deterministic."""
    rng = np.random.RandomState(11)
    prompt = rng.randint(0, 64, size=(6,)).astype(np.int32)
    sp = SamplingParams(top_p=0.8)
    n = 300

    def emissions(spec_k, draft_fn=None):
        eng = _eng(model, num_slots=1, spec_k=spec_k,
                   draft_fn=draft_fn, prefix_cache=False)
        out = []
        for s in range(n):
            # 3 tokens: prefill emission + a decode step with draft
            # budget (kmax = max_new - emitted - 1) + the tail
            r = Request(prompt, max_new_tokens=3, temperature=1.0,
                        seed=s, sampling=sp)
            eng.run([r])
            out.append(tuple(r.token_ids))
        return out, eng

    base, _ = emissions(0)
    # the draft proposes the base run's modal second token — inside
    # the nucleus often enough to accept, wrong often enough to reject
    seconds = [t[1] for t in base if len(t) >= 2]
    modal = int(np.bincount(seconds).argmax())

    def draft(history, k):
        return np.array([modal], np.int32)[:k]

    spec, eng_s = emissions(1, draft_fn=draft)
    assert eng_s.drafted_tokens > 0
    assert 0 < eng_s.accepted_tokens < eng_s.drafted_tokens, \
        "need both acceptance and rejection branches exercised"

    def hist(xs):
        h = {}
        for x in xs:
            h[x] = h.get(x, 0) + 1
        return h

    hb, hs = hist(base), hist(spec)
    keys = set(hb) | set(hs)
    tv = 0.5 * sum(abs(hb.get(k, 0) - hs.get(k, 0)) for k in keys) / n
    assert tv < 0.12, f"TV distance {tv:.3f} — speculative emission " \
                      f"distribution drifted under truncated proposals"
    assert eng_s.decode_trace_count <= 1
    assert eng_s.verify_trace_count == 1

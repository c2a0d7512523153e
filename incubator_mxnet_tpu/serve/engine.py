"""Continuous-batching inference engine over the paged KV cache.

Design (the jit-once contract):

  - The engine owns ``num_slots`` decode SLOTS. Occupancy (which slots
    are live, at what lengths, with what sampling params) is pure DATA
    — int32/float32 arrays fed to ONE jitted decode-step program whose
    shapes never change. Prefill-insert and EOS-eviction are host-side
    edits of those arrays plus page-allocator bookkeeping; in steady
    state the decode step compiles exactly once — exactly once PER
    decode-family program when speculation is on, see below —
    (asserted by ``tools/serve_bench.py --smoke`` and
    tests/test_serve.py).
  - Prefill is a separate jitted program per PROMPT BUCKET (prompt
    pages rounded up to a power of two), the BucketingModule trade-off:
    a bounded, logarithmic family of prefill shapes instead of one per
    prompt length.
  - The decode step, per layer: project the one new token per slot,
    scatter its K/V into each slot's tail page, then ragged paged
    attention (ops/ragged_attention.py) over exactly the live pages.
    Inactive slots ride along at length 0: they write to the null page,
    attend nothing (zero output by the masked-row contract), and their
    sampled token is discarded on the host — no shape anywhere depends
    on how many slots are live.
  - PREFIX CACHING (copy-on-write page sharing): a host-side radix/hash
    index (``paged_kv.PrefixIndex``) remembers which pages hold which
    page-aligned prompt prefixes. Admission matches a new prompt's
    longest cached prefix and maps those pages into the slot's page
    table READ-ONLY (refcounted — they return to the free list only
    when the last slot and the index let go); the boundary partial page
    is COPIED into a private page; only the un-cached suffix pays
    prefill compute. Shared pages are never written: decode writes land
    at positions >= the prompt length, past every shared page.
    ``warm_start`` flushes the index — cached K/V is weight-dependent.
  - CHUNKED PREFILL (``chunk_pages``): instead of one monolithic
    prompt-sized program between decode steps, the prompt is processed
    in fixed-size page-aligned chunks (one pow2 bucket family)
    interleaved with decode under a per-step TOKEN BUDGET, so a long
    arrival no longer freezes TPOT for every active slot. Chunk queries
    attend the slot's already-populated pages plus the causal
    intra-chunk part (``ops.ragged_attention.ragged_prefill_attention``)
    — chunk position/length/pages are data, so each chunk bucket
    compiles exactly once, same contract as decode. The cache-hit
    suffix path reuses the same chunk programs even in monolithic mode.
  - SPECULATIVE DECODING (``spec_k``): decode is dispatch/bandwidth-
    bound at one token per slot per step, so the engine drafts up to K
    candidate next-tokens per slot HOST-SIDE (n-gram/prompt-lookup over
    the slot's own prompt + emitted history — serve/draft.py, no second
    model) and the decode step VERIFIES all K+1 positions in the same
    single program call: the draft tokens' K/V are written into the
    slot's tail pages up front, every position is scored through a
    multi-query ragged attention variant
    (``ops.ragged_attention.ragged_verify_attention`` — the slot's
    paged prefix plus causal intra-window masking in one predicate),
    and the accepted prefix length is computed ON DEVICE (greedy:
    longest run of drafts matching the argmax chain — bit-identical to
    sequential decode by construction; temperature: rejection-sampled
    acceptance, so the output distribution is provably unchanged). The
    accepted lengths come back as a per-slot data vector feeding the
    SAME ragged lengths/page machinery — drafts, acceptance and the
    per-slot RNG keys are pure data, so the decode family still
    compiles exactly once PER PROGRAM: the W=1 narrow step (bitwise
    the non-speculative decode — it runs whenever no slot drafted,
    via adaptive gating: ``spec_patience`` fully-rejected windows
    stop a slot's drafting, ``spec_probe_every`` re-probes) and the
    K+1-wide verify, two shape-keyed entries in one jit cache
    (``decode_trace_count`` / ``verify_trace_count``). A slot whose
    drafts all miss (or that drafted nothing) advances exactly
    today's 1 token/step. Rejected drafts leave stale K/V above the
    accepted length — harmless by the same masked-read contract that
    covers reused pages, and overwritten by the next step's writes.
  - Per-slot sampling params: a (S,) temperature array is traced data;
    greedy and categorical are both computed and selected per slot.
    Every admitted request carries its own RNG key (``Request.seed``,
    engine-assigned when unset) folded with the TOKEN'S SEQUENCE
    POSITION for every draw — sampling is reproducible per request and
    independent of occupancy, chunking, and speculation depth.
  - tp sharding: pass ``mesh`` — pools are placed with the H axis
    sharded over ``tp`` via the existing ``parallel.mesh`` machinery
    and XLA propagates the layout through the step (attention runs the
    jnp ragged path under tp; wiring the Pallas kernel through
    shard_map is future work, documented in docs/SERVING.md).

The reference's closest surface is the stateful Module/forward loop +
GluonNLP's BeamSearchSampler (file-level citations, SURVEY.md caveat) —
per-request, dense, and retrace-happy; this is its redesign for ragged
multi-tenant decode.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import math
import time
from collections import deque
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import autograd
from ..base import MXNetError
from ..gluon.block import _hybrid_trace_scope
from ..ndarray import NDArray
from ..ops.attention import scaled_dot_product_attention as _sdpa
from ..ops.ragged_attention import (ragged_attention_reference,
                                    ragged_paged_attention,
                                    ragged_prefill_attention,
                                    ragged_prefill_reference,
                                    ragged_verify_attention,
                                    ragged_verify_reference)
from .draft import make_ngram_drafter
from .events import EventType, resolve_recorder, terminal_fields
from .outcomes import Outcome
from .paged_kv import (NULL_PAGE, KVTierStore, PageAllocator, PrefixIndex,
                       init_kv_pools, kv_quant_spec, page_scales,
                       read_slot_rows, write_slot_rows, zero_slot_rows,
                       write_block_kv, write_block_kv_q,
                       write_prompt_kv, write_prompt_kv_q,
                       write_token_kv, write_token_kv_q)
from .sampling import (SamplingParams, constrain_logits, grammar_mask,
                       match_stop)
from .slo import (BrownoutController, Tier, TierPolicy,
                  resolve_tier_policies)

__all__ = ["Request", "InferenceEngine", "Outcome", "Tier",
           "TierPolicy", "SamplingParams"]

_NEG_BIG = -1e30

_REQUEST_IDS = itertools.count(1)    # process-wide: ids never collide
                                     # across engines, so a router can
                                     # address any request it has seen


@dataclasses.dataclass
class Request:
    """One generation request. ``temperature`` 0 = greedy; ``eos_id``
    < 0 disables EOS stopping (generation runs to max_new_tokens).
    ``deadline_s`` (seconds, relative to submit) bounds the request's
    total queue + serve time: past it the request is dropped from the
    queue or evicted mid-decode with outcome DEADLINE_EXPIRED (partial
    tokens are kept). ``seed`` pins the request's own sampling RNG
    stream (temperature draws are then reproducible across engines,
    occupancy mixes, chunking, and speculation depth); None lets the
    engine assign one. Every request submitted to the engine ends with
    ``outcome`` set to exactly one terminal Outcome (serve/outcomes.py);
    ``detail`` carries the human-readable cause for the failure
    outcomes and ``retry_after_s`` the backpressure hint on SHED.
    ``drafted_tokens``/``accepted_tokens`` count this request's
    speculative drafting activity (accepted <= drafted; both 0 when
    the engine does not speculate).

    ``tier`` is the request's SLO priority class (serve/slo.py):
    LATENCY outranks STANDARD outranks BATCH in admission order, shed
    order (BATCH drains first) and slot preemption (a LATENCY
    admission may reclaim a BATCH slot mid-decode — the preempted
    request re-queues and resumes from its emitted suffix,
    bit-identically). ``request_id`` is a process-unique handle for
    client cancellation (``engine.cancel`` / ``router.cancel``);
    auto-assigned unless pinned. ``preemptions`` counts how many times
    a higher tier reclaimed this request's slot."""

    prompt_ids: np.ndarray
    max_new_tokens: int = 32
    temperature: float = 0.0
    eos_id: int = -1
    deadline_s: Optional[float] = None
    seed: Optional[int] = None
    tier: Tier = Tier.STANDARD
    request_id: Optional[int] = None
    # the full sampling menu (serve/sampling.py): top-k/top-p,
    # repetition/presence penalties, logit bias, stop sequences,
    # grammar-constrained decoding — all pure per-slot data through
    # the same compiled programs temperature rides today (None = the
    # plain greedy/temperature path, bit-identical to pre-round-18)
    sampling: Optional[SamplingParams] = None
    # resume split hint: the first ``prompt_len`` prompt ids are the
    # TRUE prompt, the rest previously-emitted tokens folded back in
    # by a failover/preemption replay (serve/router.py). The grammar
    # state and stop-sequence window are derived from the generated
    # part only, so a resumed request samples exactly as the unbroken
    # run would. None = the whole prompt is prompt.
    prompt_len: Optional[int] = None

    # filled in by the engine
    _stop_trim: int = 0          # stop-seq tokens the recording attempt
                                 # could not truncate locally (they were
                                 # emitted by an EARLIER attempt) — the
                                 # router trims them from the client
    preemptions: int = 0
    drafted_tokens: int = 0
    accepted_tokens: int = 0
    token_ids: List[int] = dataclasses.field(default_factory=list)
    token_times: List[float] = dataclasses.field(default_factory=list)
    token_stamps: List[float] = dataclasses.field(default_factory=list)
    submit_time: Optional[float] = None
    finish_time: Optional[float] = None
    outcome: Optional[Outcome] = None
    detail: str = ""
    retry_after_s: Optional[float] = None
    _deadline_abs: Optional[float] = None
    _assigned_key: Optional[np.ndarray] = None   # engine-drawn RNG key,
                                                 # pinned at first
                                                 # admission so a
                                                 # preemption resume
                                                 # replays the SAME
                                                 # sampling stream

    def __post_init__(self):
        self.prompt_ids = np.asarray(self.prompt_ids, np.int32).reshape(-1)
        if self.prompt_ids.size == 0:
            raise MXNetError("empty prompt")
        if self.max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if self.deadline_s is not None and self.deadline_s <= 0:
            raise MXNetError("deadline_s must be > 0 (or None)")
        if isinstance(self.tier, str):
            self.tier = Tier(self.tier)
        if not isinstance(self.tier, Tier):
            raise MXNetError(f"tier must be a serve.Tier, got "
                             f"{self.tier!r}")
        if self.sampling is not None:
            if not isinstance(self.sampling, SamplingParams):
                raise MXNetError(f"sampling must be a SamplingParams, "
                                 f"got {type(self.sampling).__name__}")
            if self.sampling.grammar is not None and self.eos_id < 0:
                raise MXNetError(
                    "grammar-constrained decoding requires eos_id >= 0 "
                    "(grammar completion is expressed through EOS)")
        if self.prompt_len is not None:
            self.prompt_len = int(self.prompt_len)
            if not (0 < self.prompt_len <= self.prompt_ids.size):
                raise MXNetError(
                    f"prompt_len {self.prompt_len} outside "
                    f"(0, {self.prompt_ids.size}]")
        if self.request_id is None:
            self.request_id = next(_REQUEST_IDS)


@dataclasses.dataclass
class _Slot:
    request: Request
    reserved_pages: int          # worst-case pages (admission guarantee)
    refs: List[int]              # pages this slot holds a refcount on
    row: np.ndarray              # (max_pages,) page row; installed into
                                 # the decode page table when prefill ends
    t0: int                      # attempt prompt length (original
                                 # prompt + tokens emitted before a
                                 # preemption resume)
    attempt_ids: np.ndarray      # the attempt prompt itself — what the
                                 # prefill programs process and the
                                 # prefix index is keyed by
    prefill_pos: int             # prompt tokens whose K/V is populated
    t_admit: float
    key: np.ndarray = None       # (2,) uint32 per-request RNG key
    stall_count: int = 0         # consecutive zero-progress steps (the
                                 # watchdog's evidence; reset on progress)
    spec_streak: int = 0         # consecutive FULLY-REJECTED draft
                                 # windows (adaptive gating's evidence;
                                 # reset on any acceptance)
    grammar_state: object = None  # current DFA state (host data; None
                                  # when the request has no grammar)
    menu_active: bool = False    # request carries LOGIT-touching
                                 # sampling params (stop-only requests
                                 # stay False: stops are host-side) —
                                 # steps serving only neutral slots
                                 # ship the cached device-resident
                                 # neutral operands instead of copying
                                 # the (S, V) tables every step
    stop_tail: list = dataclasses.field(default_factory=list)
                                 # trailing window of the GENERATED
                                 # stream (max_stop_len tokens) — the
                                 # stop-sequence matcher's evidence,
                                 # seeded across resume boundaries

    @property
    def prefilling(self) -> bool:
        return self.prefill_pos < self.t0


def _next_pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


class InferenceEngine:
    """Fixed-slot continuous-batching decode over a model that brings
    ``cache_layout()`` and ``cached_forward(ids, pos, attend, last_row,
    state, real)`` (models/gpt.py::GPTModel, models/granite_hybrid.py::
    GraniteHybridModel; docs/SERVING.md "What the engine asks of a
    model"): the model owns its math, the engine's programs own where
    keys and values (a page pool a layer that keeps them) and state
    rows (the state cache) are kept and how they are read.

    ``num_pages`` defaults to the worst case (every slot at max_len) so
    admission never stalls; shrink it to trade admission concurrency
    for cache memory — correctness is preserved by admission control
    (a request is only admitted when its worst-case page count fits,
    counting pages reclaimable from the prefix index).

    ``prefix_cache`` (default on) enables copy-on-write prefix page
    sharing; ``chunk_pages`` (a power of two, default None = the PR 2
    monolithic prefill) enables chunked prefill with at most
    ``token_budget`` prompt tokens processed per engine step (default
    ``chunk_pages * page_size``).

    Resilience knobs (docs/RESILIENCE.md — every request ends in a
    structured terminal ``Outcome`` instead of success-or-exception):

    - ``max_queue``: bounded admission queue depth — a submit beyond it
      is SHED with a ``retry_after_s`` hint instead of growing the
      queue without bound;
    - ``max_queue_delay_s``: estimated-queue-delay admission limit (an
      EWMA of observed slot-residence times scales the queue backlog
      BEYOND today's free slots — zero on an idle engine, which must
      never shed on its own steady-state latency) — load is shed
      BEFORE the queue builds a deadline-busting backlog;
    - ``guard_nonfinite`` (default on): the decode/prefill programs
      compute a cheap per-slot non-finite flag (one logits reduction on
      device) and SIGN-ENCODE it into the sampled tokens (token t on a
      poisoned slot reads -t - 1) — pure DATA riding the existing
      token transfer, so the jit-once contract is untouched and no
      extra program output or host sync is paid; a flagged slot is
      quarantined and failed with FAILED_NONFINITE rather than
      sampling garbage forever;
    - ``watchdog_steps``: a slot making zero progress for this many
      consecutive engine steps (e.g. page-starved for its tail page)
      is evicted with FAILED_UNSERVABLE — a stuck slot never wedges
      the engine;
    - ``max_slot_wall_s``: per-slot wall-clock cap (engine-imposed
      deadline) — exceeded slots are evicted DEADLINE_EXPIRED;
    - ``stall_steps``: consecutive fully-idle scheduler polls (nothing
      decoding, queue head unadmittable) before the head request is
      failed FAILED_UNSERVABLE instead of waiting forever.

    SLO-tier knobs (serve/slo.py, docs/RESILIENCE.md):

    - ``tier_policies``: {Tier: TierPolicy} overrides merged over
      ``default_tier_policies()`` — per-tier ``max_queue`` /
      ``max_queue_delay_s`` / ``default_deadline_s`` scoping of the
      global knobs, plus the preemption contract. Admission is
      priority-ordered (LATENCY > STANDARD > BATCH, FIFO within a
      tier), overload shedding drains the lowest queued tier first,
      and a tier that ``can_preempt`` may reclaim a ``preemptible``
      lower-tier slot mid-decode: the victim keeps its partial tokens
      and re-queues through normal admission as a resume-from-suffix
      replay (continuation bit-identical — same pinned sampling key,
      position-keyed draws), bounded by ``max_preemptions`` before a
      retryable PREEMPTED terminal;
    - ``brownout``: True (default controller) or a
      ``BrownoutController`` — deterministic hysteresis over pressure
      signals stepping through degrade levels (1: speculation off,
      2: chunked-prefill budget clamped to one chunk, 3: BATCH
      admissions clamped to zero) and back out as pressure clears;
    - ``cancel(request_or_id)``: client cancellation from any live
      state to a CANCELLED terminal, pages reclaimed, audit clean.

    All tier/preemption/brownout state is host-side data — none of it
    enters a compiled program, so the jit-once decode contract holds
    (asserted in tests/test_tiers.py, tools/chaos_bench.py --tiers).

    Speculative decoding knobs (docs/SERVING.md):

    - ``spec_k`` (default 0 = off): draft up to K candidate tokens per
      slot per step and verify all K + 1 positions in the one jitted
      decode program; greedy output stays bit-identical to the
      non-speculative path, temperature output keeps its exact
      distribution (rejection-sampled acceptance). A step accepts
      1..K+1 tokens per slot — 1 (exactly today's decode) when the
      drafts miss or none were found;
    - ``draft_fn``: ``(history, k) -> int32[0..k]`` draft proposer;
      default is n-gram/prompt-lookup drafting over the slot's own
      prompt + emitted tokens (``serve.draft.ngram_propose``) with
      max order ``draft_ngram``;
    - ``spec_patience`` / ``spec_probe_every``: adaptive gating — a
      slot whose last ``spec_patience`` draft windows were ALL fully
      rejected stops drafting (0 disables gating); steps where no slot
      drafted run the W=1 program, bitwise the non-speculative decode
      step, so zero-agreement traffic converges to the plain-decode
      floor. Gated slots probe again every ``spec_probe_every``-th
      engine step (shared clock — one wide step per probe, however
      many slots probe); newly admitted requests always draft
      immediately (fresh slot state), so churny traffic re-tests
      agreement without waiting for the clock.

    Quantized KV cache (docs/SERVING.md "Quantized KV cache"):

    - ``kv_quant`` (default None = f32/bf16 pools): ``'int8'`` (or
      ``'fp8_e4m3'`` on a float8-capable jax) stores every KV page as
      narrow codes with ONE symmetric scale per page per pool —
      roughly 4x (f32) / 2x (bf16) more slots-at-context on the same
      pool bytes, and the same factor more prefix-cache working set.
      K/V quantize AT WRITE TIME inside the existing programs (pure
      traced data — decode/verify/prefill trace counts stay 1), all
      three ragged kernels dequantize inline at the DMA boundary with
      the scales riding the scalar-prefetch path next to the page
      table, and the host owns the per-page amax metadata (reset on
      page allocation, copied on COW, shared when the page is
      shared). Accuracy is a measured-tolerance gate against the f32
      jnp oracle (BENCH_QUANT.json), not bit parity; int8 payloads
      cannot carry NaN, so the non-finite channel becomes the page
      SCALE — a poisoned scale makes the attention output non-finite
      and the existing sign-encoded guard quarantines the slot
      (serve/chaos.py ``CorruptPageScale``)."""

    def __init__(self, model, num_slots=8, page_size=16, max_len=None,
                 num_pages=None, dtype=None, mesh=None, interpret=None,
                 prefix_cache=True, chunk_pages=None, token_budget=None,
                 max_queue=None, max_queue_delay_s=None,
                 guard_nonfinite=True, watchdog_steps=1024,
                 max_slot_wall_s=None, stall_steps=500,
                 spec_k=0, draft_fn=None, draft_ngram=3,
                 spec_patience=2, spec_probe_every=64,
                 tier_policies=None, max_preemptions=4,
                 brownout=None, kv_quant=None, kv_tiers=None,
                 recorder=None, component="engine"):
        self.model = model
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.max_len = int(max_len or model.max_length)
        if self.max_len > model.max_length:
            raise MXNetError(f"max_len {self.max_len} exceeds model "
                             f"max_length {model.max_length}")
        self.max_pages = -(-self.max_len // self.page_size)
        if num_pages is None:
            num_pages = 1 + self.num_slots * self.max_pages
        self.num_pages = int(num_pages)
        self._dtype = dtype or model._dtype

        self.chunk_pages = None
        if chunk_pages is not None:
            cp = int(chunk_pages)
            if cp < 1 or (cp & (cp - 1)):
                raise MXNetError(f"chunk_pages must be a power of two, "
                                 f"got {cp}")
            self.chunk_pages = cp
        self.token_budget = int(token_budget) if token_budget is not None \
            else (self.chunk_pages or self.max_pages) * self.page_size
        if self.chunk_pages is not None and \
                self.token_budget < self.chunk_pages * self.page_size:
            raise MXNetError(
                f"token_budget {self.token_budget} below one chunk "
                f"({self.chunk_pages * self.page_size} tokens) — a long "
                f"prompt could never make progress")

        # what the model says a cache has to hold, a layer an entry:
        # keys and values a position (a page pool) or state rows a
        # sequence (the state cache; docs/SERVING.md "State cache")
        layout = model.cache_layout()
        kv_layers = [i for i, lay in enumerate(layout)
                     if lay["kind"] == "kv"]
        self._state_layers = [i for i, lay in enumerate(layout)
                              if lay["kind"] == "state"]
        geom = {(layout[i]["kv_heads"], layout[i]["head_dim"],
                 layout[i]["scale"]) for i in kv_layers}
        if len(geom) != 1:
            raise MXNetError(
                "the engine keeps one page geometry: the model's "
                f"attention layers declare {sorted(geom)}")
        (H, D, self._attn_scale), = geom
        L = len(kv_layers)
        self._H, self._D = H, D
        # model layer -> its pool / its state rows among their own kind
        self._pool_of = {i: j for j, i in enumerate(kv_layers)}
        self._state_of = {i: j for j, i in enumerate(self._state_layers)}
        if self._state_layers:
            # a position's keys and values can be shared, resent or
            # rolled back; a sequence's state cannot (yet): every
            # feature that leans on the first refuses the second
            refused = [
                (prefix_cache, "prefix_cache=True: a cached prefix's "
                 "pages carry no state to resume from (state snapshots "
                 "at page boundaries are not built)"),
                (int(spec_k) > 0, "spec_k > 0: a rejected draft would "
                 "have advanced the state and it cannot be rolled back"),
                (kv_tiers is not None, "kv_tiers: tiers hold prefix "
                 "pages, which a state layer cannot resume from"),
                (kv_quant is not None, "kv_quant: not measured with "
                 "state layers"),
                (mesh is not None, "mesh: the state update kernel is "
                 "per-chip and the state cache is not sharded")]
            for hit, why in refused:
                if hit:
                    raise MXNetError(
                        "the model has state layers (recurrent rows a "
                        f"sequence, not pages a position); refusing {why}")
        # quantized KV pools (docs/SERVING.md "Quantized KV cache"):
        # int8/fp8 page payload + per-page symmetric scales. The amax
        # arrays are HOST-OWNED page metadata (np, one (P,) f32 per
        # layer per pool): every program that writes pages takes them
        # as traced data and returns them updated — the host pulls the
        # tiny arrays back on its existing per-step sync — and the
        # host resets a page's amax when the allocator hands it out
        # (a recycled page must not inherit its previous owner's
        # range, and a quarantined slot's poisoned scale dies with
        # the page). Everything below is gated on self._kv_spec, so
        # kv_quant=None is byte-for-byte the unquantized engine.
        self._kv_spec = kv_quant_spec(kv_quant)
        self.kv_quant = self._kv_spec.name if self._kv_spec else None
        # one (num_pages, H, page_size, 2 * D) pool a layer: a head's
        # keys | values side by side on the lanes (serve/paged_kv.py)
        self._kvpools = tuple(init_kv_pools(
            L, self.num_pages, H, self.page_size, D,
            self._dtype, quant=self._kv_spec))
        if self._kv_spec is not None:
            self._kamax = tuple(np.zeros((self.num_pages,), np.float32)
                                for _ in range(L))
            self._vamax = tuple(np.zeros((self.num_pages,), np.float32)
                                for _ in range(L))
        else:
            self._kamax = self._vamax = ()
        # the state cache: one array a named row a state layer,
        # (num_slots, *row shape), donated to and aliased through every
        # program as the pools are. A slot's rows are zeroed at (every)
        # admission and untouched while the slot is dead or prefilling.
        self._state_spec = [layout[i]["rows"] for i in self._state_layers]
        self._states = tuple(
            {name: jnp.zeros((self.num_slots,) + tuple(shape), dtype)
             for name, (shape, dtype) in rows.items()}
            for rows in self._state_spec)
        self.state_zero_trace_count = 0

        # model params are TRACED INPUTS of the decode/prefill programs
        # (not closure constants): warm-restarting new weights into a
        # live engine is then pure data — the jitted decode step is
        # reused at compile count 1 (see warm_start / test_serve.py)
        self._eng_params = [p for p in model.collect_params().values()]
        not_ready = [p.name for p in self._eng_params if p._data is None]
        if not_ready:
            raise MXNetError(f"uninitialized model parameters "
                             f"{not_ready}; call model.initialize()")
        self._param_vals = tuple(p.data()._data for p in self._eng_params)

        self._mesh = None
        if mesh is not None and dict(mesh.shape).get("tp", 1) > 1:
            # H-axis tp sharding through parallel.mesh; the step's jnp
            # ragged path partitions cleanly under jit (the Pallas
            # kernel is per-chip — shard_map wiring is future work)
            from ..parallel.mesh import named_sharding
            self._mesh = mesh
            sh = named_sharding(mesh, None, "tp", None, None)
            self._kvpools = tuple(jax.device_put(p, sh)
                                  for p in self._kvpools)
        self._interpret = interpret

        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise MXNetError(f"spec_k must be >= 0, got {self.spec_k}")
        if self.spec_k >= self.max_len:
            raise MXNetError(f"spec_k {self.spec_k} >= max_len "
                             f"{self.max_len}")
        self._spec_w = self.spec_k + 1       # verify window (queries/slot)
        self._draft_fn = draft_fn if draft_fn is not None \
            else make_ngram_drafter(max_order=int(draft_ngram))
        # adaptive draft gating: a slot whose last ``spec_patience``
        # draft windows were FULLY rejected stops drafting (probing
        # again on every ``spec_probe_every``-th decode step, all gated
        # slots on the SAME step so probes cost one wide step, not
        # many). Zero-draft steps then run the W=1 program — the
        # zero-agreement floor is the non-speculative engine's own
        # step, not a K+1-wide verify of hopeless drafts.
        # spec_patience=0 disables gating (draft every step).
        self.spec_patience = int(spec_patience)
        self.spec_probe_every = max(1, int(spec_probe_every))

        # host-side occupancy state — DATA, never shapes
        S = self.num_slots
        self._page_table = np.zeros((S, self.max_pages), np.int32)
        self._lengths = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._slot_keys = np.zeros((S, 2), np.uint32)
        # the sampling menu's per-slot state (serve/sampling.py): knob
        # vectors, the logit-bias table, and the token-count table the
        # penalties read — all pure data into the SAME programs
        # temperature rides, reset to exact-identity neutrals on slot
        # free so an unconfigured request costs one where-select
        V = model.vocab_size
        self._vocab = V
        self._top_k = np.zeros((S,), np.int32)
        self._top_p = np.ones((S,), np.float32)
        self._rep_pen = np.ones((S,), np.float32)
        self._pres_pen = np.zeros((S,), np.float32)
        self._logit_bias = np.zeros((S, V), np.float32)
        self._tok_counts = np.zeros((S, V), np.int32)
        self._mask_true: dict = {}   # W -> cached all-True (S, W, V)
        self._neutral_ops: dict = {}  # W -> committed neutral operands
        self._alloc = PageAllocator(self.num_pages)
        self._prefix = PrefixIndex(self.page_size) if prefix_cache \
            else None
        self._slots: List[Optional[_Slot]] = [None] * S
        self._queue: deque = deque()
        self._key = jax.random.PRNGKey(0)
        self._prefill_rr = 0

        # resilience state (docs/RESILIENCE.md)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.max_queue_delay_s = max_queue_delay_s
        self.guard_nonfinite = bool(guard_nonfinite)
        self.watchdog_steps = int(watchdog_steps)
        self.max_slot_wall_s = max_slot_wall_s
        self.stall_steps = int(stall_steps)
        self.health: dict = {o.value: 0 for o in Outcome}
        self.health_by_tier: dict = {
            t.value: {o.value: 0 for o in Outcome} for t in Tier}
        self._ewma_service_s: Optional[float] = None

        # SLO tiers (serve/slo.py): per-tier admission policy, slot
        # preemption and brownout degradation — all host-side DATA
        self._tier_policies = resolve_tier_policies(tier_policies)
        self.max_preemptions = int(max_preemptions)
        self.preemptions = 0                 # slots reclaimed by a
                                             # higher-tier admission
        if brownout is True:
            brownout = BrownoutController(
                delay_ref=max_queue_delay_s or 1.0)
        self._brownout = brownout            # None | BrownoutController

        # flight recorder (serve/events.py, docs/OBSERVABILITY.md):
        # ON by default (overhead banked <2%, BENCH_SERVE.json
        # recorder_overhead); ``recorder=False`` disables, passing an
        # existing FlightRecorder shares a timeline. ``component``
        # names this engine's lane (a Router renames its replicas'
        # default lanes to replica<i> at adoption).
        self.flight = resolve_recorder(recorder)
        self._component = str(component)
        if self._brownout is not None:
            # brownout transitions land on THIS engine's lane (the
            # controller itself is engine-agnostic — serve/slo.py)
            self._brownout.flight = self.flight

        # speculative-decoding observability (docs/SERVING.md): drafted
        # vs accepted counts feed accept_rate; per-request twins live on
        # Request.drafted_tokens / .accepted_tokens
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.spec_steps = 0                  # steps run K+1 wide
        self.spec_gated_steps = 0            # steps adaptive gating
                                             # suppressed all drafting
        self.truncate_steps = 0              # steps whose program sorted
                                             # (a live top-k / top-p slot)
        self.draw_steps = 0                  # steps whose program drew
                                             # (a live temperature slot)

        self.stop_hits = 0                   # stop-sequence terminals
        self.constrained_requests = 0        # admissions with a grammar
        self.decode_trace_count = 0          # W=1 decode program traces
        self.verify_trace_count = 0          # K+1-wide verify traces
        self.prefill_trace_count = 0         # dense + chunk, total
        self.prefill_trace_counts = {}       # ("dense"|"chunk", Tpad) -> n
        self.copy_trace_count = 0
        self.decode_steps = 0
        self.warm_restarts = 0
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0
        self.prefix_flushes = 0
        self.prefix_reclaimed_pages = 0
        self.max_step_prefill_tokens = 0

        # hierarchical cache tiers beneath the prefix index
        # (docs/SERVING.md "Hierarchical prefix cache"): demote
        # evicted-but-published pages to host DRAM (and DRAM overflow
        # to disk), re-admit by copy instead of recomputing prefill.
        # ``kv_tiers`` is a dict: {"dram_bytes": int, "disk_dir": str?,
        # "disk_bytes": int?} — or None for the untiered engine.
        self._tiers = None
        if kv_tiers is not None:
            if self._prefix is None:
                raise MXNetError("kv_tiers requires prefix_cache=True "
                                 "(tiers hold evicted PREFIX pages)")
            cfg = dict(kv_tiers)
            self._tiers = KVTierStore(
                self.page_size, cfg.pop("dram_bytes"),
                disk_dir=cfg.pop("disk_dir", None),
                disk_bytes=cfg.pop("disk_bytes", None),
                recorder=self.flight, component=self._component)
            if cfg:
                raise MXNetError(f"unknown kv_tiers keys: "
                                 f"{sorted(cfg)}")
        self.tier_demotions = 0          # pages captured HBM → DRAM
        self.tier_promotions = 0         # pages re-admitted by copy
        self.tier_hits = 0               # admissions a tier extended
        self.tier_hit_tokens = 0         # prompt tokens served by tiers
        self.tier_misses = 0             # tier consulted, nothing usable
        self.tier_crc_fallbacks = 0      # integrity check → recompute
        self.promote_trace_count = 0     # the one promotion program
        self.demote_trace_count = 0      # the one page-gather program

        # page transport (serve/transport.py): capsule traffic through
        # this engine — pages/bytes captured off it and installed into
        # it — plus the in-capsule page custody: a detached slot's
        # pages stay refcounted here (owned by the in-flight capsule,
        # keyed by the attempt's request_id) until the transfer lands
        # or falls back, so ``audit_pages`` sees every in-transit page
        self.migrated_out_pages = 0      # pages captured into capsules
        self.migrated_in_pages = 0       # pages installed from capsules
        self.migrated_out_bytes = 0      # capsule wire bytes, outbound
        self.migrated_in_bytes = 0       # capsule wire bytes, inbound
        self._capsule_pages: Dict[int, List[int]] = {}
        # fleet-aware preemption (serve/router.py fleet_preempt): set
        # by the router, called with the victim's request_id BEFORE an
        # engine-internal preemption — True means the fleet moved the
        # slot to a sibling and this engine must not evict/terminal it
        self.preempt_handoff = None

        self._decode_step = jax.jit(self._decode_step_fn,
                                    donate_argnums=(1, 17))
        self._zero_state_jit = None
        self._prefill_jits = {}          # bucket_pages -> jitted dense fn
        self._chunk_jits = {}            # bucket_pages -> jitted chunk fn
        # program name -> (jitted fn, abstract args of its first
        # dispatch), for compiled_program_text
        self._programs = {}
        self._copy_jit = None
        self._promote_jit = None
        self._gather_jit = None

    def _dispatch(self, name, fn, *args):
        """Run one compiled serving program, remembering the abstract
        signature of each program's first dispatch."""
        if name not in self._programs:
            self._programs[name] = (fn, jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(np.shape(a),
                                               jnp.result_type(a)), args))
        return fn(*args)

    def compiled_program_text(self, name) -> str:
        """Optimized HLO text of a serving program this engine has
        dispatched, as the backend compiled it: ``"decode"`` /
        ``"verify"`` (the W=1 / W>1 step), ``("chunk", Cpad)``,
        ``("dense", Tpad)`` — the keys of ``prefill_trace_counts``.
        Kernels are in it, so ``chip_smoke.py`` reads the ragged Mosaic
        custom calls out of the program that ran. The lowering
        re-traces the Python body; the trace counters are put back."""
        if name not in self._programs:
            raise MXNetError(
                f"no program {name!r} dispatched yet; have "
                f"{sorted(map(str, self._programs))}")
        fn, args = self._programs[name]
        counts = (self.decode_trace_count, self.verify_trace_count,
                  self.prefill_trace_count,
                  dict(self.prefill_trace_counts))
        try:
            return fn.lower(*args).compile().as_text()
        finally:
            (self.decode_trace_count, self.verify_trace_count,
             self.prefill_trace_count, self.prefill_trace_counts) = counts

    # ------------------------------------------------------------- #
    # traced programs
    # ------------------------------------------------------------- #

    def _sample_one(self, logits, temp, pos_key, top_k=None, top_p=None,
                    rep_pen=None, pres_pen=None, counts=None, bias=None,
                    mask=None):
        """Greedy/temperature sample of ONE token from (V,) logits.
        ``pos_key`` is the request's RNG key folded with the sampled
        token's SEQUENCE POSITION (the engine-wide convention: the draw
        for position p uses ``fold_in(fold_in(request_key, p), 0)``),
        so whichever program computes it — dense prefill, chunk tail,
        or a verify emission point — produces the identical draw.

        The sampling-menu knobs (serve/sampling.py) are traced scalars
        / (V,) rows; None (a trace-time constant) means the caller has
        no menu state, which compiles the plain path — the prefill
        programs always pass real values."""
        if top_k is not None:
            logits = constrain_logits(logits, temp, counts, bias, mask,
                                      top_k, top_p, rep_pen, pres_pen)
        cat_key = jax.random.fold_in(pos_key, 0)
        greedy = jnp.argmax(logits, axis=-1)
        samp = jax.random.categorical(
            cat_key, logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6),
            axis=-1)
        return jnp.where(temp > 0, samp, greedy).astype(jnp.int32)

    @contextlib.contextmanager
    def _model_scope(self, param_vals):
        """What a program runs the model's math inside: every model
        Parameter points at the traced ``param_vals`` (the SPMDTrainer
        pure_loss idiom; this is what makes weights DATA to the compiled
        programs, the eager arrays come back after), under the hybrid
        trace and in inference mode."""
        saved = [p._data for p in self._eng_params]
        for p, v in zip(self._eng_params, param_vals):
            p._data = NDArray(v)
        try:
            with _hybrid_trace_scope(), \
                    autograd._ModeScope(recording=False, training=False):
                yield
        finally:
            for p, s in zip(self._eng_params, saved):
                p._data = s

    def _write_kv(self, write, write_q, pools, kamax, vamax, i, k, v,
                  *where):
        """Write layer ``i``'s new keys | values (joined on the lanes,
        the pool's format) at ``where`` by the program's own write
        function, or its quantizing twin where the pools hold codes;
        ``pools`` / ``kamax`` / ``vamax`` are the program's lists,
        updated in place. Returns what reading the pool back takes: the
        key and value scales (None unquantized) and the type attention
        runs in."""
        kv = jnp.concatenate([k, v], axis=-1)
        spec = self._kv_spec
        i = self._pool_of[i]
        if spec is None:
            pools[i] = write(pools[i], kv, *where)
            return None, None, pools[i].dtype
        pools[i], kamax[i], vamax[i] = write_q(
            pools[i], kamax[i], vamax[i], kv, *where, spec)
        return (page_scales(kamax[i], spec), page_scales(vamax[i], spec),
                self._dtype)

    def _state_closure(self, states, slot=None):
        """(the program's list of state rows, the ``state(i, update)``
        closure a model's ``cached_forward`` takes): layer ``i``'s rows
        go to the model's ``update`` and what it hands back is kept, so
        the model never learns where rows live. ``slot`` None: the rows
        of every slot, as they lie (a decode step; the model's update
        leaves a dead slot's rows as they were). A traced ``slot``: that
        one slot's rows, cut out and put back (a prefill program).
        read_slot_rows / write_slot_rows through this module's globals:
        the dropped-carry test patches them here."""
        new_states = [dict(rows) for rows in states]

        def state(i, update):
            j = self._state_of[i]
            rows = new_states[j] if slot is None \
                else read_slot_rows(new_states[j], slot)
            y, new = update(rows, interpret=self._interpret)
            new_states[j] = new if slot is None \
                else write_slot_rows(new_states[j], new, slot)
            return y

        return new_states, state

    def _zero_state_fn(self, states, slot):
        """A slot's state rows back to zero, every state layer, in
        place: what admission runs before a prompt's first position.
        ``slot`` is traced — one compile, ever."""
        self.state_zero_trace_count += 1     # trace-time only
        return tuple(zero_slot_rows(rows, slot) for rows in states)

    def _zero_state(self, slot_idx: int) -> bool:
        """Zero ``slot_idx``'s state rows; False for a model that keeps
        none."""
        if not self._states:
            return False
        if self._zero_state_jit is None:
            self._zero_state_jit = jax.jit(self._zero_state_fn,
                                           donate_argnums=(0,))
        self._states = self._zero_state_jit(self._states,
                                            np.int32(slot_idx))
        return True

    def _ragged_attn(self, q, pool, page_table, lengths, ks=None,
                     vs=None):
        if self._mesh is not None:
            return ragged_attention_reference(q, pool, page_table,
                                              lengths,
                                              scale=self._attn_scale,
                                              k_scale=ks, v_scale=vs)
        return ragged_paged_attention(q, pool, page_table, lengths,
                                      scale=self._attn_scale,
                                      interpret=self._interpret,
                                      k_scale=ks, v_scale=vs)

    def _verify_attn(self, q, pool, page_table, lengths, draft_len,
                     ks=None, vs=None):
        """Multi-query (speculative verify) decode attention: q is
        (S, W, H, D), ``lengths`` counts keys visible to query row 0
        (0 = dead slot), ``draft_len`` the slot's real draft count
        bounding the kernel's V-select at the freshly-written extent.
        The W = 1 narrow program routes through ``_ragged_attn`` — the
        PR 2 single-query decode step, LITERALLY (on CPU the verify
        reference's row 0 is the same ``_reference_core`` call, so
        this changes nothing there; on TPU it keeps the specialized
        decode kernel the narrow path's kernel). Under tp meshes the
        jnp reference partitions cleanly, same as the single-query
        path."""
        if q.shape[1] == 1:
            out = self._ragged_attn(q[:, 0], pool, page_table,
                                    lengths, ks, vs)
            return out[:, None]
        if self._mesh is not None:
            return ragged_verify_reference(q, pool, page_table,
                                           lengths,
                                           scale=self._attn_scale,
                                           k_scale=ks, v_scale=vs)
        return ragged_verify_attention(q, pool, page_table, lengths,
                                       draft_len=draft_len,
                                       scale=self._attn_scale,
                                       interpret=self._interpret,
                                       k_scale=ks, v_scale=vs)

    def _prefill_attn(self, q, pool, page_row, start, n_real,
                      ks=None, vs=None):
        if self._mesh is not None:
            return ragged_prefill_reference(q, pool, page_row, start,
                                            scale=self._attn_scale,
                                            n_real=n_real, k_scale=ks,
                                            v_scale=vs)
        return ragged_prefill_attention(q, pool, page_row, start,
                                        n_real=n_real,
                                        scale=self._attn_scale,
                                        interpret=self._interpret,
                                        k_scale=ks, v_scale=vs)

    def _accept_emit(self, logits, tokens, draft_len, temps, slot_keys,
                     pos, act, top_k=None, top_p=None, rep_pen=None,
                     pres_pen=None, counts=None, bias=None, mask=None):
        """On-device draft acceptance — the speculative-decoding core.

        ``logits`` (S, W, V) scores token positions ``pos + 1``;
        ``tokens[:, 0]`` is the last accepted token, ``tokens[:, 1:]``
        the draft candidates (column j+1 proposed for position
        ``pos[:, j] + 1``). Greedy slots accept the longest prefix of
        drafts matching the argmax chain — BIT-IDENTICAL to running
        that many sequential decode steps, since an accepted draft IS
        the argmax its predecessor produced. Temperature slots use
        rejection sampling against the deterministic draft proposal
        (q = point mass): draft d at position p is accepted with
        probability softmax(logits/T)[d]; on rejection the emission is
        sampled from the residual (softmax with d's mass removed) — so
        the emitted distribution is exactly the non-speculative one.
        Every RNG draw is keyed by ``fold_in(request_key, position)``
        (categorical: sub-fold 0, acceptance uniform: sub-fold 1) —
        reproducible per request, independent of occupancy and K.

        Returns ``(emitted (S, W) int32, n_emit (S,) int32)``: columns
        ``[0, n_emit)`` of ``emitted`` are real tokens (accepted drafts
        then the correction/bonus sample), later columns are dead.

        Round 18: the acceptance tests and the residual both run over
        the CONSTRAINED target distribution (serve/sampling.py — bias,
        penalties with in-window count updates, top-k/top-p
        truncation, grammar mask), so speculation stays
        distribution-correct under truncated/masked proposals: a
        drafted token the constraint forbids has p̃(d) = 0 and is
        rejected; the correction resamples from the masked residual.
        Column j's penalty counts include the drafts at columns <= j —
        exactly the history a sequential decode would have seen —
        computed in-program from the (known) draft block. The
        degenerate single-allowed-token case (empty residual) force-
        accepts: p̃ is that point mass.

        The menu's truncations and the whole temperature path each sit
        under a ``lax.cond`` of this one program, taken when a LIVE
        slot asks (top-k / top-p on; temperature > 0): a step of
        greedy, menu-free slots pays for neither, a mixed step pays
        for every row as before, and either way each row reads what
        the unbranched code gave it (``truncate_steps`` /
        ``draw_steps`` count the steps that took them)."""
        S, W = tokens.shape
        V = logits.shape[-1]
        jj = lax.broadcasted_iota(jnp.int32, (S, W), 1)

        if top_k is not None:
            # in-window history: column j scores the token AFTER
            # tokens[:, 0..j], so its penalty counts are the base
            # (prompt + emitted, incl. tokens[:, 0]) plus the one-hot
            # sum of draft columns 1..j
            oh = jax.nn.one_hot(tokens, V, dtype=jnp.int32)
            win_counts = counts[:, None, :] + \
                jnp.cumsum(oh, axis=1) - oh[:, :1]
            # a dead row (free, prefilling or stalled: its emission is
            # never read) asks for nothing: the truncation branch is
            # taken for what a LIVE slot asks
            logits = constrain_logits(
                logits, temps[:, None], win_counts, bias[:, None, :],
                mask, jnp.where(act, top_k, 0)[:, None],
                jnp.where(act, top_p, 1.0)[:, None],
                rep_pen[:, None], pres_pen[:, None])
        greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        # column j tests/replaces the token at position jpos[:, j] —
        # the draft in tokens column j + 1 (the wrapped last column is
        # never valid: draft_len <= W - 1)
        d_next = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
        valid = jj < draft_len[:, None]

        def draw(logits):
            """The temperature path, every row: taken when a live slot
            has temperature > 0 (greedy rows select their argmax chain
            out of it, as they always did)."""
            jpos = pos + 1               # position of column j's token
            pos_keys = jax.vmap(
                lambda key, row: jax.vmap(
                    lambda p: jax.random.fold_in(key, p))(row)
            )(slot_keys, jpos)                           # (S, W, 2)
            cat_keys = jax.vmap(jax.vmap(
                lambda k: jax.random.fold_in(k, 0)))(pos_keys)
            acc_keys = jax.vmap(jax.vmap(
                lambda k: jax.random.fold_in(k, 1)))(pos_keys)
            u = jax.vmap(jax.vmap(jax.random.uniform))(acc_keys)
            scaled = logits.astype(jnp.float32) / \
                jnp.maximum(temps, 1e-6)[:, None, None]
            logp = jax.nn.log_softmax(scaled, axis=-1)   # (S, W, V)
            p_next = jnp.take_along_axis(logp, d_next[..., None],
                                         axis=-1)[..., 0]  # log p_j(d)
            # residual for a REJECTED draft at column j: q was a point
            # mass at d, so max(p - q, 0) is p with d's mass removed —
            # mask d's logit out and renormalize via the categorical
            # itself. Columns with no draft (j >= draft_len) sample
            # plain p — the bonus token when every draft was accepted.
            res_logits = scaled + jax.nn.one_hot(
                d_next, V, dtype=jnp.float32) * \
                jnp.where(valid, _NEG_BIG, 0.0)[..., None]
            # an empty residual (every unit of mass sits on the draft —
            # e.g. a grammar state with ONE legal token) means p̃(d) = 1:
            # force acceptance instead of resampling from nothing.
            # Tested on the UNSCALED constrained logits: a temperature
            # divide could float a masked -1e30 back over the threshold
            res_empty = ~jnp.any(
                (logits + jax.nn.one_hot(d_next, V, dtype=jnp.float32) *
                 _NEG_BIG) > _NEG_BIG / 2, axis=-1)
            accept = jnp.where((temps > 0)[:, None],
                               (jnp.log(u) < p_next) | res_empty,
                               d_next == greedy_tok)
            samp = jax.vmap(jax.vmap(jax.random.categorical))(
                cat_keys, res_logits).astype(jnp.int32)
            return accept, jnp.where((temps > 0)[:, None], samp,
                                     greedy_tok)

        accept, final = lax.cond(
            jnp.any((temps > 0) & act), draw,
            lambda _: (d_next == greedy_tok, greedy_tok), logits)
        chain = jnp.cumprod((accept & valid).astype(jnp.int32), axis=1)
        n_acc = jnp.sum(chain, axis=1).astype(jnp.int32)
        emitted = jnp.where(jj < n_acc[:, None], d_next, final)
        n_emit = jnp.where(act, n_acc + 1, 0).astype(jnp.int32)
        return emitted, n_emit

    def _decode_step_fn(self, param_vals, pools, kamax, vamax,
                        tokens, draft_len, page_table, lengths, temps,
                        slot_keys, top_k, top_p, rep_pen, pres_pen,
                        counts, bias, mask, states=()):
        """ONE decode/verify step for every slot: W token positions per
        slot — the last accepted token plus up to W - 1 draft
        candidates — embedded, written into the tail pages, and scored
        in this single program call. W is taken from ``tokens``'
        (S, W) shape, so the SAME function yields the engine's two
        decode-family programs: the W=1 decode step (bitwise the PR 2
        single-token step — it runs whenever no slot drafted, so the
        zero-agreement floor pays no verify width) and the
        W = spec_k + 1 verify step. Each traces exactly once
        (``decode_trace_count`` / ``verify_trace_count``); within a
        width, occupancy, drafts, acceptance, sampling keys AND
        weights are data."""
        if tokens.shape[1] == 1:             # trace-time only
            self.decode_trace_count += 1
        else:
            self.verify_trace_count += 1
        S, ps = self.num_slots, self.page_size
        W = tokens.shape[1]
        act = lengths > 0
        jj = lax.broadcasted_iota(jnp.int32, (S, W), 1)
        pos = lengths[:, None] + jj          # column j's token position
        used = jj <= draft_len[:, None]      # real token columns
        # K/V writes: real columns land at their position's page (the
        # host pre-mapped the whole draft window); padded columns and
        # dead slots write to the null page, harmless and never read
        # unmasked
        page_idx = jnp.clip(pos // ps, 0, self.max_pages - 1)
        write_page = jnp.where(act[:, None] & used,
                               jnp.take_along_axis(page_table, page_idx,
                                                   axis=1),
                               NULL_PAGE)
        write_off = pos % ps
        # padded columns of a nearly-finished slot can index past the
        # table — clamp for the (masked, discarded) embedding lookup
        emb_pos = jnp.minimum(pos, self.model.max_length - 1)
        eff_len = jnp.where(act, lengths + 1, 0)
        new_pools = list(pools)
        new_ka, new_va = list(kamax), list(vamax)
        new_states, state = self._state_closure(states)

        def attend(i, q, k, v):              # (S, W, H, D)
            # quantize-at-write: the page's scales grow with the
            # window's amax and existing codes requantize in the same
            # scatter — pure traced data, no new programs (trace counts
            # stay asserted at 1)
            ks, vs, adt = self._write_kv(
                write_block_kv, write_block_kv_q, new_pools, new_ka,
                new_va, i, k, v, write_page, write_off)
            out = self._verify_attn(q.astype(adt),
                                    new_pools[self._pool_of[i]],
                                    page_table, eff_len, draft_len,
                                    ks, vs)
            return out.astype(q.dtype)

        with self._model_scope(param_vals):
            logits = self.model.cached_forward(
                tokens, emb_pos, attend, state=state,
                real=act[:, None] & used)                 # (S, W, V)
        emitted, n_emit = self._accept_emit(
            logits, tokens, draft_len, temps, slot_keys, pos, act,
            top_k=top_k, top_p=top_p, rep_pen=rep_pen,
            pres_pen=pres_pen, counts=counts, bias=bias, mask=mask)
        new_lengths = jnp.where(act, lengths + n_emit, 0)
        # per-slot non-finite guard: one logits reduction over the USED
        # verify columns (later columns may legitimately read stale
        # draft K/V — their logits are dead data), SIGN-ENCODED into
        # the emitted tokens (column 0 reads -t - 1 on a poisoned slot)
        # — pure data riding the existing token transfer, so the
        # jit-once contract is untouched (asserted), a poisoned slot is
        # visible the step it poisons, and NOTHING from a poisoned
        # verify step is ever recorded (accepted drafts included; see
        # step()). Cost banked in BENCH_SERVE.json guard_overhead.
        if self.guard_nonfinite:
            bad = jnp.any(jnp.any(~jnp.isfinite(logits), axis=-1) &
                          used, axis=-1) & act
            emitted = jnp.where(bad[:, None], -emitted - 1, emitted)
        return (tuple(new_pools), tuple(new_ka), tuple(new_va), emitted,
                n_emit, new_lengths, tuple(new_states))

    def _prefill_fn(self, param_vals, pools, kamax, vamax,
                    ids, t0, pages, temp, key, top_k, top_p, rep_pen,
                    pres_pen, counts, bias, vocab_mask, states=(),
                    slot=None):
        """Prompt forward for ONE request (ids (1, Tpad) padded): dense
        causal attention inside the prompt (the prompt attends only
        itself), K/V scattered into the slot's pages, and the FIRST
        generated token sampled from the last real position's logits.
        Tpad is the bucket shape — one compile per bucket, counted in
        ``prefill_trace_count``."""
        self.prefill_trace_count += 1        # trace-time only
        key_tc = ("dense", ids.shape[1])
        self.prefill_trace_counts[key_tc] = \
            self.prefill_trace_counts.get(key_tc, 0) + 1
        Tpad = ids.shape[1]
        pos = lax.broadcasted_iota(jnp.int32, (1, Tpad), 1)
        pos_q = lax.broadcasted_iota(jnp.int32, (Tpad, Tpad), 0)
        pos_k = lax.broadcasted_iota(jnp.int32, (Tpad, Tpad), 1)
        mask = ((pos_k <= pos_q) & (pos_k < t0))[None, None]
        new_pools = list(pools)
        new_ka, new_va = list(kamax), list(vamax)
        new_states, state = self._state_closure(states, slot)

        def attend(i, q, k, v):              # (1, Tpad, H, D)
            # quantized pools take the prompt's pages at FRESH per-page
            # scales; the prompt's own attention runs on the exact
            # pre-quantization K/V (only future paged reads pay the
            # quantization error)
            self._write_kv(write_prompt_kv, write_prompt_kv_q, new_pools,
                           new_ka, new_va, i, k[0], v[0], pages)
            rep = q.shape[2] // k.shape[2]
            if rep > 1:
                k, v = (jnp.repeat(t, rep, axis=2) for t in (k, v))
            return _sdpa(q, k, v, mask=mask, scale=self._attn_scale)

        with self._model_scope(param_vals):
            logits = self.model.cached_forward(
                ids, pos, attend, last_row=t0 - 1, state=state,
                real=pos < t0)[:, 0]
        # the first generated token occupies position t0: its draw is
        # keyed by fold_in(request_key, t0), the engine-wide convention
        tok = self._sample_one(logits[0], temp,
                               jax.random.fold_in(key, t0),
                               top_k, top_p, rep_pen, pres_pen,
                               counts, bias, vocab_mask)
        if self.guard_nonfinite:             # sign-encoded, see decode
            tok = jnp.where(jnp.any(~jnp.isfinite(logits)),
                            -tok - 1, tok)
        return (tuple(new_pools), tuple(new_ka), tuple(new_va), tok,
                tuple(new_states))

    def _chunk_prefill_fn(self, param_vals, pools, kamax,
                          vamax, ids, start, n_real, page_row, temp,
                          key, top_k, top_p, rep_pen, pres_pen, counts,
                          bias, vocab_mask, states=(), slot=None):
        """ONE prefill chunk of ONE slot's prompt: ids (1, Cpad) holds
        ``n_real`` prompt tokens at absolute positions ``start + i``.
        Their K/V is scattered into the slot's pages (padded tokens land
        in the null page), then each chunk query attends the slot's
        already-populated paged prefix plus the causal intra-chunk part
        (``ragged_prefill_attention``). The last real row's logits are
        always computed and sampled — the host uses the token only when
        this is the final chunk. Cpad is the bucket shape; start /
        lengths / pages / weights are data, so each chunk bucket
        compiles exactly once (same contract as decode)."""
        self.prefill_trace_count += 1        # trace-time only
        key_tc = ("chunk", ids.shape[1])
        self.prefill_trace_counts[key_tc] = \
            self.prefill_trace_counts.get(key_tc, 0) + 1
        ps = self.page_size
        Cpad = ids.shape[1]
        pos = start + lax.broadcasted_iota(jnp.int32, (1, Cpad), 1)
        live = lax.broadcasted_iota(jnp.int32, (Cpad,), 0) < n_real
        page_idx = jnp.clip(pos[0] // ps, 0, self.max_pages - 1)
        tok_pages = jnp.where(live, page_row[page_idx], NULL_PAGE)
        tok_off = pos[0] % ps
        new_pools = list(pools)
        new_ka, new_va = list(kamax), list(vamax)
        new_states, state = self._state_closure(states, slot)

        def attend(i, q, k, v):              # (1, Cpad, H, D)
            # write_token_kv through this module's global: the
            # benchmark's never-written-page control patches it here
            ks, vs, adt = self._write_kv(
                write_token_kv, write_token_kv_q, new_pools, new_ka,
                new_va, i, k[0], v[0], tok_pages, tok_off)
            out = self._prefill_attn(q[0].astype(adt),
                                     new_pools[self._pool_of[i]],
                                     page_row, start, n_real, ks, vs)
            return out.astype(q.dtype)[None]

        with self._model_scope(param_vals):
            logits = self.model.cached_forward(
                ids, pos, attend, last_row=n_real - 1, state=state,
                real=live[None])[:, 0]
        # on the FINAL chunk start + n_real == t0, so the draw key
        # matches the dense prefill's exactly — chunked vs monolithic
        # prefill emit the identical first token even at temperature
        tok = self._sample_one(logits[0], temp,
                               jax.random.fold_in(key, start + n_real),
                               top_k, top_p, rep_pen, pres_pen,
                               counts, bias, vocab_mask)
        if self.guard_nonfinite:             # sign-encoded, see decode
            tok = jnp.where(jnp.any(~jnp.isfinite(logits)),
                            -tok - 1, tok)
        return (tuple(new_pools), tuple(new_ka), tuple(new_va), tok,
                tuple(new_states))

    def _copy_page_fn(self, pools, src, dst):
        """COW boundary copy: duplicate one page's K/V across every
        layer, so the cached partial page becomes this slot's private
        page (the cached original stays read-only for its sharers).
        src/dst are traced scalars — one compile, ever."""
        self.copy_trace_count += 1           # trace-time only
        return tuple(p.at[dst].set(p[src]) for p in pools)

    def _copy_page(self, src: int, dst: int):
        if self._copy_jit is None:
            self._copy_jit = jax.jit(self._copy_page_fn,
                                     donate_argnums=(0,))
        self._kvpools = self._copy_jit(self._kvpools, np.int32(src),
                                       np.int32(dst))
        if self._kv_spec is not None:
            # the scale is page metadata: a COW copy carries its
            # source's scale (the codes were copied verbatim), and the
            # suffix writes grow it from there
            for a in self._kamax:
                a[dst] = a[src]
            for a in self._vamax:
                a[dst] = a[src]

    def _promote_page_fn(self, pools, kpage, vpage, dst):
        """Write one demoted page's payload (per-layer (H, ps, D) key
        and value host arrays, traced as data, joined on the lanes
        here: the host format keeps them apart) into page ``dst`` of
        every pool —
        the tier PROMOTION program. Like the COW copy it is jitted
        once with donated pools and traced operands: re-admitting a
        page from DRAM or disk is data movement, never a new program
        and never a prefill recompute."""
        self.promote_trace_count += 1        # trace-time only
        return tuple(
            p.at[dst].set(jnp.concatenate([kpg, vpg], -1).astype(p.dtype))
            for p, kpg, vpg in zip(pools, kpage, vpage))

    def _promote_page(self, k_payload, v_payload, kamax, vamax,
                      dst: int):
        if self._promote_jit is None:
            self._promote_jit = jax.jit(self._promote_page_fn,
                                        donate_argnums=(0,))
        self._kvpools = self._promote_jit(
            self._kvpools, tuple(k_payload), tuple(v_payload),
            np.int32(dst))
        if self._kv_spec is not None:
            # scale metadata rides back with the codes: the payload
            # was captured at demotion with exactly these amaxes
            for l, a in enumerate(self._kamax):
                a[dst] = kamax[l]
            for l, a in enumerate(self._vamax):
                a[dst] = vamax[l]

    def _gather_page_fn(self, pools, page):
        """Demotion capture: slice one page out of EVERY pool in one
        program call, its key lanes and its value lanes apart (the
        host format of tiers and capsules, whatever the pool's). Naively ``np.asarray(pool[page])`` per layer
        costs 2L separate dispatches per demoted page — on a small
        host that overhead alone made re-admission-by-copy slower
        than the recompute it replaces. One program, traced once
        (``page`` is a traced scalar), then a single device_get."""
        self.demote_trace_count += 1         # trace-time only
        D = self._D
        pages = [p[page] for p in pools]
        return (tuple(pg[..., :D] for pg in pages),
                tuple(pg[..., D:] for pg in pages))

    def gather_page(self, page: int) -> tuple:
        """One page's wire/at-rest payload: per-layer (H, ps, D)
        host arrays plus the per-layer amax pair on quantized pools
        (int8/fp8 codes + one f32 scale — the 4x-denser form), None
        otherwise. One jitted gather program (traced once) and ONE
        device_get — shared by tier demotion and page transport, so
        a capture never compiles a second program."""
        if self._gather_jit is None:
            self._gather_jit = jax.jit(self._gather_page_fn)
        k_payload, v_payload = jax.device_get(
            self._gather_jit(self._kvpools, np.int32(page)))
        kamax = vamax = None
        if self._kv_spec is not None:
            kamax = np.asarray([a[page] for a in self._kamax],
                               np.float32)
            vamax = np.asarray([a[page] for a in self._vamax],
                               np.float32)
        return k_payload, v_payload, kamax, vamax

    def _demote_entry(self, key: bytes, ent) -> None:
        """Capture an evicted-but-published page's payload into the
        cache tiers BEFORE its page returns to the free list (the
        ``demote`` callback threaded through PrefixIndex.reclaim).
        For quantized pools the payload is the page's int8/fp8 codes
        plus its per-layer amax — the 4x-denser at-rest form; for
        unquantized pools the raw-dtype page."""
        k_payload, v_payload, kamax, vamax = self.gather_page(ent.page)
        if self._tiers.put(key, ent.tokens, ent.depth, k_payload,
                           v_payload, kamax, vamax):
            self.tier_demotions += 1
            self.flight.emit(self._component, EventType.CACHE_DEMOTE,
                             entity=f"tier:{key.hex()[:16]}",
                             tier="dram", depth=ent.depth)

    def _reclaim_prefix(self, n: int) -> int:
        """Reclaim ``n`` pages from the prefix index, demoting every
        victim's payload into the cache tiers when they are on."""
        demote = self._demote_entry if self._tiers is not None else None
        return self._prefix.reclaim(n, self._alloc, demote)

    def _reset_page_amax(self, pages):
        """Zero the scale metadata of freshly-allocated pages (host-
        side np — the arrays are host-owned between program calls).
        Pages are identity-free and never cleared on reuse; their
        SCALE must be, or a recycled page would quantize its new
        owner's rows against the previous owner's range (including a
        quarantined slot's poisoned scale)."""
        if self._kv_spec is None or not pages:
            return
        idx = np.asarray(list(pages), np.int64)
        for a in self._kamax:
            a[idx] = 0.0
        for a in self._vamax:
            a[idx] = 0.0

    def _pull_amax(self, ka, va):
        """Re-take host ownership of the scale metadata a program just
        updated (``np.array`` — a mutable COPY, never a read-only view
        of the device buffer; the host resets entries in place)."""
        if self._kv_spec is None:
            return
        self._kamax = tuple(np.array(a, np.float32) for a in ka)
        self._vamax = tuple(np.array(a, np.float32) for a in va)

    # ------------------------------------------------------------- #
    # host-side scheduler
    # ------------------------------------------------------------- #

    @property
    def active_count(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def _lazy_debt(self) -> int:
        """Pages promised at admission but not yet physically held."""
        return sum(s.reserved_pages - len(s.refs)
                   for s in self._slots if s is not None)

    # health counters (asserted consistent with per-request outcomes in
    # tests/test_resilience.py)
    @property
    def completed(self) -> int:
        return self.health[Outcome.EOS.value] + \
            self.health[Outcome.MAX_TOKENS.value] + \
            self.health[Outcome.STOP.value]

    @property
    def shed(self) -> int:
        return self.health[Outcome.SHED.value]

    @property
    def expired(self) -> int:
        return self.health[Outcome.DEADLINE_EXPIRED.value]

    @property
    def quarantined(self) -> int:
        return self.health[Outcome.FAILED_NONFINITE.value]

    @property
    def unservable(self) -> int:
        return self.health[Outcome.FAILED_UNSERVABLE.value]

    def _retry_hint(self) -> float:
        """The machine-readable backoff hint attached to every
        retryable terminal: the EWMA of observed slot-residence times
        (how long until capacity realistically frees), or a small
        default before a first completion calibrates it."""
        return self._ewma_service_s if self._ewma_service_s else 0.05

    def _record_terminal(self, request: Request, outcome: Outcome,
                         detail: str = "",
                         retry_after: Optional[float] = None):
        """The single point where a request becomes terminal — exactly
        once, with the health counter kept consistent. Every
        shed/deadline-class (``Outcome.retryable``) terminal carries a
        ``retry_after_s`` hint — callers may pass a sharper estimate,
        but no retryable outcome ever leaves without one (the single
        backoff contract clients and the fleet router consume)."""
        if request.outcome is not None:
            raise MXNetError(
                f"request already terminal ({request.outcome}) — "
                f"double-finish is an engine bug")
        if retry_after is None and outcome.retryable:
            retry_after = self._retry_hint()
        request.outcome = outcome
        request.detail = detail
        request.retry_after_s = retry_after
        request.finish_time = time.perf_counter()
        self.health[outcome.value] += 1
        self.health_by_tier[request.tier.value][outcome.value] += 1
        # the TERMINAL event (and the latency histograms it feeds) are
        # emitted HERE and only here — exactly-once by the same
        # construction as the outcome itself (serve/events.py). The
        # enabled gate keeps the O(tokens) gap derivation off the
        # recorder=False path entirely.
        if self.flight.enabled:
            self.flight.emit(self._component, EventType.TERMINAL,
                             request_id=request.request_id,
                             **terminal_fields(request))

    def _tier_policy(self, tier: Tier) -> TierPolicy:
        return self._tier_policies[tier]

    @property
    def brownout_level(self) -> int:
        return self._brownout.level if self._brownout is not None else 0

    def _observe_service(self, t_admit: float):
        """EWMA of SLOT-RESIDENCE time (admit -> finish) for completed
        requests — the unit the queue-delay estimate multiplies. NOT
        submit -> finish: that would fold past queue wait back into the
        estimate and double-count delay under load."""
        served = time.perf_counter() - t_admit
        self._ewma_service_s = served if self._ewma_service_s is None \
            else 0.2 * served + 0.8 * self._ewma_service_s

    def _estimated_queue_delay(self, tier: Optional[Tier] = None) \
            -> Optional[float]:
        """Rough admission-delay estimate for a NEWLY submitted
        request: how many service generations must complete before it
        gets a slot, scaled by the EWMA of observed slot-residence
        times. Zero when the queue fits today's free slots — an idle
        engine must never shed on its own steady-state latency. None
        until a first completion calibrates the EWMA.

        ``tier`` scopes the backlog to the requests that will actually
        be admitted ahead of (or with) that tier — priority admission
        means a queue full of BATCH work does not delay a LATENCY
        arrival, so it must not shed one either. None counts
        everything (the tierless view health_snapshot exports)."""
        if self._ewma_service_s is None:
            return None
        if tier is None:
            ahead = len(self._queue)
        else:
            ahead = sum(1 for q in self._queue
                        if q.tier.order <= tier.order)
        free = self.num_slots - self.active_count
        if ahead < free:
            return 0.0
        waves = (ahead - free) // self.num_slots + 1
        return waves * self._ewma_service_s

    def health_snapshot(self) -> dict:
        """A CONSISTENT, detached copy of the engine's health state.

        ``engine.health`` is a live-mutated dict — a scraper (or the
        fleet router's scheduling read) iterating it while the
        scheduler records terminals can see torn state, and anything
        that stores the reference sees values silently change under
        it. This returns a snapshot taken in one pass — outcome
        counters plus the scheduling signals the router routes on
        (queue depth, free slots, EWMA service time, estimated
        admission delay) — that never mutates after return. All
        ``serve_bench``/``chaos_bench`` reporting and the router's
        least-delay spill read through here, never through the live
        dict."""
        bo = self._brownout
        return {
            "outcomes": dict(self.health),
            "outcomes_by_tier": {t: dict(d) for t, d in
                                 self.health_by_tier.items()},
            "queue_depth": len(self._queue),
            "queue_depth_by_tier": {
                t.value: sum(1 for q in self._queue if q.tier is t)
                for t in Tier},
            "active_slots": self.active_count,
            "free_slots": self.num_slots - self.active_count,
            "num_slots": self.num_slots,
            "ewma_service_s": self._ewma_service_s,
            "estimated_queue_delay_s": self._estimated_queue_delay(),
            # the PRIORITY tiers' delay (LATENCY+STANDARD backlog
            # only): the brownout controller's delay signal — BATCH
            # queue depth must not drive it, or the level-3 clamp
            # would sustain the very signal that raised it (the
            # clamped queue never drains → the estimate never falls
            # → the clamp never lifts; deadlock found end-to-end)
            "estimated_queue_delay_priority_s":
                self._estimated_queue_delay(Tier.STANDARD),
            "free_pages": self._alloc.free_count,
            # KV-pool capacity surface (docs/SERVING.md "Quantized KV
            # cache"): the bytes the cache actually pins — scale
            # metadata included — and the payload dtype, so a capacity
            # dashboard can see the quantized working set. At a fixed
            # HBM budget slots × context ≤ pool bytes, so kv_pool_bytes
            # IS the serving-capacity denominator.
            "kv_dtype": str(self._kvpools[0].dtype),
            "kv_quant": self.kv_quant or "off",
            # a page of one pool, (H, page_size, 2 * D): keys | values
            # on the lanes, the layout the kernels read
            "kv_page_shape": tuple(self._kvpools[0].shape[1:]),
            "kv_pool_bytes": int(
                sum(p.nbytes for p in self._kvpools) +
                sum(a.nbytes for a in self._kamax) +
                sum(a.nbytes for a in self._vamax)),
            # the state cache (docs/SERVING.md "State cache"): how many
            # layers keep rows a slot, one slot's rows of one layer
            # ({name: (shape, dtype)}), and the bytes all of it pins
            "state_layers": len(self._states),
            "state_row_shapes": {
                name: (tuple(shape), str(jnp.dtype(dtype)))
                for name, (shape, dtype) in
                (self._state_spec[0] if self._state_spec else {}).items()},
            "state_cache_bytes": int(sum(
                a.nbytes for rows in self._states for a in rows.values())),
            "kv_quantized_pages": (
                self.num_pages - 1 - self._alloc.free_count
                if self._kv_spec is not None else 0),
            "decode_steps": self.decode_steps,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "accept_rate": self.accept_rate,
            "prefix_hits": self.prefix_hits,
            "prefix_lookups": self.prefix_lookups,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            # hierarchical cache tiers (docs/SERVING.md "Hierarchical
            # prefix cache"): per-tier resident bytes plus the
            # demotion/promotion/fallback counters — all zeros when
            # tiers are off, so scrapers need no feature probe
            "kv_tier_bytes": (self._tiers.tier_bytes()
                              if self._tiers is not None
                              else {"dram": 0, "disk": 0}),
            "tier_demotions": self.tier_demotions,
            "tier_disk_demotions": (self._tiers.disk_demotions
                                    if self._tiers is not None else 0),
            "tier_promotions": self.tier_promotions,
            "tier_hits": self.tier_hits,
            "tier_hit_tokens": self.tier_hit_tokens,
            "tier_misses": self.tier_misses,
            "tier_crc_fallbacks": self.tier_crc_fallbacks,
            "tier_disk_errors": (self._tiers.disk_errors
                                 if self._tiers is not None else 0),
            "tier_dropped": (self._tiers.dropped
                             if self._tiers is not None else 0),
            # page transport (serve/transport.py): capsule traffic
            # through this engine, plus live in-custody state — pages
            # a detached slot parked here while its transfer is in
            # flight (gauge, normally 0 between router steps)
            "migrated_out_pages": self.migrated_out_pages,
            "migrated_in_pages": self.migrated_in_pages,
            "migrated_out_bytes": self.migrated_out_bytes,
            "migrated_in_bytes": self.migrated_in_bytes,
            "capsule_pages": sum(len(p) for p in
                                 self._capsule_pages.values()),
            "stop_hits": self.stop_hits,
            "constrained_requests": self.constrained_requests,
            "preemptions": self.preemptions,
            "brownout_level": self.brownout_level,
            "brownout_escalations": bo.escalations if bo else 0,
            "brownout_deescalations": bo.deescalations if bo else 0,
            # tier-labeled TTFT/TPOT/queue-delay/e2e histograms,
            # ingested from the SAME event stream as every counter
            # above (serve/events.py) — rendered by serve/metrics.py;
            # None when the recorder is disabled
            "latency_hists": self.flight.hist_snapshot(),
        }

    def prefix_probe(self, prompt_ids) -> int:
        """READ-ONLY cache-affinity query: how many leading tokens of
        ``prompt_ids`` this engine's prefix index has cached right now.
        No refcounts move, no LRU clock ticks, nothing compiles — a
        router may probe every replica per admission for free. 0 when
        the prefix cache is off (an affinity-blind replica)."""
        if self._prefix is None:
            return 0
        return int(self._prefix.probe(prompt_ids))

    def tier_probe(self, prompt_ids) -> int:
        """READ-ONLY twin of ``prefix_probe`` for the cache tiers: how
        many leading tokens the engine could serve counting HBM PLUS
        the pages its lower tiers would re-admit by copy. Side-effect
        free like ``prefix_probe`` (no LRU ticks in any tier) — the
        router's SECOND affinity axis. Equals ``prefix_probe`` when
        tiers are off."""
        if self._prefix is None:
            return 0
        shared, _, cached_len = self._prefix.match(prompt_ids,
                                                   mutate=False)
        if self._tiers is None:
            return int(cached_len)
        n = self._tiers.probe(prompt_ids, len(shared))
        if n == 0:
            return int(cached_len)
        return (len(shared) + n) * self.page_size

    def can_serve(self, total_positions: int) -> bool:
        """Could a request spanning ``total_positions`` (prompt +
        max_new_tokens) EVER be served by this engine? The single
        definition of the servability bound — ``submit``'s fail-fast,
        the fleet router's fleet-wide admission check, and its
        per-replica routing filter all call this, so the bound can
        never drift between the engine and the router."""
        need = -(-total_positions // self.page_size)
        return total_positions <= self.max_len and \
            need <= self.num_pages - 1

    def withdraw(self, request: Request) -> bool:
        """Remove a still-QUEUED request from the admission queue
        without recording a terminal (the caller owns the outcome) —
        the fleet router's starved-attempt give-up. Returns False when
        the request is not in the queue (already admitted or
        terminal). Queued requests hold no pages, so nothing else
        needs releasing. Removal is by IDENTITY: Request's generated
        __eq__ compares ndarray fields, so deque.remove would raise
        mid-scan on a same-shape neighbour instead of finding the
        target."""
        for i, q in enumerate(self._queue):
            if q is request:
                del self._queue[i]
                return True
        return False

    def _shed_one_below(self, tier: Tier) -> bool:
        """Overload drains the LOWEST tier first: shed the most
        recently queued request of the lowest-priority tier strictly
        below ``tier`` (it waited least — FIFO fairness within its
        tier is preserved for the rest). Returns True when a queued
        request was shed to make room."""
        victim = None
        for q in self._queue:
            if q.tier.order <= tier.order:
                continue
            if victim is None or q.tier.order >= victim.tier.order:
                victim = q               # rightmost of the worst tier
        if victim is None:
            return False
        self.withdraw(victim)
        self._record_terminal(
            victim, Outcome.SHED,
            f"displaced from the admission queue by a {tier.value} "
            f"submission under overload")
        return True

    def cancel(self, request: Union[Request, int],
               detail: str = "cancelled by client") -> bool:
        """Client cancellation — a first-class transition from ANY
        live state to the CANCELLED terminal: a QUEUED request leaves
        the queue, a slotted one (prefilling, mid-decode, or
        mid-spec-verify — all host-visible as a live slot between
        steps) is evicted with its pages reclaimed; partial tokens are
        kept either way. Accepts the ``Request`` itself or its
        ``request_id``. Returns False — the refusal the double-finish
        guard implies — when the request is already terminal (or not
        known to this engine): exactly one terminal, ever, even when
        a cancel races a completion."""
        if isinstance(request, Request) and request.outcome is not None:
            return False                     # already terminal: refuse
        for i, q in enumerate(self._queue):
            if q is request or q.request_id == request:
                del self._queue[i]
                self._record_terminal(q, Outcome.CANCELLED, detail)
                return True
        for s in range(self.num_slots):
            slot = self._slots[s]
            if slot is not None and (slot.request is request or
                                     slot.request.request_id == request):
                self._evict(s, Outcome.CANCELLED, detail)
                return True
        return False

    def submit(self, request: Request) -> bool:
        """Admission-queue entry with load shedding. Returns True when
        the request was queued; False when it was refused — already
        terminal with SHED (queue bounds exceeded, ``retry_after_s``
        set) or FAILED_UNSERVABLE (it could NEVER be served: more
        positions than ``max_len`` or more worst-case pages than the
        whole pool — failing fast beats wedging the queue head).

        Tier scoping (serve/slo.py): the request's ``TierPolicy`` may
        supply a default deadline, a per-tier queue depth bound, and a
        per-tier estimated-delay limit (each falling back to the
        engine-global knob). When the GLOBAL queue bound is hit by a
        higher-tier submission, shedding drains the lowest queued tier
        first (``_shed_one_below``) — BATCH absorbs overload before
        STANDARD before LATENCY."""
        request.submit_time = time.perf_counter()
        self.flight.emit(self._component, EventType.SUBMIT,
                         request_id=request.request_id,
                         tier=request.tier.value,
                         queue_depth=len(self._queue))
        pol = self._tier_policy(request.tier)
        if request.deadline_s is None and \
                pol.default_deadline_s is not None:
            request.deadline_s = float(pol.default_deadline_s)
        if request.deadline_s is not None:
            request._deadline_abs = request.submit_time + request.deadline_s
        total = int(request.prompt_ids.size) + request.max_new_tokens
        need = -(-total // self.page_size)
        if not self.can_serve(total):
            self._record_terminal(
                request, Outcome.FAILED_UNSERVABLE,
                f"request needs {total} positions / {need} pages but the "
                f"engine caps at max_len {self.max_len} / "
                f"{self.num_pages - 1} usable pages")
            return False
        if request.sampling is not None:
            # fail-fast like the size bound: a grammar over the wrong
            # vocab (or a bias on a token the model has no logit for)
            # could NEVER be served — it must not wedge the queue head
            err = request.sampling.validate_for(self.model.vocab_size,
                                                request.eos_id)
            if err is not None:
                self._record_terminal(request,
                                      Outcome.FAILED_UNSERVABLE, err)
                return False
        est = self._estimated_queue_delay(request.tier)
        # the newcomer's OWN refusals come first: a request its tier
        # bound or delay limit is about to refuse anyway must not
        # displace an innocent lower-tier victim on the way out
        if pol.max_queue is not None and \
                sum(1 for q in self._queue
                    if q.tier is request.tier) >= pol.max_queue:
            self._record_terminal(
                request, Outcome.SHED,
                f"{request.tier.value} queue at its tier depth limit "
                f"{pol.max_queue}",
                retry_after=est if est else 0.05)
            return False
        delay_limit = pol.max_queue_delay_s \
            if pol.max_queue_delay_s is not None else self.max_queue_delay_s
        if delay_limit is not None and est is not None \
                and est > delay_limit:
            self._record_terminal(
                request, Outcome.SHED,
                f"estimated queue delay {est:.3f}s exceeds "
                f"{delay_limit}s for tier {request.tier.value}",
                retry_after=est)
            return False
        if self.max_queue is not None and \
                len(self._queue) >= self.max_queue and \
                not self._shed_one_below(request.tier):
            self._record_terminal(
                request, Outcome.SHED,
                f"admission queue at depth limit {self.max_queue}",
                retry_after=est if est else 0.05)
            return False
        self._queue.append(request)
        return True

    def _next_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    @property
    def accept_rate(self) -> float:
        """Fraction of drafted tokens the verify step accepted (0.0
        when the engine never drafted)."""
        return self.accepted_tokens / self.drafted_tokens \
            if self.drafted_tokens else 0.0

    def _finish_token(self, slot_idx: int, token: int,
                      dt: float) -> Optional[Outcome]:
        """Record one generated token; returns the success outcome when
        the request's own stopping condition hit (EOS / max_new_tokens),
        else None."""
        slot = self._slots[slot_idx]
        req = slot.request
        tok = int(token)
        req.token_ids.append(tok)
        req.token_times.append(dt)
        req.token_stamps.append(time.perf_counter())
        self._tok_counts[slot_idx, tok] += 1     # penalty history
        if req.eos_id >= 0 and tok == req.eos_id:
            return Outcome.EOS
        sp = req.sampling
        if sp is not None:
            if sp.grammar is not None:
                nxt = sp.grammar.advance(slot.grammar_state, tok)
                if nxt is not None:
                    slot.grammar_state = nxt
            if sp.stop_sequences:
                slot.stop_tail.append(tok)
                if len(slot.stop_tail) > sp.max_stop_len:
                    del slot.stop_tail[:-sp.max_stop_len]
                hit = match_stop(slot.stop_tail, sp.stop_sequences)
                if hit:
                    # the matched sequence is NOT part of the output:
                    # truncate what this attempt recorded; anything the
                    # match reaches back into an EARLIER attempt's
                    # stream is reported via _stop_trim for the router
                    # to trim off the client (docs/SERVING.md)
                    trim = min(hit, len(req.token_ids))
                    if trim:
                        del req.token_ids[-trim:]
                        del req.token_times[-trim:]
                        del req.token_stamps[-trim:]
                    req._stop_trim = hit - trim
                    self.stop_hits += 1
                    return Outcome.STOP
        if len(req.token_ids) >= req.max_new_tokens:
            return Outcome.MAX_TOKENS
        return None

    def _evict(self, slot_idx: int, outcome: Outcome, detail: str = ""):
        slot = self._slots[slot_idx]
        self._free_slot_state(slot_idx)
        if outcome.ok:
            self._observe_service(slot.t_admit)
        self._record_terminal(slot.request, outcome, detail)

    def _quarantine(self, slot_idx: int, detail: str):
        """Fail a poisoned slot (non-finite logits): evict it — pages
        reclaimed, its output never published — and flush the prefix
        index, since a corrupt SHARED page would otherwise keep
        poisoning every future cache hit (the index cannot tell which
        cached page went bad; dropping retention is cheap and safe —
        live slots keep their own page references)."""
        self._evict(slot_idx, Outcome.FAILED_NONFINITE, detail)
        if self._prefix is not None and len(self._prefix):
            self._prefix.flush(self._alloc)
            self.prefix_flushes += 1
        if self._tiers is not None and len(self._tiers):
            # demoted payloads were captured from the same poisoned
            # cache lineage — quarantine drops them with the index
            self._tiers.flush()

    def _expire_queue(self):
        """Host-side deadline enforcement for QUEUED requests: a
        request whose deadline passes before admission is dropped
        terminally (mid-queue expiry) instead of being admitted into
        work it can no longer use."""
        if not any(r._deadline_abs is not None for r in self._queue):
            return
        now = time.perf_counter()
        keep = deque()
        for req in self._queue:
            if req._deadline_abs is not None and now > req._deadline_abs:
                self._record_terminal(
                    req, Outcome.DEADLINE_EXPIRED,
                    f"deadline ({req.deadline_s}s) passed while queued")
            else:
                keep.append(req)
        self._queue = keep

    def _expire_slots(self):
        """Host-side deadline enforcement for DECODING slots: evict
        (pages reclaimed) any slot past its request deadline or the
        engine's per-slot wall cap — before spending another decode
        step on it. Partial tokens are kept."""
        now = time.perf_counter()
        for s in range(self.num_slots):
            slot = self._slots[s]
            if slot is None:
                continue
            dl = slot.request._deadline_abs
            if dl is not None and now > dl:
                phase = "prefill" if slot.prefilling else "decode"
                self._evict(s, Outcome.DEADLINE_EXPIRED,
                            f"deadline ({slot.request.deadline_s}s) "
                            f"passed mid-{phase}")
                continue
            if self.max_slot_wall_s is not None and \
                    now - slot.t_admit > self.max_slot_wall_s:
                self._evict(s, Outcome.DEADLINE_EXPIRED,
                            f"per-slot wall cap {self.max_slot_wall_s}s "
                            f"exceeded")

    def _attempt_ids(self, req: Request) -> np.ndarray:
        """The sequence a (re)admission actually prefills: the
        original prompt plus every token already emitted — the
        resume-from-suffix replay (PR 7's router pattern, here used by
        slot preemption). Fresh requests return the prompt itself."""
        if not req.token_ids:
            return req.prompt_ids
        return np.concatenate([req.prompt_ids,
                               np.asarray(req.token_ids, np.int32)])

    def _queue_head(self, clamped_ok: bool = True) -> Optional[Request]:
        """The queue's PRIORITY head: the earliest-submitted request of
        the highest-priority tier present (FIFO within a tier —
        deque order is submit order). ``clamped_ok=False`` skips tiers
        the brownout controller has clamped (level 3: BATCH admissions
        held at zero — they stay queued, they do not block others)."""
        best = None
        for q in self._queue:
            if not clamped_ok and self.brownout_level >= 3 and \
                    q.tier is Tier.BATCH:
                continue
            if best is None or q.tier.order < best.tier.order:
                best = q
        return best

    def _preempt_candidate(self, tier: Tier) -> Optional[int]:
        """The slot a ``tier`` admission may reclaim: a live slot of a
        PREEMPTIBLE, strictly lower-priority tier — the lowest tier
        first, the fewest emitted tokens within it (cheapest replay),
        smallest index as the deterministic tie-break. None when
        ``tier`` cannot preempt or no victim qualifies."""
        if not self._tier_policy(tier).can_preempt:
            return None
        best, best_key = None, None
        for s, slot in enumerate(self._slots):
            if slot is None:
                continue
            vt = slot.request.tier
            if vt.order <= tier.order or \
                    not self._tier_policy(vt).preemptible:
                continue
            key = (-vt.order, len(slot.request.token_ids), s)
            if best_key is None or key < best_key:
                best, best_key = s, key
        return best

    def _free_slot_state(self, slot_idx: int):
        """Release a slot's pages and scrub its device-facing arrays —
        shared by eviction (terminal) and preemption (re-queue)."""
        slot = self._slots[slot_idx]
        self._alloc.free(slot.refs)          # refcounted: shared pages
        self._scrub_slot_arrays(slot_idx)

    def _scrub_slot_arrays(self, slot_idx: int):
        """Scrub a slot's device-facing arrays WITHOUT touching its
        page references — the shared tail of ``_free_slot_state``
        (pages freed) and ``detach_slot`` (pages move to in-capsule
        custody instead)."""
        self._page_table[slot_idx, :] = NULL_PAGE  # survive via sharers
        self._lengths[slot_idx] = 0
        self._temps[slot_idx] = 0.0
        self._slot_keys[slot_idx] = 0
        # sampling-menu state back to exact-identity neutrals
        self._top_k[slot_idx] = 0
        self._top_p[slot_idx] = 1.0
        self._rep_pen[slot_idx] = 1.0
        self._pres_pen[slot_idx] = 0.0
        self._logit_bias[slot_idx, :] = 0.0
        self._tok_counts[slot_idx, :] = 0
        self._slots[slot_idx] = None

    def _preempt(self, slot_idx: int, detail: str = ""):
        """Reclaim a slot for a higher-tier admission: pages released,
        partial tokens KEPT, and — within ``max_preemptions`` — the
        request re-queued through normal admission (original
        ``submit_time`` / ``_deadline_abs`` untouched: deadlines stay
        anchored to the original admission). The resume replays
        prompt + emitted as the next attempt's prompt under the SAME
        pinned sampling key, so the continuation is bit-identical to
        an unpreempted run. Past the budget the request terminates
        PREEMPTED — bounded, retryable, hinted."""
        slot = self._slots[slot_idx]
        req = slot.request
        if self.preempt_handoff is not None and not slot.prefilling:
            # fleet-aware preemption: offer the victim to a sibling
            # FIRST — a successful handoff MOVES the slot's pages
            # (zero redone prefill, no queue bounce); the fallback
            # below keeps the engine-internal requeue semantics when
            # nobody can take it. The handoff may also end with the
            # router re-queueing the request itself (replay fallback
            # after a failed transfer) — the slot is gone from this
            # engine either way, so the re-check guards the eviction,
            # not the return value alone.
            try:
                handed = bool(self.preempt_handoff(req.request_id))
            except Exception:
                handed = False
            if handed or self._slots[slot_idx] is not slot:
                self.preemptions += 1
                self.flight.emit(self._component, EventType.PREEMPT,
                                 request_id=req.request_id,
                                 tier=req.tier.value, slot=slot_idx,
                                 preemptions=req.preemptions,
                                 handoff=True, detail=detail)
                return
        req.preemptions += 1
        self.preemptions += 1
        self._free_slot_state(slot_idx)
        self.flight.emit(self._component, EventType.PREEMPT,
                         request_id=req.request_id,
                         tier=req.tier.value, slot=slot_idx,
                         preemptions=req.preemptions, detail=detail)
        if req.preemptions > self.max_preemptions:
            self._record_terminal(
                req, Outcome.PREEMPTED,
                f"preempted {req.preemptions} times "
                f"(max_preemptions={self.max_preemptions}): {detail}")
        else:
            self.flight.emit(self._component, EventType.REQUEUE,
                             request_id=req.request_id,
                             cause="preemption",
                             preemptions=req.preemptions)
            self._queue.append(req)

    def _admit(self):
        """Priority admission into free slots, gated on worst-case
        pages: the highest-priority queued request first (FIFO within
        a tier), with slot PREEMPTION — when no slot (or not enough
        pages) is free for a tier that ``can_preempt``, a preemptible
        lower-tier slot is reclaimed (``_preempt``: partial tokens
        kept, bounded re-queue). The blocked priority head blocks the
        tiers at and below it (no priority inversion: BATCH never
        slips past a page-starved LATENCY head).

        With the prefix cache on, admission first matches the attempt
        prompt's longest cached page-aligned prefix: matched full
        pages are mapped copy-on-write (incref'd, read-only), the
        boundary partial page is copied, and only the remaining suffix
        pays prefill compute — a preempted request's resume typically
        re-lands on its own published prompt pages. Pages held only by
        the index count as reclaimable budget — they are evicted (LRU)
        when the free list alone cannot cover a request."""
        while self._queue:
            req = self._queue_head(clamped_ok=False)
            if req is None:
                return
            slot_idx = next((i for i in range(self.num_slots)
                             if self._slots[i] is None), None)
            if slot_idx is None:
                slot_idx = self._preempt_candidate(req.tier)
                if slot_idx is None:
                    return
                self._preempt(slot_idx,
                              f"slot reclaimed for a {req.tier.value} "
                              f"admission")
            if not self._try_admit(slot_idx, req):
                return

    def _try_admit(self, slot_idx: int, req: Request) -> bool:
        """Admit ``req`` into the free ``slot_idx`` if its worst-case
        pages fit (preempting lower-tier slots for pages when the
        request's tier may); returns False — request left queued,
        nothing pinned — when the pool cannot cover it yet."""
        ids = self._attempt_ids(req)
        t0 = int(ids.size)
        # submit() fail-fasts requests that can never fit, so here
        # ``need`` is always <= the usable pool (resume attempts span
        # the same total positions: prompt + max_new_tokens)
        total = t0 + (req.max_new_tokens - len(req.token_ids))
        need = -(-total // self.page_size)
        prompt_pages = -(-t0 // self.page_size)

        shared: List[int] = []
        partial = None
        cached_len = 0
        if self._prefix is not None:
            self.prefix_lookups += 1
            shared, partial, cached_len = self._prefix.match(ids)
            # pin matches NOW so reclaim below can't free them
            for p in shared:
                self._alloc.incref(p)
            if partial is not None:
                self._alloc.incref(partial[0])

        tier_chain = []
        if self._tiers is not None:
            # continue the radix walk through the lower tiers from the
            # page where HBM stopped. A chain supersedes a boundary
            # partial hit: the tiers hold the FULL page the partial is
            # a prefix of, and promotion is cheaper than COW + suffix
            # recompute of the same tokens.
            tier_chain = self._tiers.match_chain(ids, len(shared))
            if tier_chain:
                if partial is not None:
                    self._alloc.decref(partial[0])
                    partial = None
                    cached_len = len(shared) * self.page_size
                # pin the chain: THIS admission's reclaim demotes pages
                # into the same store and must not spill or drop what
                # it is about to promote
                self._tiers.pin(tier_chain)

        def _budget():
            n_new = need - len(shared)   # pages the free list owes
            avail = self._alloc.free_count - self._lazy_debt
            recl = self._prefix.reclaimable(self._alloc) \
                if self._prefix is not None else 0
            return n_new, avail, recl

        n_new, avail, recl = _budget()
        if avail + recl < n_new:
            # not enough pages even reclaiming cache retention: a tier
            # that can preempt reclaims lower-tier slots' pages — but
            # only when the OPTIMISTIC bound (every preemptible
            # victim's refs freed in full) can actually cover the
            # deficit. Bouncing every BATCH slot (each bounce burning
            # its preemption budget and redoing its prefill) only to
            # fail the admission anyway would be pure loss.
            victim_pages = sum(
                len(s.refs) for s in self._slots
                if s is not None
                and s.request.tier.order > req.tier.order
                and self._tier_policy(s.request.tier).preemptible)
            if self._tier_policy(req.tier).can_preempt and \
                    avail + recl + victim_pages >= n_new:
                while avail + recl < n_new:
                    victim = self._preempt_candidate(req.tier)
                    if victim is None:
                        break
                    self._preempt(victim, f"pages reclaimed for a "
                                          f"{req.tier.value} admission")
                    n_new, avail, recl = _budget()
        if avail + recl < n_new:
            # no cache budget yet — unpin and wait for evictions
            for p in shared:
                self._alloc.decref(p)
            if partial is not None:
                self._alloc.decref(partial[0])
            if tier_chain:
                self._tiers.unpin(tier_chain)
            return False
        if avail < n_new:
            self.prefix_reclaimed_pages += \
                self._reclaim_prefix(n_new - avail)
        if cached_len:
            self.prefix_hits += 1
            self.prefix_hit_tokens += cached_len

        self.withdraw(req)
        priv = [self._alloc.alloc()
                for _ in range(prompt_pages - len(shared))]
        self._reset_page_amax(priv)          # fresh pages, fresh scales
        row = np.zeros((self.max_pages,), np.int32)
        row[:len(shared)] = shared
        row[len(shared):prompt_pages] = priv

        promoted = 0
        if tier_chain:
            # re-admit the chain BY COPY into the freshly allocated
            # pages: host-side data movement through the one jitted
            # promotion program — never a prefill recompute. A failed
            # integrity check truncates the chain there and falls back
            # to recomputing the rest, loudly.
            for key, ent in tier_chain:
                dst = int(priv[promoted])
                src_tier = ent.tier
                payload = self._tiers.load(key, ent)
                if payload is None:
                    self.tier_crc_fallbacks += 1
                    self.flight.emit(
                        self._component, EventType.CACHE_TIER_MISS,
                        request_id=req.request_id, reason="integrity",
                        tier=src_tier, depth=ent.depth)
                    break
                self._promote_page(*payload, dst)
                self._tiers.remove(key, ent)
                promoted += 1
                self.tier_promotions += 1
                self.flight.emit(
                    self._component, EventType.CACHE_PROMOTE,
                    request_id=req.request_id, tier=src_tier,
                    depth=ent.depth, page=dst)
            self._tiers.unpin(tier_chain)
            if promoted:
                cached_len = (len(shared) + promoted) * self.page_size
                self.tier_hits += 1
                self.tier_hit_tokens += promoted * self.page_size
                # promoted pages are published back into the HBM index
                # IMMEDIATELY (refcount slot + index, exactly as if
                # never evicted) so sibling requests share them without
                # waiting for this slot's prefill to finish
                self._prefix.insert(ids[:cached_len], row, self._alloc)
        elif self._tiers is not None \
                and (t0 - 1) // self.page_size > len(shared):
            # tiers consulted, nothing usable, and at least one full
            # page of this prompt was demotable — a true tier miss
            self.tier_misses += 1
            self.flight.emit(self._component, EventType.CACHE_TIER_MISS,
                             request_id=req.request_id, reason="absent")
        # per-request RNG key: pinned by Request.seed (reproducible
        # across engines/occupancy), engine-split otherwise — and
        # REMEMBERED on the request, so a preemption resume keeps the
        # same stream and the continuation stays bit-identical
        if req.seed is not None:
            # mxlint: allow-host-sync(once per request at admission, not per decode step)
            skey = np.asarray(jax.random.PRNGKey(int(req.seed)),
                              np.uint32)
        elif req._assigned_key is not None:
            skey = req._assigned_key
        else:
            # mxlint: allow-host-sync(once per request at admission, not per decode step)
            skey = np.asarray(self._next_key(), np.uint32)
            req._assigned_key = skey
        slot = _Slot(req, reserved_pages=need,
                     refs=list(shared) + priv, row=row, t0=t0,
                     attempt_ids=ids, prefill_pos=cached_len,
                     t_admit=time.perf_counter(), key=skey)
        self._slots[slot_idx] = slot
        self._slot_keys[slot_idx] = skey
        # decode-invisible until prefill completes: the decode step
        # must neither attend a half-built prompt nor scatter its
        # (dead-slot) write into a mapped — possibly SHARED — page
        self._page_table[slot_idx, :] = NULL_PAGE
        self._lengths[slot_idx] = 0
        self._temps[slot_idx] = 0.0
        self._restore_stream_state(slot_idx, slot)
        # a sequence's state starts from nothing, at every admission: a
        # preempted request re-prefills from its first position
        state_zeroed = self._zero_state(slot_idx)
        if partial is not None:
            # COW: the boundary page becomes a private copy; drop
            # the temporary pin on the cached source
            self._copy_page(partial[0], int(row[len(shared)]))
            self._alloc.decref(partial[0])
        self.flight.emit(
            self._component, EventType.ADMIT,
            request_id=req.request_id, tier=req.tier.value,
            slot=slot_idx, t0=t0, cached_len=cached_len,
            state_zeroed=state_zeroed,
            queue_delay_s=(slot.t_admit - req.submit_time
                           if req.submit_time is not None else None))

        if self.chunk_pages is None:
            # monolithic mode: prefill to completion inside _admit.
            # A cache hit still runs the (chunk-program) suffix path
            # — the dense program cannot start mid-prompt.
            if cached_len == 0:
                self._dense_prefill(slot_idx)
            else:
                while (self._slots[slot_idx] is slot and
                       slot.prefilling):
                    self._run_chunk(slot_idx)
        # chunked mode: the slot prefills across subsequent step()
        # calls under the token budget
        return True

    def _restore_stream_state(self, slot_idx: int, slot: "_Slot"):
        """Re-derive a slot's resumable-as-data stream state from its
        attempt ids — sampling-menu slot state (serve/sampling.py):
        knob vectors, bias row, and the token-count table (full attempt
        history — prompt + carried tokens) the penalties read. Grammar
        state and the stop-sequence window are re-derived from the
        GENERATED part only (``prompt_len`` marks the resume split), so
        a preemption/failover resume — and a migration install, which
        goes through exactly this path on the destination — samples as
        the unbroken run would: bit-identical continuations under every
        knob (tests/test_sampling.py, tests/test_transport.py)."""
        req = slot.request
        ids = slot.attempt_ids
        self._tok_counts[slot_idx] = np.bincount(
            ids, minlength=self._vocab)[:self._vocab]
        sp = req.sampling
        slot.menu_active = sp is not None and not sp.logits_neutral
        if sp is not None:
            self._top_k[slot_idx] = sp.top_k
            self._top_p[slot_idx] = sp.top_p
            self._rep_pen[slot_idx] = sp.repetition_penalty
            self._pres_pen[slot_idx] = sp.presence_penalty
            if sp.logit_bias:
                for t, b in sp.logit_bias.items():
                    self._logit_bias[slot_idx, t] = b
            base = req.prompt_len if req.prompt_len is not None \
                else int(req.prompt_ids.size)
            gen = [int(t) for t in ids[base:]]
            if sp.grammar is not None:
                self.constrained_requests += 1
                st = sp.grammar.start()
                for t in gen:
                    nxt = sp.grammar.advance(st, t)
                    if nxt is None:
                        break        # off-grammar history: hold state
                    st = nxt
                slot.grammar_state = st
            if sp.stop_sequences and sp.max_stop_len > 1:
                slot.stop_tail = gen[-(sp.max_stop_len - 1):]

    # ------------------------------------------------------------- #
    # page-transport hooks (serve/transport.py owns the capsule)
    # ------------------------------------------------------------- #

    def kv_wire_sig(self) -> tuple:
        """The pool layout a page payload is only meaningful under:
        quant mode, page size, layer count, per-page shape, and code
        dtype. A capsule captured under one signature must never be
        installed under another — the transport refuses the transfer
        and the replay fallback recomputes instead."""
        return (self.kv_quant or "off", self.page_size,
                len(self._kvpools), (self._H, self.page_size, self._D),
                str(self._kvpools[0].dtype))

    def _refuse_capsule(self, what: str):
        """A capsule carries pages; a model with state layers has rows a
        sequence beside them that the wire format does not carry, and a
        slot resumed without them would serve wrong tokens silently."""
        if self._states:
            raise MXNetError(
                f"page transport ({what}): the model has state layers "
                "(recurrent rows a sequence) and a capsule carries "
                "pages only; migrate by replay, not by transfer")

    def decode_ready(self, request_id: int) -> bool:
        """True when ``request_id`` holds a slot past prefill — the
        only state a slot is page-capturable from (a prefilling slot's
        pages are half-built; migrating it is a replay, not a
        transfer). The router's role-split streaming poll."""
        for slot in self._slots:
            if slot is not None and \
                    slot.request.request_id == request_id:
                return not slot.prefilling
        return False

    def capture_slot(self, request_id: int) -> Optional[dict]:
        """READ-ONLY capture probe for the page transport: the decode-
        ready slot's populated page row (positions ``[0, n_pos)`` —
        the one position beyond it is recomputed on the destination,
        its logits must seed the next sample there), its pinned RNG
        key, and the attempt request. Nothing moves: refcounts, the
        slot, and the pools are untouched, so an aborted capture
        (source death mid-transfer) leaves the slot exactly as it was.
        None when the request holds no slot here or is still
        prefilling."""
        self._refuse_capsule("capture")
        for i, slot in enumerate(self._slots):
            if slot is not None and \
                    slot.request.request_id == request_id:
                if slot.prefilling:
                    return None
                n_pos = int(self._lengths[i])
                if n_pos <= 0:
                    return None
                n_pages = -(-n_pos // self.page_size)
                return {
                    "request": slot.request,
                    "key": np.array(slot.key, np.uint32),
                    "pages": [int(p) for p in
                              self._page_table[i, :n_pages]],
                    "n_pos": n_pos,
                }
        return None

    def detach_slot(self, request_id: int) -> Optional[Request]:
        """Move a captured slot's page references into in-capsule
        custody (``_capsule_pages``) and release the slot — WITHOUT a
        terminal (the transport owns the outcome: install on the
        destination, or the replay fallback). The pages stay
        refcounted by the custody entry, so ``audit_pages`` balances
        at every step of an in-flight transfer; ``release_capsule``
        returns them to the pool once the transfer lands or falls
        back. Returns the detached attempt request, or None when the
        request holds no decode-ready slot here."""
        for i, slot in enumerate(self._slots):
            if slot is not None and \
                    slot.request.request_id == request_id:
                if slot.prefilling:
                    return None
                self._capsule_pages[int(request_id)] = list(slot.refs)
                self._scrub_slot_arrays(i)
                return slot.request
        return None

    def release_capsule(self, request_id: int) -> int:
        """Drop an in-flight capsule's page custody — the source-side
        end of every transfer, success or fallback. Returns the number
        of page references released."""
        pages = self._capsule_pages.pop(int(request_id), None)
        if pages is None:
            return 0
        self._alloc.free(pages)
        return len(pages)

    def install_slot(self, request: Request, payloads, n_pos: int,
                     key, wire_bytes: int = 0, page_hook=None,
                     abort=None) -> bool:
        """Install a transported slot: allocate private pages, write
        every capsule payload through the ONE jitted promotion program
        (the tier re-admission program — nothing new compiles), pin
        the capsule's RNG key, and re-derive the stream state exactly
        as a preemption resume would. The slot resumes with
        ``prefill_pos = n_pos``: the only recomputed position is the
        boundary token the wire cannot carry (its logits seed the next
        sample), so redone prefill is zero.

        Refuses — False, engine untouched — when no slot or not
        enough pages are free, the request is already terminal, or the
        capsule does not line up with the resume attempt
        (``n_pos != len(attempt) - 1``). A mid-install abort (chaos:
        destination death) frees the allocated pages and refuses —
        ``audit_pages`` stays clean on the destination too."""
        self._refuse_capsule("install")
        if request.outcome is not None:
            return False
        slot_idx = next((i for i in range(self.num_slots)
                         if self._slots[i] is None), None)
        if slot_idx is None:
            return False
        ids = self._attempt_ids(request)
        t0 = int(ids.size)
        if n_pos != t0 - 1 or n_pos <= 0:
            return False                 # capsule/attempt mismatch
        n_install = -(-n_pos // self.page_size)
        if n_install != len(payloads):
            return False
        total = t0 + (request.max_new_tokens - len(request.token_ids))
        need = -(-total // self.page_size)
        prompt_pages = -(-t0 // self.page_size)
        avail = self._alloc.free_count - self._lazy_debt
        recl = self._prefix.reclaimable(self._alloc) \
            if self._prefix is not None else 0
        if avail + recl < need:
            return False
        if avail < prompt_pages:
            self.prefix_reclaimed_pages += \
                self._reclaim_prefix(prompt_pages - avail)
        priv = [self._alloc.alloc() for _ in range(prompt_pages)]
        self._reset_page_amax(priv)      # fresh pages, fresh scales
        aborted = False
        for j, payload in enumerate(payloads):
            if page_hook is not None:
                page_hook(j, len(payloads))
            if abort is not None and abort():
                aborted = True
                break
            self._promote_page(*payload, int(priv[j]))
        if aborted:
            # pages are identity-free: a half-written payload needs no
            # scrub, only its references back on the free list
            self._alloc.free(priv)
            return False
        row = np.zeros((self.max_pages,), np.int32)
        row[:prompt_pages] = priv
        skey = np.asarray(key, np.uint32)
        # the capsule's pinned key IS the live stream's key: remember
        # it on the request so a later preemption resume on THIS
        # replica keeps the same stream (the cross-replica seed gap —
        # an engine-drawn key must travel, never be re-drawn)
        request._assigned_key = skey
        if request.submit_time is None:
            request.submit_time = time.perf_counter()
        if request._deadline_abs is None and \
                request.deadline_s is not None:
            request._deadline_abs = \
                request.submit_time + request.deadline_s
        slot = _Slot(request, reserved_pages=need, refs=priv, row=row,
                     t0=t0, attempt_ids=ids, prefill_pos=n_pos,
                     t_admit=time.perf_counter(), key=skey)
        self._slots[slot_idx] = slot
        self._slot_keys[slot_idx] = skey
        # decode-invisible until the boundary token lands — exactly
        # the cache-hit suffix admission contract
        self._page_table[slot_idx, :] = NULL_PAGE
        self._lengths[slot_idx] = 0
        self._temps[slot_idx] = 0.0
        self._restore_stream_state(slot_idx, slot)
        self.migrated_in_pages += len(payloads)
        self.migrated_in_bytes += int(wire_bytes)
        self.flight.emit(
            self._component, EventType.ADMIT,
            request_id=request.request_id, tier=request.tier.value,
            slot=slot_idx, t0=t0, cached_len=n_pos, migrated=True,
            queue_delay_s=None)
        # recompute ONLY the boundary position, through the same chunk
        # program family a cache-hit suffix uses (bucket 1 — already
        # compiled on any engine that admitted a cache hit)
        while self._slots[slot_idx] is slot and slot.prefilling:
            self._run_chunk(slot_idx)
        return True

    def _slot_sampling_args(self, slot_idx: int) -> tuple:
        """The per-request sampling-row operands a prefill/chunk
        program takes: knob scalars, the count/bias rows, and the
        grammar mask for the FIRST generated token — all traced data
        (same bucket, same compile; trace counts asserted). A slot
        with neutral (or no) params reuses one cached device-resident
        row set — a long chunked prompt re-ships zero sampling bytes
        per chunk."""
        slot = self._slots[slot_idx]
        req = slot.request
        sp = req.sampling
        if not slot.menu_active:
            ops = self._neutral_ops.get("row")
            if ops is None:
                V = self._vocab
                ops = (jnp.int32(0), jnp.float32(1.0),
                       jnp.float32(1.0), jnp.float32(0.0),
                       jnp.zeros((V,), jnp.int32),
                       jnp.zeros((V,), jnp.float32),
                       jnp.ones((V,), bool))
                self._neutral_ops["row"] = ops
            return ops
        if sp is not None and sp.grammar is not None:
            mask = grammar_mask(sp.grammar, slot.grammar_state,
                                req.eos_id)
        else:
            mask = np.ones((self._vocab,), bool)
        return (np.int32(self._top_k[slot_idx]),
                np.float32(self._top_p[slot_idx]),
                np.float32(self._rep_pen[slot_idx]),
                np.float32(self._pres_pen[slot_idx]),
                self._tok_counts[slot_idx].copy(),
                self._logit_bias[slot_idx].copy(), mask)

    def _dense_prefill(self, slot_idx: int):
        """The PR 2 monolithic prompt program (one pow2-page bucket)."""
        slot = self._slots[slot_idx]
        req = slot.request
        t_start = time.perf_counter()
        t0 = slot.t0
        prompt_pages = -(-t0 // self.page_size)
        bucket = min(_next_pow2(prompt_pages), self.max_pages)
        Tpad = bucket * self.page_size
        ids = np.zeros((1, Tpad), np.int32)
        ids[0, :t0] = slot.attempt_ids
        pages_arr = np.zeros((bucket,), np.int32)
        pages_arr[:prompt_pages] = slot.row[:prompt_pages]
        fn = self._prefill_jits.get(bucket)
        if fn is None:
            fn = jax.jit(self._prefill_fn, donate_argnums=(1, 16))
            self._prefill_jits[bucket] = fn
        self._kvpools, ka, va, tok, self._states = self._dispatch(
            ("dense", Tpad), fn,
            self._param_vals, self._kvpools, self._kamax,
            self._vamax, ids, np.int32(t0), pages_arr,
            np.float32(req.temperature), slot.key,
            *self._slot_sampling_args(slot_idx), self._states,
            np.int32(slot_idx))
        self._pull_amax(ka, va)
        slot.prefill_pos = t0
        # mxlint: allow-host-sync(prefill-boundary readback, once per prompt: the sampled first token must reach token_ids)
        tok = int(np.asarray(tok))
        self.flight.emit(self._component, EventType.PREFILL_CHUNK,
                         request_id=req.request_id, ts=t_start,
                         slot=slot_idx, start=0, n=t0,
                         dur_s=time.perf_counter() - t_start)
        if tok < 0:                          # sign-encoded guard flag
            self._quarantine(slot_idx, "non-finite logits in prefill")
            return
        self._finish_prefill(slot_idx, tok)

    def _run_chunk(self, slot_idx: int) -> int:
        """Process ONE prefill chunk for a prefilling slot; returns the
        number of real prompt tokens processed. The chunk size is
        ``chunk_pages * page_size`` (the tail, and the monolithic-mode
        cache-hit suffix, bucket to the same pow2-page family)."""
        slot = self._slots[slot_idx]
        req = slot.request
        t_start = time.perf_counter()
        start = slot.prefill_pos
        remaining = slot.t0 - start
        if self.chunk_pages is not None:
            n = min(remaining, self.chunk_pages * self.page_size)
        else:
            n = remaining
        bucket = min(_next_pow2(-(-n // self.page_size)), self.max_pages)
        Cpad = bucket * self.page_size
        ids = np.zeros((1, Cpad), np.int32)
        ids[0, :n] = slot.attempt_ids[start:start + n]
        fn = self._chunk_jits.get(bucket)
        if fn is None:
            fn = jax.jit(self._chunk_prefill_fn, donate_argnums=(1, 17))
            self._chunk_jits[bucket] = fn
        self._kvpools, ka, va, tok, self._states = self._dispatch(
            ("chunk", Cpad), fn,
            self._param_vals, self._kvpools, self._kamax,
            self._vamax, ids, np.int32(start), np.int32(n),
            slot.row.copy(), np.float32(req.temperature), slot.key,
            *self._slot_sampling_args(slot_idx), self._states,
            np.int32(slot_idx))
        self._pull_amax(ka, va)
        slot.prefill_pos = start + n
        # mxlint: allow-host-sync(chunk-boundary readback, once per chunk: the guard flag and tail token gate the next chunk)
        tok = int(np.asarray(tok))
        self.flight.emit(self._component, EventType.PREFILL_CHUNK,
                         request_id=req.request_id, ts=t_start,
                         slot=slot_idx, start=start, n=n,
                         dur_s=time.perf_counter() - t_start)
        if tok < 0:                          # sign-encoded guard flag
            # poisoned mid-prompt: fail NOW — later chunks would only
            # propagate the contamination (and the prompt's pages must
            # never reach the prefix index)
            self._quarantine(slot_idx, "non-finite logits in prefill "
                                       f"chunk at {start}")
            return n
        if not slot.prefilling:
            self._finish_prefill(slot_idx, tok)
        return n

    def _finish_prefill(self, slot_idx: int, tok: int):
        """Prompt fully populated: make the slot decode-visible, publish
        its full prompt pages into the prefix index, and record the
        first generated token."""
        slot = self._slots[slot_idx]
        self._page_table[slot_idx, :] = slot.row
        self._lengths[slot_idx] = slot.t0
        self._temps[slot_idx] = slot.request.temperature
        if self._prefix is not None:
            self._prefix.insert(slot.attempt_ids, slot.row,
                                self._alloc)
        done = self._finish_token(slot_idx, tok,
                                  time.perf_counter() - slot.t_admit)
        if done is not None:
            self._evict(slot_idx, done)

    def _advance_prefill(self) -> int:
        """Chunked-prefill scheduler: round-robin one chunk at a time
        over prefilling slots, never exceeding ``token_budget`` real
        prompt tokens per engine step (brownout level 2+ clamps the
        budget to ONE chunk — same bucket shapes, so nothing
        retraces). Returns tokens processed."""
        budget = self.token_budget
        if self.brownout_level >= 2 and self.chunk_pages is not None:
            budget = min(budget, self.chunk_pages * self.page_size)
        spent = 0
        progressed = True
        while budget > 0 and progressed:
            progressed = False
            pf = [s for s in range(self.num_slots)
                  if self._slots[s] is not None
                  and self._slots[s].prefilling]
            if not pf:
                break
            for k in range(len(pf)):
                s = pf[(self._prefill_rr + k) % len(pf)]
                slot = self._slots[s]
                if slot is None or not slot.prefilling:
                    continue
                nxt = min(slot.t0 - slot.prefill_pos,
                          self.chunk_pages * self.page_size)
                if nxt > budget:
                    continue
                n = self._run_chunk(s)
                budget -= n
                spent += n
                progressed = True
            self._prefill_rr += 1
        self.max_step_prefill_tokens = max(self.max_step_prefill_tokens,
                                           spent)
        return spent

    def _propose_drafts(self) -> dict:
        """Host-side drafting (pure data): up to ``spec_k`` candidate
        tokens per decode-ready slot from its OWN prompt + emitted
        history, capped at ``max_new_tokens - emitted - 1`` so the
        accepted output can never exceed the request's token budget —
        which also keeps every write of the draft window inside the
        admission-time worst-case page reservation. Out-of-vocab
        proposals from a custom ``draft_fn`` are truncated at the first
        invalid token rather than fed to the embedding.

        Adaptive gating: a slot whose last ``spec_patience`` draft
        windows were ALL fully rejected is skipped (its drafts are
        hopeless — randomish text the n-gram drafter cannot predict),
        probing again on every ``spec_probe_every``-th engine decode
        step. All gated slots share the probe clock, so a probe costs
        ONE wide step. Returns ``(drafts, gated)`` where ``gated``
        records whether gating suppressed at least one slot — when it
        suppressed them ALL, step() runs the W=1 program and the
        zero-agreement workload pays the plain decode price."""
        drafts: dict = {}
        gated = False
        if self.spec_k == 0 or self.brownout_level >= 1:
            # brownout level 1+ disables speculation: the engine
            # narrow-steps (W=1 — already compiled) until pressure
            # clears, trading peak tokens/s for per-step latency
            return drafts, gated
        vocab = self.model.vocab_size
        probe = self.spec_patience == 0 or \
            self.decode_steps % self.spec_probe_every == 0
        for s in range(self.num_slots):
            slot = self._slots[s]
            if slot is None or slot.prefilling:
                continue
            req = slot.request
            kmax = min(self.spec_k,
                       req.max_new_tokens - len(req.token_ids) - 1)
            if kmax <= 0:
                continue
            if not probe and slot.spec_streak >= self.spec_patience > 0:
                gated = True
                continue
            hist = np.concatenate([req.prompt_ids,
                                   np.asarray(req.token_ids, np.int32)])
            d = np.asarray(self._draft_fn(hist, kmax),
                           np.int32).reshape(-1)[:kmax]
            oob = np.nonzero((d < 0) | (d >= vocab))[0]
            if oob.size:
                d = d[:oob[0]]
            sp = req.sampling
            if d.size and sp is not None and sp.grammar is not None:
                # truncate at the first grammar-forbidden draft: a
                # masked token has probability 0 under the constrained
                # target, so verifying it (and everything after it)
                # would be a guaranteed rejection — pure waste
                st = slot.grammar_state
                keep = 0
                for t in d:
                    t = int(t)
                    if not grammar_mask(sp.grammar, st, req.eos_id)[t]:
                        break
                    keep += 1
                    if t == req.eos_id:
                        break            # drafting past EOS is waste
                    nxt = sp.grammar.advance(st, t)
                    if nxt is None:
                        break
                    st = nxt
                d = d[:keep]
            if d.size:
                drafts[s] = d
        return drafts, gated

    def _ensure_tail_pages(self, drafts=None) -> List[int]:
        """Lazily allocate the pages the NEXT write positions need —
        this is where cache memory tracks live tokens. Prefilling slots
        are skipped: they are decode-invisible and their pages are
        already mapped.

        With speculation, a slot drafting d tokens writes positions
        ``[L, L + d]`` this step, so every page covering that WINDOW
        must be mapped up front. The FIRST page (position L) keeps the
        watchdog/stall semantics — without it the slot cannot advance
        at all; failing to map a LATER window page merely TRUNCATES the
        slot's drafts in ``drafts`` (speculation is best-effort: under
        page pressure it degrades to fewer — or zero — drafts, never
        to a stall the non-speculative engine would not have had).

        A slot whose tail page cannot be allocated (pool starved even
        after reclaiming prefix-index retention) is STALLED, not
        crashed: it sits out this decode step (returned here, masked to
        length 0 with a NULL page row so its dead write cannot touch a
        real — possibly shared — page) and the watchdog evicts it
        FAILED_UNSERVABLE after ``watchdog_steps`` of zero progress."""
        drafts = {} if drafts is None else drafts
        ps = self.page_size
        stalled: List[int] = []
        for s in range(self.num_slots):
            slot = self._slots[s]
            if slot is None or slot.prefilling:
                continue
            L = int(self._lengths[s])
            d = drafts.get(s)
            dlen = 0 if d is None else int(d.size)
            first_pi = L // ps
            mapped_through = first_pi - 1
            starved = False
            for pi in range(first_pi, (L + dlen) // ps + 1):
                if self._page_table[s, pi] != NULL_PAGE:
                    mapped_through = pi
                    continue
                if self._alloc.free_count == 0 and \
                        self._prefix is not None:
                    self.prefix_reclaimed_pages += \
                        self._reclaim_prefix(1)
                if self._alloc.free_count == 0:
                    if pi == first_pi:
                        slot.stall_count += 1
                        if slot.stall_count > self.watchdog_steps:
                            self._evict(
                                s, Outcome.FAILED_UNSERVABLE,
                                f"watchdog: tail page starved for "
                                f"{slot.stall_count} steps")
                        else:
                            stalled.append(s)
                        starved = True
                    break
                page = self._alloc.alloc()
                self._reset_page_amax((page,))   # fresh page, fresh scale
                self._page_table[s, pi] = page
                slot.row[pi] = page
                slot.refs.append(page)
                mapped_through = pi
            if starved:
                drafts.pop(s, None)
                continue
            slot.stall_count = 0
            if dlen:                         # clip drafts to the window
                cap = (mapped_through + 1) * ps - 1 - L
                if cap < dlen:
                    if cap <= 0:
                        drafts.pop(s, None)
                    else:
                        drafts[s] = d[:cap]
        return stalled

    def _mask_block(self, drafts: dict, W: int, live) -> np.ndarray:
        """The (S, W, V) vocabulary-mask block this step's decode
        program takes: column j of a grammar-constrained slot is
        masked at the grammar state AFTER consuming its drafts at
        columns <= j (the host walks the known draft chain), so every
        verify column is constrained exactly as the sequential decode
        at that position would be. Grammar-free steps reuse one cached
        all-True block per width — no per-step allocation on the
        unconstrained hot path."""
        gslots = [s for s in live
                  if self._slots[s].request.sampling is not None and
                  self._slots[s].request.sampling.grammar is not None]
        if not gslots:
            # cached all-True block per width (host np: this branch
            # only runs on the menu-ACTIVE path — fully-neutral steps
            # take _neutral_step_ops' device-resident operands and
            # never reach here)
            m = self._mask_true.get(W)
            if m is None:
                m = self._mask_true[W] = np.ones(
                    (self.num_slots, W, self._vocab), bool)
            return m
        m = np.ones((self.num_slots, W, self._vocab), bool)
        for s in gslots:
            slot = self._slots[s]
            sp = slot.request.sampling
            eos = slot.request.eos_id
            st = slot.grammar_state
            m[s, 0] = grammar_mask(sp.grammar, st, eos)
            d = drafts.get(s)
            if d is None:
                continue
            for j, t in enumerate(d):
                t = int(t)
                if t == eos:
                    break                # later columns are dead
                nxt = sp.grammar.advance(st, t)
                if nxt is not None:
                    st = nxt
                if j + 1 < W:
                    m[s, j + 1] = grammar_mask(sp.grammar, st, eos)
        return m

    def _neutral_step_ops(self, W: int) -> tuple:
        """Committed device-resident NEUTRAL sampling operands for a
        step whose live slots all carry neutral (or no) sampling
        params — built once per width and reused, so the menu-free hot
        path ships zero per-step sampling bytes (the operands are
        value-identical to the real tables when every knob is neutral:
        the penalties never read the counts, the bias adds zero, the
        mask allows everything)."""
        ops = self._neutral_ops.get(W)
        if ops is None:
            S, V = self.num_slots, self._vocab
            ops = (jnp.zeros((S,), jnp.int32),        # top_k (off)
                   jnp.ones((S,), jnp.float32),       # top_p
                   jnp.ones((S,), jnp.float32),       # rep_pen
                   jnp.zeros((S,), jnp.float32),      # pres_pen
                   jnp.zeros((S, V), jnp.int32),      # counts (unread)
                   jnp.zeros((S, V), jnp.float32),    # bias
                   jnp.ones((S, W, V), bool))         # mask
            self._neutral_ops[W] = ops
        return ops

    def step(self) -> int:
        """Enforce deadlines, admit, advance chunked prefill under the
        token budget, then run ONE decode/verify step for all
        decode-ready slots: each live slot advances 1..spec_k+1 tokens
        (exactly 1 when speculation is off, found no draft, or every
        draft missed). Returns the number of live slots that advanced."""
        self._expire_queue()
        self._expire_slots()
        if self._brownout is not None:
            # one deterministic evaluation per scheduler step, BEFORE
            # admission so a clamp decision applies to this step's
            # admissions; level effects are pure host policy
            self._brownout.update(self)
        self._admit()
        if self.chunk_pages is not None:
            self._advance_prefill()
        drafts, gated = self._propose_drafts()
        stalled = self._ensure_tail_pages(drafts)
        live = [s for s in range(self.num_slots)
                if self._slots[s] is not None
                and not self._slots[s].prefilling and s not in stalled]
        if not live:
            return 0
        # adaptive width routing: a step where NO slot drafted runs the
        # W=1 program — bitwise the non-speculative decode step — so
        # gated/zero-draft workloads pay no verify width. Either width
        # traces exactly once (shape-keyed jit cache).
        W = self._spec_w if drafts else 1
        if W > 1:
            self.spec_steps += 1
        elif gated:
            self.spec_gated_steps += 1
        tokens = np.zeros((self.num_slots, W), np.int32)
        draft_len = np.zeros((self.num_slots,), np.int32)
        for s in live:
            tokens[s, 0] = self._slots[s].request.token_ids[-1]
            d = drafts.get(s)
            if d is not None and d.size:
                tokens[s, 1:1 + d.size] = d
                draft_len[s] = d.size
        lengths_dev = self._lengths.copy()
        table_dev = self._page_table.copy()
        for s in stalled:                    # decode-invisible this step
            lengths_dev[s] = 0
            table_dev[s, :] = NULL_PAGE
        if any(self._slots[s].menu_active for s in live):
            samp_ops = (self._top_k.copy(), self._top_p.copy(),
                        self._rep_pen.copy(), self._pres_pen.copy(),
                        self._tok_counts.copy(),
                        self._logit_bias.copy(),
                        self._mask_block(drafts, W, live))
        else:
            samp_ops = self._neutral_step_ops(W)
        # which branches of the sampling tail the step's program will
        # take, from the host's own copies of what it ships (no
        # read-back): the sort under a live slot's top-k / top-p, the
        # draw under a live slot's temperature
        k, p = self._top_k[live], self._top_p[live]
        truncating = int((((k > 0) & (k < self._vocab)) | (p < 1.0)).any())
        drawing = int((self._temps[live] > 0).any())
        self.truncate_steps += truncating
        self.draw_steps += drawing
        t_start = time.perf_counter()
        self._kvpools, ka, va, emitted, n_emit, lengths, self._states = \
            self._dispatch("decode" if W == 1 else "verify",
                           self._decode_step, self._param_vals,
                           self._kvpools, self._kamax,
                           self._vamax, tokens, draft_len, table_dev,
                           lengths_dev, self._temps.copy(),
                           self._slot_keys.copy(), *samp_ops,
                           self._states)
        self._pull_amax(ka, va)
        # THE designed per-step host sync: the scheduler needs the
        # emitted tokens/acceptance counts to advance slots; everything
        # above this line is enqueued without blocking
        # mxlint: allow-host-sync(THE one designed readback per decode step)
        emitted = np.asarray(emitted)
        # mxlint: allow-host-sync(same readback: device already synced by the emitted pull)
        n_emit = np.asarray(n_emit)
        # mxlint: allow-host-sync(same readback: device already synced by the emitted pull)
        new_lengths = np.asarray(lengths).copy()
        for s in stalled:                    # their true length is kept
            new_lengths[s] = self._lengths[s]
        self._lengths = new_lengths
        dt = time.perf_counter() - t_start
        self.decode_steps += 1
        # pages: what the step's attention walked, the live share of a
        # (num_slots x max_pages) table
        self.flight.emit(self._component, EventType.DECODE_STEP,
                         ts=t_start, step=self.decode_steps, width=W,
                         live=len(live), dur_s=dt, pages=int(
                             (-(-new_lengths[live] // self.page_size))
                             .sum()),
                         state_rows=len(live) * len(self._states),
                         truncating=truncating, drawing=drawing)
        for s in live:
            if emitted[s, 0] < 0:            # sign-encoded guard flag
                # poisoned verify: NOTHING from this step is recorded —
                # accepted drafts included (they were scored by
                # non-finite math)
                self._quarantine(s, "non-finite logits in decode")
                continue
            slot = self._slots[s]
            req = slot.request
            d = int(draft_len[s])
            n = int(n_emit[s])
            if d:
                self.drafted_tokens += d
                req.drafted_tokens += d
                # adaptive gating signal: a fully-rejected window grows
                # the streak; ANY acceptance resets it
                slot.spec_streak = 0 if n > 1 else slot.spec_streak + 1
            per_tok = dt / max(n, 1)
            recorded = 0
            for i in range(n):
                done = self._finish_token(s, int(emitted[s, i]), per_tok)
                recorded += 1
                if done is not None:
                    # EOS inside the accepted window: later accepted
                    # tokens are discarded — sequential decode would
                    # never have generated them
                    self._evict(s, done)
                    break
            if d:
                # count only accepted drafts actually RECORDED —
                # columns [0, n - 1) are drafts, n - 1 the
                # bonus/correction, and an in-window EOS discards the
                # tail, which must not inflate accept_rate
                kept = min(recorded, n - 1)
                self.accepted_tokens += kept
                req.accepted_tokens += kept
        return len(live)

    # ------------------------------------------------------------- #
    # page accounting audit (tests / debugging)
    # ------------------------------------------------------------- #

    def audit_pages(self):
        """Assert the global page invariant: every page 1..P-1 is EITHER
        on the free list (refcount 0) OR live — and a live page's
        refcount equals exactly the number of slot mappings plus index
        entries that hold it. Raises MXNetError on any leak (page
        unreachable but not free) or double grant (page free AND
        referenced, or granted twice). With cache tiers on, the third
        state — demoted — is audited too: a demoted entry is payload
        WITHOUT a page id (structurally disjoint from free and live),
        and the tier store's own byte/shape accounting must balance.
        With page transport in play there is a fourth state — IN
        CAPSULE: a detached slot's pages sit in ``_capsule_pages``
        custody (refcounted here, owned by the in-flight transfer) so
        the invariant is free XOR live XOR demoted XOR in-capsule, and
        a request id must never be both slotted and in custody."""
        for rid in self._capsule_pages:
            for slot in self._slots:
                if slot is not None and \
                        slot.request.request_id == rid:
                    raise MXNetError(
                        f"page audit: request {rid} holds a slot AND "
                        f"an in-flight capsule (double identity)")
        expect = [0] * self.num_pages
        for slot in self._slots:
            if slot is None:
                continue
            for p in slot.refs:
                expect[p] += 1
        if self._prefix is not None:
            for p in self._prefix.held_pages():
                expect[p] += 1
        for p in self._alloc.held:           # chaos-harness page holds
            expect[p] += 1
        for pages in self._capsule_pages.values():   # in-capsule custody
            for p in pages:
                expect[p] += 1
        free = self._alloc._free
        free_set = set(free)
        if len(free_set) != len(free):
            raise MXNetError("page audit: duplicate pages on the free "
                             "list (double grant)")
        if NULL_PAGE in free_set:
            raise MXNetError("page audit: the null page is on the free "
                             "list")
        for p in range(1, self.num_pages):
            rc = self._alloc.refcount(p)
            if rc != expect[p]:
                raise MXNetError(
                    f"page audit: page {p} refcount {rc} != "
                    f"{expect[p]} references held (slots + index)")
            if (p in free_set) == (rc > 0):
                state = "free AND referenced (double grant)" if rc > 0 \
                    else "neither free nor referenced (leak)"
                raise MXNetError(f"page audit: page {p} is {state}")
        if self._tiers is not None:
            # demoted entries hold PAYLOADS, never page ids, so the
            # page-level invariant above cannot see them; the tier
            # store audits its own accounting (free XOR live XOR
            # demoted — "demoted" lives entirely below this line)
            self._tiers.audit()

    # ------------------------------------------------------------- #
    # elastic checkpointing / warm restart (checkpoint/ subsystem)
    # ------------------------------------------------------------- #

    def warm_start(self, params=None, manager=None, step=None) -> None:
        """Swap new model weights into the LIVE engine without
        retracing: weights are traced inputs of the decode/prefill
        programs, so as long as shapes and dtypes match, the compiled
        steps are reused as-is (``decode_trace_count`` stays put —
        asserted in tests/test_serve.py).

        The prefix index is FLUSHED: cached K/V was computed under the
        old weights, and serving it against new weights would silently
        mix models (``prefix_flushes`` counts, asserted in tests).

        ``params``: dict keyed by Parameter name (a training capsule's
        ``param/`` entries also accepted), or pass ``manager`` (+
        optional ``step``) to pull the latest committed training
        capsule straight from a CheckpointManager.
        """
        import jax.numpy as jnp
        if params is None:
            if manager is None:
                raise MXNetError("warm_start needs params or a "
                                 "CheckpointManager")
            params, _meta = manager.restore(step)
        # a training capsule also carries opt/<i>/<j> and rng/key
        # entries — when param/ keys exist, ONLY they are weights;
        # otherwise the dict itself is the name→array mapping
        items = {k[len("param/"):]: v for k, v in params.items()
                 if k.startswith("param/")} or params
        flat = {}
        for name, v in items.items():
            flat[name] = v._data if isinstance(v, NDArray) else np.asarray(v)
        positional = all(n.isdigit() for n in flat)
        for i, p in enumerate(self._eng_params):
            # capsules key params positionally ("param/<i>", construction
            # order); plain dicts may key by Parameter name
            lookup = str(i) if positional else p.name
            if lookup not in flat:
                raise MXNetError(f"warm_start: no value for parameter "
                                 f"{i} ('{p.name}')")
            new = jnp.asarray(flat[lookup])    # one conversion, reused
            cur = p.data()._data
            if new.shape != cur.shape or new.dtype != cur.dtype:
                raise MXNetError(
                    f"warm_start: parameter '{p.name}' is "
                    f"{str(cur.dtype)}{tuple(cur.shape)} but new value "
                    f"is {str(new.dtype)}{tuple(new.shape)}"
                    f" — shape/dtype changes require a new engine")
            p.data()._data = new
        self._param_vals = tuple(p.data()._data
                                 for p in self._eng_params)
        if self._prefix is not None:
            # cached K/V is weight-dependent — a prefix computed under
            # the old weights must never be matched again
            self._prefix.flush(self._alloc)
            self.prefix_flushes += 1
        if self._tiers is not None:
            # same contract one level down: DRAM/disk payloads were
            # captured under the old weights — ALL tiers flush
            self._tiers.flush()
        self.warm_restarts += 1

    def save_checkpoint(self, manager, step=None, block=False) -> int:
        """Snapshot the serving weights into ``manager`` (async) so a
        replacement process can ``warm_start(manager=...)``."""
        tree = {f"param/{i}": p.data()
                for i, p in enumerate(self._eng_params)}
        meta = {"kind": "serve",
                "param_names": [p.name for p in self._eng_params],
                "step": int(step if step is not None
                            else self.decode_steps)}
        manager.save(int(meta["step"]), tree, meta=meta, block=block)
        return int(meta["step"])

    def install_preemption(self, manager, exit_after=True):
        """SIGTERM → drain in-flight snapshot + final sync weight save
        (the serving tier's preemption contract)."""

        def _state():
            tree = {f"param/{i}": p.data()
                    for i, p in enumerate(self._eng_params)}
            return self.decode_steps, tree, {"kind": "serve",
                                             "step": self.decode_steps}

        return manager.install_preemption_hook(_state,
                                               exit_after=exit_after)

    def shutdown(self, detail: str = "engine shutdown"):
        """Graceful stop (SIGTERM / replica drain): every in-flight and
        queued request becomes terminal — active slots are evicted
        (pages reclaimed, partial tokens kept) and the queue is failed —
        all with SHED, the 'retry me on another replica' signal. The
        engine stays structurally valid (``audit_pages`` passes) and
        idle afterwards."""
        for s in range(self.num_slots):
            if self._slots[s] is not None:
                self._evict(s, Outcome.SHED, detail)
        while self._queue:
            self._record_terminal(self._queue.popleft(), Outcome.SHED,
                                  detail)

    def _fail_starved_head(self, polls: int):
        """Bounded give-up on an unadmittable queue head while the
        engine is otherwise idle — shared by ``run()`` and the HTTP
        front end's driver loop (serve/frontend.py), so both speak the
        same outcome semantics. The PRIORITY head is what admission is
        blocked on — failing a lower tier behind it would not unwedge
        anything. A head that is only queued because the brownout
        clamp holds its tier is NOT page-starved: it gets a retryable
        SHED (the honest 'come back when pressure clears'), not a
        FAILED_UNSERVABLE — still bounded, the engine never wedges on
        a pinned controller."""
        head = self._queue_head(clamped_ok=False)
        if head is not None:
            self.withdraw(head)
            self._record_terminal(
                head, Outcome.FAILED_UNSERVABLE,
                f"page-starved: head of an idle engine "
                f"for {polls} polls "
                f"(free={self._alloc.free_count})")
        else:
            head = self._queue_head()
            self.withdraw(head)
            self._record_terminal(
                head, Outcome.SHED,
                f"brownout level {self.brownout_level} "
                f"held {head.tier.value} admissions "
                f"clamped for {polls} idle polls")

    def run(self, requests, arrival_times=None, poll_sleep=1e-3,
            before_step=None, after_step=None):
        """Drive ``requests`` until EVERY one is terminal (structured
        ``Outcome`` — never an exception for per-request conditions).
        ``arrival_times`` (seconds, relative to call time) gates
        submission — the Poisson-arrival harness of
        tools/serve_bench.py; None submits everything up front (pure
        batch drain).

        ``before_step(engine, i)`` / ``after_step(engine, i)`` bracket
        every scheduler iteration ``i`` — the chaos harness's injection
        and per-step audit hooks (serve/chaos.py).

        A queue head that cannot be admitted while the engine is
        otherwise idle (page starvation — e.g. the pool is chaos-held
        or fragmented by retention) is failed FAILED_UNSERVABLE after
        ``stall_steps`` consecutive idle polls; requests too large to
        EVER fit were already failed at submit. The engine keeps
        serving everything else — one doomed request no longer raises
        out of the serving loop."""
        if arrival_times is None:
            for r in requests:
                self.submit(r)
            pending = []
        else:
            pending = sorted(zip(arrival_times, requests),
                             key=lambda p: p[0])
        t0 = time.perf_counter()
        stall = 0
        it = 0
        while pending or self._queue or self.active_count:
            now = time.perf_counter() - t0
            while pending and pending[0][0] <= now:
                self.submit(pending.pop(0)[1])
            if before_step is not None:
                before_step(self, it)
            n = self.step()
            if after_step is not None:
                after_step(self, it)
            it += 1
            if n > 0 or self.active_count:
                stall = 0
                continue
            if self._queue:
                # nothing decoding, nothing prefilling, head unadmitted
                stall += 1
                if stall > self.stall_steps:
                    self._fail_starved_head(stall)
                    stall = 0
                else:
                    time.sleep(poll_sleep)   # let deadlines/holds move
            elif pending:
                stall = 0
                time.sleep(min(poll_sleep,
                               max(0.0, pending[0][0] - now)))
        return requests

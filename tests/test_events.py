"""Flight recorder + end-to-end tracing tests (serve/events.py,
tools/trace_export.py, the serve/metrics.py histograms —
docs/OBSERVABILITY.md).

The load-bearing claims: (1) EVERY structured transition — all nine
request ``Outcome``s, all four training ``StepOutcome``s, every
brownout level move, every replica health move — emits EXACTLY ONE
event through the recorder API, and the health counters can never
disagree with the event stream they summarize; (2) postmortem dumps
validate against the schema and name the faulted entity; (3) the
Perfetto export of a mixed prefill/decode/preemption run validates
and renders per-slot lanes; (4) the tier-labeled latency histograms
golden-parse with correct ``le`` buckets / ``+Inf`` / ``_sum`` /
``_count`` discipline; (5) the recorder is cheap, bounded, and
cleanly disableable."""

import json
import re
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.models import gpt as g
from incubator_mxnet_tpu.serve import (EventType, FlightRecorder,
                                       InferenceEngine, Outcome,
                                       Request, Tier, build_fleet,
                                       render_metrics)
from incubator_mxnet_tpu.serve.chaos import NaNWeights, run_chaos
from incubator_mxnet_tpu.serve.events import (DEFAULT_BUCKETS,
                                              terminal_fields,
                                              token_gaps,
                                              validate_event_dict,
                                              validate_postmortem)
from incubator_mxnet_tpu.serve.slo import BrownoutController
from incubator_mxnet_tpu.train.outcomes import StepOutcome, StepRecorder
from tools.trace_export import to_perfetto, validate_trace

VOCAB = 64


@pytest.fixture(scope="module")
def model():
    mx.random.seed(0)
    m = g.gpt_mini(vocab_size=VOCAB, max_length=64)
    m.initialize()
    return m


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=(n,)).astype(np.int32)


def _drain(eng, reqs, max_steps=3000):
    steps = 0
    while any(r.outcome is None for r in reqs):
        eng.step()
        steps += 1
        assert steps < max_steps, "engine failed to reach quiescence"
    return steps


def _terminals(flight):
    return flight.events(etype=EventType.TERMINAL)


# ------------------------------------------------------------------- #
# recorder core semantics
# ------------------------------------------------------------------- #

def test_recorder_causal_order_ring_bound_and_dump(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.emit("a", EventType.DECODE_STEP, step=i)
    rec.emit("b", EventType.SUBMIT, request_id=7, tier="STANDARD")
    evs = rec.events()
    # bounded per component: a's ring kept the trailing 8 only
    assert len(rec.events("a")) == 8
    assert [e.data["step"] for e in rec.events("a")] == list(range(12,
                                                                  20))
    # merged view is seq-ordered (total causal order)
    seqs = [e.seq for e in evs]
    assert seqs == sorted(seqs)
    assert rec.emitted == 21
    # serialized events validate, and the dump round-trips
    path = tmp_path / "events.json"
    rec.dump_events(str(path))
    payload = json.loads(path.read_text())
    assert payload["schema_version"] == 1
    for d in payload["events"]:
        validate_event_dict(d)
    with pytest.raises(ValueError):
        validate_event_dict({"seq": 1, "ts": 0.0, "component": "x",
                             "etype": "NOT_A_TYPE"})


def test_recorder_disabled_is_a_noop(model):
    rng = np.random.RandomState(3)
    eng = InferenceEngine(model, num_slots=1, page_size=8, max_len=64,
                          recorder=False)
    reqs = [Request(_prompt(rng, 5), max_new_tokens=2)]
    eng.submit(reqs[0])
    _drain(eng, reqs)
    assert reqs[0].outcome is not None
    assert eng.flight.events() == []
    snap = eng.health_snapshot()
    assert snap["latency_hists"] is None
    assert "_bucket" not in render_metrics(snap)


def test_token_gaps_and_terminal_fields():
    assert token_gaps([1.0, 1.5, 2.5]) == [0.5, 1.0]
    req = SimpleNamespace(outcome=Outcome.EOS, tier=Tier.LATENCY,
                          token_ids=[1, 2, 3], detail="",
                          retry_after_s=None, submit_time=10.0,
                          finish_time=12.0,
                          token_stamps=[10.5, 11.0, 12.0])
    f = terminal_fields(req)
    assert f["outcome"] == "EOS" and f["tier"] == "LATENCY"
    assert f["e2e_s"] == pytest.approx(2.0)
    assert f["ttft_s"] == pytest.approx(0.5)
    assert f["tpot_gaps"] == [0.5, 1.0]


# ------------------------------------------------------------------- #
# event-schema completeness: every Outcome → exactly one TERMINAL
# ------------------------------------------------------------------- #

def test_engine_outcomes_emit_exactly_one_terminal(model):
    """EOS, MAX_TOKENS, SHED, DEADLINE_EXPIRED, FAILED_UNSERVABLE,
    CANCELLED, PREEMPTED all through one engine; the TERMINAL events
    match the per-request outcomes one-to-one and the health counters
    equal the event tally (counters and events can never disagree)."""
    rng = np.random.RandomState(5)
    probe = InferenceEngine(model, num_slots=1, page_size=8,
                            max_len=64, recorder=False)
    p_eos = _prompt(rng, 5)
    pr = Request(p_eos.copy(), max_new_tokens=4)
    probe.submit(pr)
    _drain(probe, [pr])
    first_tok = pr.token_ids[0]          # greedy: reproducible

    eng = InferenceEngine(model, num_slots=1, page_size=8, max_len=64,
                          max_queue=1, max_preemptions=0)
    reqs = {}
    # EOS: stop on the known first token
    reqs["EOS"] = Request(p_eos.copy(), max_new_tokens=4,
                          eos_id=first_tok)
    assert eng.submit(reqs["EOS"])
    _drain(eng, [reqs["EOS"]])
    # MAX_TOKENS
    reqs["MAX_TOKENS"] = Request(_prompt(rng, 5), max_new_tokens=2)
    assert eng.submit(reqs["MAX_TOKENS"])
    _drain(eng, [reqs["MAX_TOKENS"]])
    # FAILED_UNSERVABLE: can never fit (fail-fast at submit)
    reqs["FAILED_UNSERVABLE"] = Request(_prompt(rng, 60),
                                        max_new_tokens=30)
    assert not eng.submit(reqs["FAILED_UNSERVABLE"])
    # SHED: queue bound 1, same tier — the second queued submit sheds
    held = Request(_prompt(rng, 40), max_new_tokens=4)   # blocks slot 0
    filler = Request(_prompt(rng, 5), max_new_tokens=4)
    assert eng.submit(held)
    # occupy the slot so the queue actually builds
    eng.step()
    assert eng.submit(filler)
    reqs["SHED"] = Request(_prompt(rng, 5), max_new_tokens=2)
    assert not eng.submit(reqs["SHED"])
    # CANCELLED: cancel the queued filler
    reqs["CANCELLED"] = filler
    assert eng.cancel(filler)
    # PREEMPTED: max_preemptions=0 — a LATENCY arrival preempts the
    # BATCH holder terminally... the holder is STANDARD; use fresh
    batch = Request(_prompt(rng, 5), max_new_tokens=30,
                    tier=Tier.BATCH)
    # drain the current holder first
    _drain(eng, [held])
    assert eng.submit(batch)
    eng.step()                           # batch takes the slot
    lat = Request(_prompt(rng, 5), max_new_tokens=2, tier=Tier.LATENCY)
    assert eng.submit(lat)
    _drain(eng, [lat])
    reqs["PREEMPTED"] = batch
    assert batch.outcome is Outcome.PREEMPTED
    # DEADLINE_EXPIRED: sub-microsecond deadline, expired in queue
    reqs["DEADLINE_EXPIRED"] = Request(_prompt(rng, 5),
                                       max_new_tokens=2,
                                       deadline_s=1e-7)
    assert eng.submit(reqs["DEADLINE_EXPIRED"])
    import time as _t
    _t.sleep(0.001)
    eng.step()

    for want, r in reqs.items():
        assert r.outcome is not None and r.outcome.value == want, \
            f"{want}: got {r.outcome}"
    terms = _terminals(eng.flight)
    by_rid = Counter(e.request_id for e in terms)
    for want, r in reqs.items():
        assert by_rid[r.request_id] == 1, \
            f"{want}: {by_rid[r.request_id]} TERMINAL events"
        (ev,) = [e for e in terms if e.request_id == r.request_id]
        assert ev.data["outcome"] == want
        assert ev.data["tier"] == r.tier.value
    # counters == event tally, for every outcome ever recorded
    tally = Counter(e.data["outcome"] for e in terms)
    for o, n in eng.health.items():
        assert tally.get(o, 0) == n, f"counter drift on {o}"
    # lifecycle sanity: one SUBMIT per submitted request, decode steps
    # counted 1:1
    submits = Counter(e.request_id
                      for e in eng.flight.events(
                          etype=EventType.SUBMIT))
    assert all(n == 1 for n in submits.values())
    assert len(eng.flight.events(etype=EventType.DECODE_STEP)) == \
        eng.decode_steps
    # exactly one PREEMPT event for the preempted request
    preempts = eng.flight.events(etype=EventType.PREEMPT)
    assert len(preempts) == 1 and \
        preempts[0].request_id == batch.request_id


def test_decode_step_counts_the_pages_its_attention_walked(model):
    """``DECODE_STEP.pages``: the live slots' ``ceil(length /
    page_size)`` summed, lengths as the step's attention read them
    (the token written this step included): the live share of the
    (num_slots x max_pages) table the ragged kernel walks."""
    rng = np.random.RandomState(9)
    eng = InferenceEngine(model, num_slots=3, page_size=8, max_len=64)
    reqs = [Request(_prompt(rng, n), max_new_tokens=6) for n in (3, 8, 21)]
    for r in reqs:
        assert eng.submit(r)
    _drain(eng, reqs)
    steps = eng.flight.events(etype=EventType.DECODE_STEP)
    # all three prompts prefill in step 1, so decode step i reads
    # prompt + i keys in every slot still live; a request's last token
    # comes from the step after its fifth decode write
    want = [sum(-(-(n + i) // 8) for n in (3, 8, 21))
            for i in range(1, 6)]
    got = [e.data["pages"] for e in steps]
    assert [e.data["live"] for e in steps] == [3] * len(steps)
    assert got == want, (got, want)


def test_state_cache_events_say_what_was_zeroed_and_updated(model):
    """A model with state layers: ``ADMIT.state_zeroed`` is true at every
    admission and ``DECODE_STEP.state_rows`` counts a row a live slot a
    state layer; a model without state layers reads false and 0."""
    from granite_hybrid_util import build_model, draw_weights, tiny_config
    cfg = tiny_config()
    hybrid = build_model(cfg, draw_weights(cfg, 3))
    rng = np.random.RandomState(11)
    eng = InferenceEngine(hybrid, num_slots=2, page_size=8, max_len=64,
                          prefix_cache=False, chunk_pages=2)
    reqs = [Request(rng.randint(0, 256, n).astype(np.int32),
                    max_new_tokens=5) for n in (3, 20, 9)]
    for r in reqs:
        assert eng.submit(r)
    _drain(eng, reqs)
    admits = eng.flight.events(etype=EventType.ADMIT)
    assert [e.data["state_zeroed"] for e in admits] == [True] * 3
    steps = eng.flight.events(etype=EventType.DECODE_STEP)
    assert steps and all(e.data["state_rows"] == 5 * e.data["live"]
                         for e in steps)
    assert eng.state_zero_trace_count == 1
    plain = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    req = Request(_prompt(rng, 5), max_new_tokens=3)
    assert plain.submit(req)
    _drain(plain, [req])
    assert [e.data["state_zeroed"] for e in
            plain.flight.events(etype=EventType.ADMIT)] == [False]
    assert all(e.data["state_rows"] == 0 for e in
               plain.flight.events(etype=EventType.DECODE_STEP))


def test_nonfinite_quarantine_emits_terminal():
    # PRIVATE model: NaNWeights poisons the weights in place via
    # warm_start — the shared module fixture must never see it
    mx.random.seed(2)
    own = g.gpt_mini(vocab_size=VOCAB, max_length=64)
    own.initialize()
    rng = np.random.RandomState(11)
    eng = InferenceEngine(own, num_slots=2, page_size=8, max_len=64)
    reqs = [Request(_prompt(rng, 5), max_new_tokens=8)
            for _ in range(2)]
    run_chaos(eng, reqs, [NaNWeights(at_step=1, seed=0)])
    assert all(r.outcome is Outcome.FAILED_NONFINITE for r in reqs)
    terms = _terminals(eng.flight)
    assert Counter(e.request_id for e in terms) == \
        Counter(r.request_id for r in reqs)
    # the injected fault itself is on the timeline (CHAOS event)
    assert any(e.etype is EventType.CHAOS and
               e.entity == "nan_weights"
               for e in eng.flight.events())


def test_router_failover_events_and_postmortem(model):
    rt = build_fleet(model, 2,
                     engine_kw=dict(num_slots=2, page_size=8,
                                    max_len=64),
                     max_requeues=0)
    rng = np.random.RandomState(7)
    reqs = [Request(_prompt(rng, 5), max_new_tokens=6)
            for _ in range(4)]
    from incubator_mxnet_tpu.serve.chaos import (KillReplica,
                                                 run_fleet_chaos)
    run_fleet_chaos(rt, reqs, [KillReplica(0, at_step=1,
                                           phase="decode")])
    # exactly one client TERMINAL per request
    terms = _terminals(rt.flight)
    assert Counter(e.request_id for e in terms) == \
        Counter(r.request_id for r in reqs)
    failed = [r for r in reqs if r.outcome is Outcome.FAILED_REPLICA]
    assert failed, "the kill produced no FAILED_REPLICA at bound"
    # replica death is one REPLICA_HEALTH transition to DEAD
    deaths = [e for e in rt.flight.events(
        etype=EventType.REPLICA_HEALTH)
        if e.data["to_state"] == "DEAD"]
    assert len(deaths) == 1 and deaths[0].data["replica"] == 0
    # FAILED_REPLICA at the bound dumped a postmortem that validates
    # and names the request + the dead replica
    assert len(rt.flight.postmortems) == len(failed)
    pm = list(rt.flight.postmortems)[-1]
    validate_postmortem(pm)
    assert "request" in pm["entity"]
    ets = [e["etype"] for e in pm["events"]]
    assert "REPLICA_HEALTH" in ets and "CHAOS" in ets
    # replicas adopted fleet lane names
    assert rt.replicas[1].engine._component == "replica1"


# ------------------------------------------------------------------- #
# training / brownout / replica-health / checkpoint / supervisor
# ------------------------------------------------------------------- #

def test_step_outcomes_emit_exactly_one_event_each():
    rec = StepRecorder(max_consecutive_nonfinite=2)
    rec.open_step()
    rec.record(StepOutcome.APPLIED)
    rec.open_step()
    rec.record(StepOutcome.SKIPPED_STALE)
    rec.open_step()
    rec.record(StepOutcome.SKIPPED_NONFINITE)
    rec.open_step()
    out = rec.record(StepOutcome.SKIPPED_NONFINITE)   # escalates
    assert out is StepOutcome.HALTED_POISONED
    evs = rec.flight.events(etype=EventType.TRAIN_STEP)
    assert [e.data["outcome"] for e in evs] == \
        ["APPLIED", "SKIPPED_STALE", "SKIPPED_NONFINITE",
         "HALTED_POISONED"]
    # all four StepOutcome values covered, one event per record()
    assert {e.data["outcome"] for e in evs} == \
        {o.value for o in StepOutcome}
    tally = Counter(e.data["outcome"] for e in evs)
    assert dict(tally) == {k: v for k, v in rec.health.items() if v}
    # the halt dumped a postmortem naming the trainer
    assert len(rec.flight.postmortems) == 1
    pm = rec.flight.postmortems[0]
    validate_postmortem(pm)
    assert pm["reason"] == "HALTED_POISONED"
    assert pm["entity"] == "trainer"


def test_brownout_transitions_emit_one_event_each():
    bo = BrownoutController(enter=(0.5, 0.7, 0.9), exit_margin=0.2,
                            up_steps=1, down_steps=1)
    bo.flight = FlightRecorder(histograms=False)
    snaps = {"num_slots": 4, "queue_depth": 40, "free_pages": 0,
             "active_slots": 4, "estimated_queue_delay_s": None}
    eng = SimpleNamespace(num_pages=11, decode_steps=0,
                          health_snapshot=lambda: dict(snaps))
    for _ in range(3):                   # 0→1→2→3
        bo.update(eng)
        eng.decode_steps += 1
    snaps.update(queue_depth=0, active_slots=0, free_pages=10)
    for _ in range(3):                   # 3→2→1→0
        bo.update(eng)
        eng.decode_steps += 1
    evs = bo.flight.events(etype=EventType.BROWNOUT)
    assert len(evs) == len(bo.timeline) == \
        bo.escalations + bo.deescalations == 6
    for e in evs:                        # one level at a time, logged
        assert abs(e.data["to_level"] - e.data["from_level"]) == 1


def test_replica_health_recovery_emits_transitions(model):
    rt = build_fleet(model, 1,
                     engine_kw=dict(num_slots=1, page_size=8,
                                    max_len=64),
                     breaker_failures=2, probe_recovery=2)
    rep = rt.replicas[0]
    for _ in range(2):
        rt._heartbeat_miss(rep, "unit-driven miss")
    assert rep.state.value == "DEGRADED"
    for _ in range(2):
        rt._step_ok(rep, dt=0.0, compiled=False)
    assert rep.state.value == "SERVING"
    evs = rt.flight.events(etype=EventType.REPLICA_HEALTH)
    assert [(e.data["from_state"], e.data["to_state"])
            for e in evs] == [("SERVING", "DEGRADED"),
                              ("DEGRADED", "SERVING")]


def test_checkpoint_commit_event(tmp_path):
    from incubator_mxnet_tpu.checkpoint import CheckpointManager
    mgr = CheckpointManager(str(tmp_path), keep=2)
    mgr.save(3, {"w": np.arange(4, dtype=np.float32)}, block=True)
    mgr.close()
    evs = mgr.flight.events(etype=EventType.CHECKPOINT_COMMIT)
    assert len(evs) == 1
    assert evs[0].data["step"] == 3
    assert evs[0].entity == str(tmp_path)


def test_supervisor_restart_and_giveup_events(tmp_path):
    import sys
    from incubator_mxnet_tpu.base import MXNetError
    from incubator_mxnet_tpu.train.supervisor import Supervisor
    sup = Supervisor([sys.executable, "-c", "import sys; sys.exit(3)"],
                     max_restarts=1, backoff_s=0.01,
                     postmortem_dir=str(tmp_path))
    with pytest.raises(MXNetError):
        sup.run()
    restarts = sup.flight.events(etype=EventType.SUPERVISOR_RESTART)
    giveups = sup.flight.events(etype=EventType.SUPERVISOR_GIVEUP)
    assert len(restarts) == 1 and restarts[0].data["exit_code"] == 3
    assert len(giveups) == 1
    assert len(sup.flight.postmortems) == 1
    pm = sup.flight.postmortems[0]
    validate_postmortem(pm)
    assert pm.get("path") and json.load(open(pm["path"]))


# ------------------------------------------------------------------- #
# histogram golden-parse (le buckets / +Inf / _sum / _count)
# ------------------------------------------------------------------- #

_HLINE = re.compile(r"^(\w+?)(_bucket|_sum|_count)"
                    r"(\{[^}]*\})?\s([-+0-9.eEIna]+)$")


def _parse_hists(text):
    """{base: {labels-sans-le: {"buckets": [(le, cum)], "sum": x,
    "count": n}}} from the rendered metrics text."""
    out = {}
    for line in text.splitlines():
        m = _HLINE.match(line)
        if not m:
            continue
        base, kind, labels, value = m.groups()
        labels = labels or ""
        le = None
        if kind == "_bucket":
            lm = re.search(r'le="([^"]+)"', labels)
            assert lm, f"bucket without le: {line!r}"
            le = lm.group(1)
            labels = re.sub(r',?le="[^"]+"', "", labels)
        cell = out.setdefault(base, {}).setdefault(
            labels, {"buckets": [], "sum": None, "count": None})
        if kind == "_bucket":
            cell["buckets"].append((le, float(value)))
        elif kind == "_sum":
            cell["sum"] = float(value)
        else:
            cell["count"] = float(value)
    return out


def test_latency_histograms_golden_parse(model):
    rng = np.random.RandomState(9)
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64)
    reqs = [Request(_prompt(rng, 5), max_new_tokens=4,
                    tier=[Tier.LATENCY, Tier.BATCH][i % 2])
            for i in range(6)]
    for r in reqs:
        eng.submit(r)
    _drain(eng, reqs)
    snap = eng.health_snapshot()
    text = render_metrics(snap)
    hists = _parse_hists(text)
    for metric in ("ttft", "tpot", "queue_delay", "e2e_latency"):
        name = f"mxtpu_serve_{metric}_seconds"
        assert name in hists, f"missing histogram {name}"
        assert f"# TYPE {name} histogram" in text
        for labels, cell in hists[name].items():
            assert 'tier="' in labels
            les = [le for le, _ in cell["buckets"]]
            # le set: the full bound family, ascending, closed by +Inf
            assert les[:-1] == [repr(float(b)) for b in
                                DEFAULT_BUCKETS]
            assert les[-1] == "+Inf"
            counts = [c for _, c in cell["buckets"]]
            assert counts == sorted(counts), "buckets not cumulative"
            assert cell["count"] == counts[-1], "+Inf != _count"
            assert cell["sum"] is not None and cell["sum"] >= 0
    # per-token accounting: TPOT observations = tokens - one first
    # token per request (the gaps between consecutive stamps)
    total_gaps = sum(len(r.token_ids) - 1 for r in reqs)
    tpot_cells = hists["mxtpu_serve_tpot_seconds"]
    assert sum(c["count"] for c in tpot_cells.values()) == total_gaps
    # TTFT/e2e: one observation per request, per tier
    ttft = hists["mxtpu_serve_ttft_seconds"]
    assert sum(c["count"] for c in ttft.values()) == len(reqs)
    # histograms come from the SAME stream as the counters: e2e count
    # equals the terminal tally
    assert sum(c["count"] for c in
               hists["mxtpu_serve_e2e_latency_seconds"].values()) == \
        sum(eng.health.values())


def test_router_metrics_include_client_histograms(model):
    rt = build_fleet(model, 2, engine_kw=dict(num_slots=1, page_size=8,
                                              max_len=64))
    rng = np.random.RandomState(15)
    reqs = [Request(_prompt(rng, 5), max_new_tokens=3)
            for _ in range(3)]
    rt.run(reqs)
    text = render_metrics(rt.health_snapshot())
    hists = _parse_hists(text)
    # client-level histograms at the fleet namespace AND per-replica
    # attempt histograms under the replica namespace
    assert "mxtpu_serve_e2e_latency_seconds" in hists
    assert "mxtpu_serve_replica_e2e_latency_seconds" in hists
    for labels in hists["mxtpu_serve_replica_e2e_latency_seconds"]:
        assert 'replica="' in labels
    # the router's DISPATCH events feed the CLIENT queue-delay
    # histogram (one observation per dispatch)
    qd = hists["mxtpu_serve_queue_delay_seconds"]
    assert sum(c["count"] for c in qd.values()) >= len(reqs)


# ------------------------------------------------------------------- #
# Perfetto export
# ------------------------------------------------------------------- #

def test_perfetto_export_mixed_run_slot_lanes(model):
    rng = np.random.RandomState(21)
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          chunk_pages=1, max_preemptions=4)
    batch = [Request(_prompt(rng, 20), max_new_tokens=6,
                     tier=Tier.BATCH) for _ in range(3)]
    for r in batch:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    lat = [Request(_prompt(rng, 5), max_new_tokens=3,
                   tier=Tier.LATENCY) for _ in range(2)]
    for r in lat:
        eng.submit(r)
    _drain(eng, batch + lat)
    assert eng.preemptions >= 1          # the mix exercises preemption
    trace = to_perfetto(eng.flight.events())
    validate_trace(trace)
    xs = [ev for ev in trace["traceEvents"] if ev["ph"] == "X"]
    slot_lanes = {ev["tid"] for ev in xs
                  if str(ev["tid"]).startswith("slot")}
    assert len(slot_lanes) >= 2, f"per-slot lanes missing: {xs[:3]}"
    cats = {ev.get("cat") for ev in trace["traceEvents"]}
    assert {"request", "prefill", "decode"} <= cats
    # every span is non-negative and timestamps are rebased
    assert all(ev["ts"] >= 0 and ev["dur"] >= 0 for ev in xs)
    # request spans name their outcome
    req_spans = [ev for ev in xs if ev["cat"] == "request"]
    assert any("(MAX_TOKENS)" in ev["name"] or "(EOS)" in ev["name"]
               for ev in req_spans)
    assert any("(preempted)" in ev["name"] for ev in req_spans)
    # json-loadable end to end
    json.loads(json.dumps(trace))


def test_perfetto_export_rejects_malformed():
    with pytest.raises(ValueError):
        validate_trace({"traceEvents": [{"ph": "X", "name": "x",
                                         "pid": 1, "tid": 1,
                                         "ts": 0.0}]})   # no dur
    with pytest.raises(ValueError):
        validate_trace({"not_traceEvents": []})


def test_postmortem_schema_rejects_malformed():
    rec = FlightRecorder(histograms=False)
    rec.emit("x", EventType.SUBMIT, request_id=1, tier="STANDARD")
    pm = rec.postmortem("unit", "entity-x", context={"k": 1})
    validate_postmortem(pm)
    bad = dict(pm)
    bad["events"] = list(reversed([dict(e) for e in pm["events"]] +
                                  [{"seq": 0, "ts": 0.0,
                                    "component": "x",
                                    "etype": "SUBMIT"}]))
    with pytest.raises(ValueError):
        validate_postmortem(bad)
    with pytest.raises(ValueError):
        validate_postmortem({"reason": "r"})

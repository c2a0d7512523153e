"""The training step measured from inside (docs/OBSERVABILITY.md "Where a
training step's time goes"): ``mx.`` scopes in the compiled step and the
table that reads them back, host phases on the ``TRAIN_STEP`` event,
``mx.trainer.*`` annotations while a profiler session is live, compile
events from ``jax.monitoring``, and ``parallel.live_trainers()``."""

import gc
import glob
import os
import re
import time

import jax
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import parallel, profiler
from incubator_mxnet_tpu.events import EventType, validate_event_dict
from incubator_mxnet_tpu.models import bert as B, gpt as G
from incubator_mxnet_tpu.parallel import mesh as pmesh
from tools.trace_export import to_perfetto, validate_trace

VOCAB, T = 128, 32
PHASES = ("prepare_s", "dispatch_s", "bind_s", "flag_wait_s")


def _mesh(n=1):
    return pmesh.build_mesh(devices=jax.devices()[:n], axis_sizes={"dp": n})


def _gpt(rows=4, remat="dots", mesh=None):
    mx.random.seed(0)
    m = G.gpt_mini(vocab_size=VOCAB, max_length=T, dropout=0.0, remat=remat)
    m.initialize()
    tr = parallel.SPMDTrainer(
        m, forward_loss=G.lm_loss, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3}, mesh=mesh or _mesh())
    rng = np.random.RandomState(0)

    def batch(n=rows):
        return (rng.randint(0, VOCAB, (n, T)).astype(np.int32),
                rng.randint(0, VOCAB, (n, T)).astype(np.int32))
    return tr, batch


def _bert(rows=4):
    mx.random.seed(0)
    m = B.BERTForPretraining(B.bert_tiny(vocab_size=VOCAB, max_length=T,
                                         dropout=0.0, remat="dots"))
    m.initialize()
    tr = parallel.SPMDTrainer(
        m, forward_loss=B.pretraining_loss, optimizer="lamb",
        optimizer_params={"learning_rate": 1e-3}, mesh=_mesh())
    rng = np.random.RandomState(0)

    def batch(n=rows):
        return (rng.randint(0, VOCAB, (n, T)).astype(np.int32),
                np.zeros((n, T), np.int32), np.full((n,), T, np.int32),
                rng.randint(0, T, (n, 5)).astype(np.int32),
                rng.randint(0, VOCAB, (n, 5)).astype(np.int32),
                np.ones((n, 5), np.float32),
                rng.randint(0, 2, (n,)).astype(np.int32))
    return tr, batch


@pytest.fixture(scope="module", params=["bert", "gpt"])
def stepped(request):
    """A tiny trainer after two steps, with its scope table."""
    tr, batch = (_bert if request.param == "bert" else _gpt)()
    tr.step(*batch())
    tr.step(*batch())
    return tr, batch, tr.scope_table()


# ------------------------------------------------------------- scopes

def test_scope_table_names_every_part_in_both_directions(stepped):
    _, _, table = stepped
    have = set(table.values())
    for part in ("mx.embed", "mx.attn", "mx.ffn", "mx.norm", "mx.head",
                 "mx.loss"):
        assert (part, "fwd") in have, part
        assert (part, "bwd") in have, part
    assert ("mx.optimizer", "") in have and ("mx.guard", "") in have
    assert {s for s, _ in have} <= {
        "", "mx.embed", "mx.attn", "mx.ffn", "mx.norm", "mx.head",
        "mx.loss", "mx.optimizer", "mx.guard"}
    # no direction without a scope
    assert all(d == "" for s, d in have if s == "")


def test_scope_table_covers_the_step(stepped):
    """Of the entry computation's instructions that carry an op_name at all
    (the compiler's own copies and rewrites carry none), at least nine in
    ten lie in an ``mx.`` scope."""
    tr, _, table = stepped
    text = tr.compiled_step_text()
    entry = text[text.index("\nENTRY "):]
    named = scoped = 0
    for line in entry.splitlines()[1:]:
        m = profiler._HLO_NAME.match(line)
        if m is None or 'op_name="' not in line or \
                re.search(r"\s(parameter|constant|tuple|get-tuple-element|"
                          r"bitcast)\(", line):
            continue
        named += 1
        scoped += table[m.group(1)][0] != ""
    assert named > 100
    assert scoped / named >= 0.9, (scoped, named)


def test_scope_table_parser_on_plain_text():
    text = "\n".join([
        'ENTRY %main (p: f32[2]) -> f32[2] {',
        '  %p = f32[2]{0} parameter(0)',
        '  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%f, '
        'metadata={op_name="jit(step)/jvp(mx.loss)/mx.ffn/dot_general"}',
        '  %fusion.2 = f32[2]{0} fusion(%p), kind=kLoop, calls=%f, metadata='
        '{op_name="jit(step)/transpose(jvp(mx.loss))/mx.ffn/dot_general"}',
        '  %fusion.3 = (f32[2]{0}, f32[]) fusion(%p), kind=kInput, '
        'calls=%f, metadata={op_name="jit(step)/mx.guard/reduce_and"}',
        '  ROOT %copy.4 = f32[2]{0} copy(%fusion.1)',
        '}'])
    assert profiler.scope_table(text) == {
        "p": ("", ""), "fusion.1": ("mx.ffn", "fwd"),
        "fusion.2": ("mx.ffn", "bwd"), "fusion.3": ("mx.guard", ""),
        "copy.4": ("", "")}


def test_compiled_step_text_traces_nothing_again(stepped):
    """The abstract call keeps its arrays' shardings, so lowering it again
    finds the step's own jaxpr in JAX's caches: table and trace name the
    executable that ran, and no Python body runs twice."""
    tr, _, _ = stepped
    first = tr.flight.events("trainer")[0]
    traced_then = sum(
        e["dur_s"] for e in profiler.compile_events()
        if e["kind"] == "trace"
        and first.ts <= e["ts"] <= first.ts + first.data["dur_s"])
    n = len(profiler.compile_events())
    tr.compiled_step_text()
    traced_now = sum(e["dur_s"] for e in profiler.compile_events()[n:]
                     if e["kind"] == "trace")
    assert traced_now < 0.02 * traced_then, (traced_now, traced_then)
    assert tr.step_trace_count == 1


# -------------------------------------------------------- host phases

def test_train_step_event_carries_the_span_and_its_phases(stepped):
    tr, batch, _ = stepped
    t_before = time.perf_counter()
    tr.step(*batch())
    t_after = time.perf_counter()
    evs = tr.flight.events("trainer", EventType.TRAIN_STEP)
    ev = evs[-1]
    d = ev.to_dict()
    validate_event_dict(d)
    data = d["data"]
    assert data["outcome"] == "APPLIED" and data["step"] == len(evs)
    # ts is the step's START, on the clock the caller reads
    assert t_before <= d["ts"] <= d["ts"] + data["dur_s"] <= t_after
    assert all(data[p] >= 0.0 for p in PHASES)
    assert sum(data[p] for p in PHASES) <= data["dur_s"] + 1e-9
    # the first step of this trainer held the trace and the compile
    first = evs[0].data
    assert first["dispatch_s"] > 10 * data["dispatch_s"]
    # and the export draws it as a span with its four phases beneath
    trace = to_perfetto([e.to_dict() for e in evs])
    validate_trace(trace)
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sum(e["name"].startswith("step ") for e in spans) == len(evs)
    assert {e["name"] for e in spans if e["tid"] == "phases"} == \
        {p[:-2] for p in PHASES}


def test_accumulated_round_is_one_span():
    tr, batch = _gpt()
    tr.step_microbatches([batch(), batch(), batch()])
    tr.step_microbatches([batch()])
    evs = tr.flight.events("trainer", EventType.TRAIN_STEP)
    assert len(evs) == 2
    for ev in evs:
        data = ev.data
        assert sum(data[p] for p in PHASES) <= data["dur_s"] + 1e-9
        assert data["dur_s"] > 0 and data["flag_wait_s"] >= 0


def test_skipped_step_carries_the_span_too():
    tr, batch = _gpt()
    ids, labels = batch()
    tr.step(ids, labels)
    for p in tr._params:                 # poison the weights: NaN grads
        if p.grad_req != "null":
            p._data._data = p._data._data * np.float32("nan")
            break
    tr.step(ids, labels)
    ev = tr.flight.events("trainer", EventType.TRAIN_STEP)[-1]
    assert ev.data["outcome"] == "SKIPPED_NONFINITE"
    assert ev.data["dur_s"] > 0 and "flag_wait_s" in ev.data


def test_gluon_trainer_step_stays_an_instant():
    """``StepRecorder.record`` without a span emits what it always did."""
    from incubator_mxnet_tpu.train.outcomes import StepOutcome, StepRecorder
    rec = StepRecorder()
    rec.open_step()
    rec.record(StepOutcome.APPLIED)
    (ev,) = rec.flight.events("trainer")
    assert set(ev.data) == {"step", "outcome", "detail"}
    rec.open_step()
    rec.record(StepOutcome.APPLIED, span=(12.5, 0.4, 0.1, 0.1, 0.05, 0.15))
    ev = rec.flight.events("trainer")[-1]
    assert ev.ts == 12.5 and ev.data["dur_s"] == 0.4
    assert [ev.data[p] for p in PHASES] == [0.1, 0.1, 0.05, 0.15]


# ---------------------------------------------------- profiler session

def test_nothing_is_written_with_no_session_live(stepped):
    tr, batch, _ = stepped
    assert not profiler.session_live() and not profiler.is_running()
    n_events, table = len(profiler._events), profiler.dumps()
    tr.step(*batch())
    with profiler.scope("mx.test"):
        pass
    assert len(profiler._events) == n_events
    assert profiler.dumps() == table


def test_live_session_shows_the_phases_beside_the_ops(tmp_path, stepped):
    """Whoever started the session: the trainer's phases are annotations
    in its trace, and ``profiler.scope`` on the host records."""
    from jax.profiler import ProfileData
    tr, batch, _ = stepped
    n_events = len(profiler._events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert profiler.session_live() and profiler.is_running()
        tr.step(*batch())
        tr.step(*batch())
        with profiler.scope("mx.test"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert not profiler.is_running()
    assert [e["name"] for e in profiler._events[n_events:]] == ["mx.test"]
    del profiler._events[n_events:]
    profiler._agg.pop("mx.test", None)
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    names = {}
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("mx.trainer."):
                    names.setdefault(ev.name, []).append(
                        (ev.start_ns, ev.start_ns + ev.duration_ns))
    assert set(names) == {"mx.trainer.step", "mx.trainer.prepare",
                          "mx.trainer.dispatch", "mx.trainer.bind",
                          "mx.trainer.flag_wait"}
    assert all(len(v) == 2 for v in names.values())
    # each phase lies inside its step's span
    for phase in ("prepare", "dispatch", "bind", "flag_wait"):
        for (s, e), (S, E) in zip(sorted(names["mx.trainer." + phase]),
                                  sorted(names["mx.trainer.step"])):
            assert S <= s <= e <= E


# ------------------------------------------------------ compile events

def test_compile_events_follow_the_shapes():
    tr, batch = _gpt(remat=False)
    tr.step(*batch())
    first = tr.flight.events("trainer")[-1]
    evs = profiler.compile_events()
    inside = [e for e in evs
              if first.ts <= e["ts"] <= first.ts + first.data["dur_s"]]
    assert {"trace", "lower", "backend_compile"} <= \
        {e["kind"] for e in inside}
    assert all(e["dur_s"] >= 0 and e["ts"] > 0 for e in evs)
    # the same shapes again: nothing compiles, traces or lowers
    n = len(profiler.compile_events())
    counts = profiler.compile_counts()
    tr.step(*batch())
    assert len(profiler.compile_events()) == n
    assert profiler.compile_counts() == counts
    # another batch shape: the step compiles once more
    tr.step(*batch(8))
    new = profiler.compile_events()[n:]
    assert sum(e["kind"] == "backend_compile" for e in new) >= 1
    assert tr.step_trace_count == 2
    snap = tr.health_snapshot()["compile_events"]
    assert snap == profiler.compile_counts()
    assert snap["backend_compile"] > counts["backend_compile"]


def test_compile_event_list_is_bounded():
    assert profiler._compile_events.maxlen == profiler._COMPILE_RING
    n = profiler.compile_counts().get("cache_hit", 0)
    profiler._on_event("/jax/compilation_cache/cache_hits")
    profiler._on_event("/jax/some/other/event")
    profiler._on_duration("/jax/some/other/duration", 1.0)
    assert profiler.compile_counts()["cache_hit"] == n + 1
    last = profiler.compile_events()[-1]
    assert last["kind"] == "cache_hit" and last["dur_s"] == 0.0


# ------------------------------------------------------- live trainers

def test_live_trainers_forgets_a_dropped_trainer():
    tr, batch = _gpt()
    tr.step(*batch())
    assert any(t is tr for t in parallel.live_trainers())
    assert tr.flight is tr._recorder.flight
    ident = id(tr)
    del tr, batch
    gc.collect()            # the compiled step's closure holds a cycle
    assert all(id(t) != ident for t in parallel.live_trainers())


def test_scope_table_over_a_mesh():
    """dp=4: the batch comes from the host uncommitted, parameters are
    committed to the mesh; the abstract call must lower all the same, and
    without tracing again."""
    tr, batch = _gpt(rows=8, mesh=_mesh(4))
    tr.step(*batch())
    n = len(profiler.compile_events())
    table = tr.scope_table()
    assert ("mx.attn", "bwd") in set(table.values())
    assert sum(e["dur_s"] for e in profiler.compile_events()[n:]
               if e["kind"] == "trace") < 0.05
    assert tr in parallel.live_trainers()

"""Self-check of the attribution (``harness/attribute.py``), on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_attribute.py -q

The clock alignment, the cut of a gap by host phase, the join of device time
with the scope table and the union of nested compile events, first on small
hand-made data with known answers, then on ``fixtures/
train_1chip_scoped.json.gz``: a trace kept on the v5e with ``--keep-trace``,
with that run's program spans, scope-table rows and compile events merged in
(``attribute.save_fixture``). A planted case, a table with no scopes, has to
read 100% unattributed, and a program that says nothing of itself has to
leave every reader silent, not raise.
"""

import contextlib
import io
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from harness import attribute as A, manifest, trace  # noqa: E402

FIXTURE = os.path.join(BENCH, "fixtures", "train_1chip_scoped.json.gz")
REHEARSAL = os.path.join(BENCH, "tests", "rehearsal",
                         "manifest_scoped.json")
NEW = sorted(list(A.SCOPE_SHARES) + list(A.GAP_SHARES) + [
    "scope_unattributed_share", "trainer_step_ms_p50", "host_prepare_ms_p50",
    "host_dispatch_ms_p50", "flag_wait_ms_p50", "step_gap_ms_p50",
    "backend_compiles_in_window.train"])
FIRST = ["first_step_trace_s", "first_step_lower_s",
         "first_step_compile_or_load_s"]


def span(ts, prepare, dispatch, bind, flag_wait, step=2):
    return {"ts": ts, "step": step, "prepare_s": prepare,
            "dispatch_s": dispatch, "bind_s": bind, "flag_wait_s": flag_wait,
            "dur_s": prepare + dispatch + bind + flag_wait}


# --------------------------------------------------------------- by hand

def test_split_takes_the_traced_stretch_from_the_end():
    steps = [span(float(i), .1, .1, .1, .1, step=i + 1) for i in range(10)]
    window, traced = A.split(steps, 4, 3)
    assert [s["step"] for s in window] == [4, 5, 6, 7]
    assert [s["step"] for s in traced] == [8, 9, 10]
    assert A.split(steps, 8, 3) == (None, None)     # the ring lost some
    assert A.split(steps, 0, 3) == (None, None)


def test_alignment_recovers_a_known_offset():
    # host clock 100.0 s is trace clock 5_000_000 ns; the harness's span
    # starts 4 to 6 us before the trainer's and ends 1.2 to 1.8 ms after it
    off = 5_000_000 - 100.0 * 1e9
    traced = [span(100.0 + 0.5 * k, .004, .002, .001, .4) for k in range(3)]
    bench = [(A.STEP_SPAN, s["ts"] * 1e9 + off - 4_000 - 1_000 * k,
              (s["ts"] + s["dur_s"]) * 1e9 + off + 1_200_000 + 300_000 * k)
             for k, s in enumerate(traced)]
    offsets, err_s = A.align(traced, bench)
    assert err_s == pytest.approx(2e-6, rel=1e-3)      # 6 us less 4 us
    for got in offsets:
        assert 4_000 - 200 <= off - got <= 6_000 + 200
    assert A.align(traced, bench[:2]) == (None, None)
    assert A.align([], []) == (None, None)
    # the device runs each step from 1 ms into its dispatch to 2 ms before
    # its flag read ends: both margins come out positive; an offset a
    # millisecond and a half late turns the first negative
    runs = [((s["ts"] + .005) * 1e9 + off, (s["ts"] + .405) * 1e9 + off)
            for s in traced]
    start, end = A.causality(runs, traced, offsets)
    assert start == pytest.approx(1e-3, abs=1e-5)
    assert end == pytest.approx(2e-3, abs=1e-5)
    late = [o + 1.5e6 for o in offsets]
    assert A.causality(runs, traced, late)[0] < 0
    assert A.causality(runs[:2], traced, offsets) is None


def test_gap_is_cut_at_the_phase_boundaries():
    # step A ends on the device at t=1.000 s; the host's flag read returns
    # at 1.003, the next trainer step starts at 1.004, prepares until
    # 1.010, dispatches until 1.012, and the device starts at 1.0125
    a = span(0.590, .006, .002, .001, 1.003 - 0.599)
    b = span(1.004, .006, .002, .001, .4)
    runs = [(int(0.6e9), int(1.0e9)), (int(1.0125e9), int(1.4e9))]
    gaps, cut = A.cut_gaps(runs, [a, b], [0.0, 0.0])
    assert gaps == [pytest.approx(12.5e6)]
    assert cut["flag_wait"] == pytest.approx(3e6, rel=1e-6)
    assert cut["outside"] == pytest.approx(1e6, rel=1e-6)
    assert cut["prepare"] == pytest.approx(6e6, rel=1e-6)
    assert cut["dispatch"] == pytest.approx(2e6, rel=1e-6)
    assert cut["other"] == pytest.approx(0.5e6, rel=1e-3)
    assert sum(cut.values()) == pytest.approx(sum(gaps))
    assert A.cut_gaps(runs, [a], [0.0]) == (None, None)
    assert A.cut_gaps(runs, [a, b], None) == (None, None)


def test_step_runs_are_those_of_the_heaviest_program():
    modules = {0: [("jit__threefry_split(1)", 5, 8), ("jit_step(7)", 10, 500),
                   ("jit_step(7)", 520, 1000), ("jit__unstack(2)", 505, 506)],
               1: [("jit_step(7)", 11, 501)]}
    assert A.step_runs(modules) == [(10, 500), (520, 1000)]
    assert A.step_runs({}) == []


DEVICES = {0: [("fusion.1", 0, 600), ("fusion.2", 600, 900),
               ("copy.3", 900, 950), ("all-reduce.4", 950, 1000)]}
TABLE = {"fusion.1": ("mx.ffn", "fwd"), "fusion.2": ("mx.guard", ""),
         "copy.3": ("", ""), "fusion.9": ("mx.attn", "bwd")}


def test_device_time_joins_the_scope_table():
    secs, ops = A.by_scope(DEVICES, TABLE)
    assert secs == {("mx.ffn", "fwd"): pytest.approx(600e-9),
                    ("mx.guard", ""): pytest.approx(300e-9),
                    ("", ""): pytest.approx(100e-9)}     # copy + all-reduce
    assert ops["fusion"] == {("mx.ffn", "fwd"): pytest.approx(600e-9),
                             ("mx.guard", ""): pytest.approx(300e-9)}
    assert ops["all-reduce"] == {("", ""): pytest.approx(50e-9)}
    # two devices: averaged
    two, _ = A.by_scope({0: DEVICES[0], 1: [("fusion.1", 0, 200)]}, TABLE)
    assert two[("mx.ffn", "fwd")] == pytest.approx(400e-9)


def said(table, steps=(), events=()):
    return {"steps": list(steps), "scope_table": table,
            "compile_events": list(events)}


def reduced(devices=DEVICES, modules=None, host=()):
    raw = {"planes": [{"name": f"/device:TPU:{d}", "lines": [
        {"name": "XLA Ops", "events": [[n, s, e - s] for n, s, e in evs]},
        {"name": "XLA Modules", "events": [
            [n, s, e - s] for n, s, e in (modules or {}).get(d, [])]}]}
        for d, evs in devices.items()]
        + [{"name": "/host:CPU", "lines": [{"name": "main", "events": [
            [n, s, e - s] for n, s, e in host]}]}]}
    return trace.Reduced(raw, window_s=1.0)


@pytest.mark.parametrize("table", [{}, {n: ["", ""] for n in TABLE}],
                         ids=["empty-table", "table-without-scopes"])
def test_planted_table_with_no_scopes_reads_all_unattributed(table):
    """An executable that a cache kept from before the scopes existed: every
    share of a part reads 0 and the unattributed share 100, not None and not
    a share of zero."""
    out, _ = A.build(said(table), 0, 0, reduced())
    assert out["scope_unattributed_share"] == pytest.approx(100.0)
    assert all(out[m] == 0.0 for m in A.SCOPE_SHARES)


def test_no_table_at_all_leaves_the_shares_out():
    out, _ = A.build(said(None), 0, 0, reduced())
    assert "scope_unattributed_share" not in out
    assert not any(m in out for m in A.SCOPE_SHARES)


def test_shares_sum_to_a_hundred():
    out, detail = A.build(said({k: list(v) for k, v in TABLE.items()}),
                          0, 0, reduced())
    assert out["ffn_busy_share"] == pytest.approx(60.0)
    assert out["guard_busy_share"] == pytest.approx(30.0)
    assert out["scope_unattributed_share"] == pytest.approx(10.0)
    assert sum(out[m] for m in list(A.SCOPE_SHARES)
               + ["scope_unattributed_share"]) == pytest.approx(100.0)
    assert detail["scope_seconds"][("mx.ffn", "fwd")] == pytest.approx(6e-7)


def test_nested_compile_events_are_counted_once():
    events = [{"ts": 10.2, "kind": "trace", "dur_s": 0.1},    # inner jit
              {"ts": 10.5, "kind": "trace", "dur_s": 0.2},    # inner jit
              {"ts": 11.0, "kind": "trace", "dur_s": 1.0},    # the step
              {"ts": 11.4, "kind": "lower", "dur_s": 0.4},
              {"ts": 11.5, "kind": "cache_hit", "dur_s": 0.0},
              {"ts": 12.4, "kind": "backend_compile", "dur_s": 1.0},
              {"ts": 20.0, "kind": "backend_compile", "dur_s": 0.5},
              {"ts": 20.0, "kind": "cache_miss", "dur_s": 0.0}]
    assert A.compile_seconds(events, "trace", 10.0, 13.0) == \
        pytest.approx(1.0)
    assert A.compile_seconds(events, "backend_compile", 10.0, 13.0) == \
        pytest.approx(1.0)
    assert A.compiles_between(events, 10.0, 13.0) == 1
    assert A.compiles_between(events, 13.0, 25.0) == 2
    assert A.compiles_between(events, 13.0, 19.0) == 0
    first = span(10.0, .1, 2.5, .01, .3, step=1)
    steps = [first] + [span(14.0 + k, .01, .01, .01, .5, step=k + 2)
                       for k in range(4)]
    out, detail = A.build(said(None, steps, events), 3, 1, None)
    assert out["first_step_trace_s"] == pytest.approx(1.0)
    assert out["first_step_lower_s"] == pytest.approx(0.4)
    assert out["first_step_compile_or_load_s"] == pytest.approx(1.0)
    assert sum(out[m] for m in FIRST) <= first["dur_s"]
    assert detail["first_step_cache"]["cache_hit"] == 1
    assert out["backend_compiles_in_window.train"] == 0
    assert out["trainer_step_ms_p50"] == pytest.approx(530.0)
    assert out["flag_wait_ms_p50"] == pytest.approx(500.0)
    assert detail["longest_window_step"]["step"] in (2, 3, 4)


# ------------------------------------------------------- recorded on chip

@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.skip("train_1chip_scoped.json.gz was not recorded")
    raw = trace.load_fixture(FIXTURE)
    return raw, trace.Reduced(raw, window_s=5.0)


def test_recorded_clock_alignment(recorded):
    raw, red = recorded
    _, traced = A.split(raw["steps"], raw["n_window"], raw["n_traced"])
    bench = [e for e in red.host if e[0] == A.STEP_SPAN]
    assert len(traced) == len(bench) == raw["n_traced"] > 2
    offsets, err_s = A.align(traced, bench)
    assert 0 < err_s < 0.2e-3                       # under 0.2 ms
    # every trainer span lies inside its harness span once aligned, and the
    # device runs each step between its dispatch's start and its flag
    # read's end
    for step, off, (_, s, e) in zip(traced, offsets, bench):
        t = A.boundaries(step)
        assert s - 1 <= t[0] * 1e9 + off and t[-1] * 1e9 + off <= e + 1
    start, end = A.causality(A.step_runs(red.modules), traced, offsets)
    assert 0 < start < 5e-3 and 0 < end < 10e-3
    # anchored on the spans' ends the offsets would scatter far more: what
    # the harness's span holds beyond the trainer's varies step by step
    ends = [e - A.boundaries(st)[-1] * 1e9
            for st, (_, _, e) in zip(traced, bench)]
    assert (max(ends) - min(ends)) / 1e9 > 10 * err_s


def test_recorded_gaps_are_booked_to_host_phases(recorded):
    raw, red = recorded
    out, detail = A.build(raw, raw["n_window"], raw["n_traced"], red)
    shares = [out[m] for m in A.GAP_SHARES]
    assert all(0.0 <= s <= 100.0 for s in shares)
    assert 98.0 <= sum(shares) <= 100.0 + 1e-6
    runs = A.step_runs(red.modules)
    assert len(runs) == raw["n_traced"]
    assert 0 < out["step_gap_ms_p50"] < out["trainer_step_ms_p50"]
    assert sum(detail["gap_seconds"].values()) == pytest.approx(
        sum(b[0] - a[1] for a, b in zip(runs, runs[1:])) / 1e9)


def test_recorded_device_time_by_scope(recorded):
    raw, red = recorded
    out, detail = A.build(raw, raw["n_window"], raw["n_traced"], red)
    parts = list(A.SCOPE_SHARES) + ["scope_unattributed_share"]
    assert sum(out[m] for m in parts) == pytest.approx(100.0, abs=1e-6)
    assert out["scope_unattributed_share"] < 10.0
    assert all(out[m] > 0 for m in A.SCOPE_SHARES)
    # both directions of every block part took device time
    secs = detail["scope_seconds"]
    for part in ("mx.attn", "mx.ffn", "mx.norm"):
        assert secs[(part, "fwd")] > 0 and secs[(part, "bwd")] > 0
    # the flash kernels are attention's, whatever the table is asked
    flash = detail["ops"]["mxtpu_flash_dense_fwd"]
    assert set(sc for sc, _ in flash) == {"mx.attn"}
    # the same trace with its table's scopes struck out: all unattributed
    planted = dict(raw, scope_table={n: ["", ""] for n in raw["scope_table"]})
    out2, _ = A.build(planted, raw["n_window"], raw["n_traced"], red)
    assert out2["scope_unattributed_share"] == pytest.approx(100.0)


def test_recorded_first_step_and_window(recorded):
    raw, red = recorded
    out, detail = A.build(raw, raw["n_window"], raw["n_traced"], red)
    first = detail["first_step"]
    assert first["step"] == 1
    assert all(out[m] > 0 for m in FIRST)
    assert sum(out[m] for m in FIRST) <= first["dur_s"]
    assert out["backend_compiles_in_window.train"] == 0
    assert out["host_prepare_ms_p50"] + out["host_dispatch_ms_p50"] \
        + out["flag_wait_ms_p50"] <= out["trainer_step_ms_p50"] * 1.001


# ------------------------------------------------------- files and runs

def test_every_new_metric_has_its_entry_and_reader():
    m = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    entries = {x["name"]: x for x in m["per_layer"]}
    cells = [w["name"] for w in m["workloads"]]
    for name in NEW:
        assert entries[name]["workloads"] == cells
        assert entries[name]["moves"] == "train_tok_s_chip"
        assert entries[name]["better"] == "lower"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))
    reh = {x["name"]: x for x in manifest.load_json(REHEARSAL)["per_layer"]}
    for name in NEW + FIRST:
        assert name in reh
    # the three that move setup_s wait for a benchmark PR (PERF.md, open
    # questions); their readers are here and rehearsed
    for name in FIRST:
        assert name not in entries and reh[name]["moves"] == "setup_s"
        assert os.path.exists(os.path.join(BENCH, "metrics", name + ".py"))


def test_a_program_that_says_nothing_leaves_the_readers_silent(monkeypatch):
    """The commit before ``live_trainers`` existed: the adapter returns
    None, and every reader returns None and does not raise."""
    from incubator_mxnet_tpu import parallel
    spans = manifest.load_module(os.path.join(BENCH, "adapters",
                                              "spans_mxtpu.py"))
    monkeypatch.delattr(parallel, "live_trainers")
    assert spans.collect() is None
    cell = manifest.Cell(REHEARSAL, "tiny-mlm")
    ctx = {"kind": "train", "window": {"steps": 3}, "trace": None}
    for name in NEW + FIRST:
        assert cell.reader(name)(ctx) is None
    assert ctx["_attribution"] == {}
    assert cell.reader(NEW[0])({"kind": "serve_open_loop"}) is None


def test_rehearsed_traced_run_reads_the_program_spans():
    """One traced run at the rehearsal size: the host-phase metrics and the
    first step's split print, the inside twin agrees with the outside span,
    and the device's share metrics stay out (the CPU has no device plane)."""
    run = manifest.load_module(os.path.join(BENCH, "run.py"), "bm_run_attr")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "tiny-mlm", "--seed", "77", "--seconds",
                       "0.5", "--trace", "1", "--rehearsal", "--manifest",
                       REHEARSAL])
    assert rc == 0
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in ("trainer_step_ms_p50", "host_prepare_ms_p50",
                 "host_dispatch_ms_p50", "flag_wait_ms_p50",
                 "backend_compiles_in_window.train", *FIRST):
        assert name in got, name
    assert not any(m in got for m in A.SCOPE_SHARES)
    assert 0 < got["trainer_step_ms_p50"] <= got["step_ms_p50"] * 1.02
    assert got["backend_compiles_in_window.train"] == 0
    assert got["compiles_in_window.train"] == 0
    side = manifest.load_json(A.SIDE_FILE)
    assert side["readings"]["trainer_step_ms_p50"] == \
        got["trainer_step_ms_p50"]
    assert len(side["steps"]) >= res["attempted"]

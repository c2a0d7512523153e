"""Where a training step's time goes, from what the program says of itself.

Three joins between the profiler's trace (``trace.Reduced``) and what
``adapters/spans_mxtpu.py`` fetched from the live trainer:

1. *Clock.* The trainer stamps ``time.perf_counter()``; the trace is on the
   device's clock. The k-th ``TRAIN_STEP`` span of the traced stretch lies
   inside the k-th ``bench.train_step`` span of the trace (the harness draws
   it around the very call), and starts a few microseconds of Python after
   it (one call and a short list), while the harness span ends a millisecond
   or more after the trainer's (it waits on the loss once more). So the
   offset is taken step by step, start on start. Both clocks are the host's
   own, so there is one true offset: the spread of the per-step offsets is
   reported as the error bound, with two checks that no offset can pass by
   luck: on the aligned clock the device starts each step after its
   ``dispatch`` began and ends it before its ``flag_wait`` ended.
2. *Gaps.* Step boundaries on device 0 are the runs of the heaviest program
   on its ``XLA Modules`` line. Each gap between two runs is cut at the host
   phases' boundaries on the aligned clock and booked to the step's
   ``flag_wait``, to the time outside the trainer (from the end of one step's
   ``flag_wait`` to the next step's start: the outcome record, the return,
   the harness's feed), to the next step's ``prepare`` and to its ``dispatch``.
3. *Scopes.* Device seconds by ``mx.`` scope and direction: the trace names
   its events by HLO instruction, and the scope table maps instruction to
   scope. Time of an instruction that has no scope, or that the table does
   not hold, is unattributed.

Everything here works on plain lists and dicts, so the fixture under
``fixtures/`` (a trace kept with ``--keep-trace``, with the program's spans
and the part of the scope table that the trace names merged in by
``save_fixture``) checks it in the sandbox.
"""

import gzip
import json
import os
import statistics

from . import manifest, trace as T
from .device import log

STEP_SPAN = "bench.train_step"
PHASES = ("prepare_s", "dispatch_s", "bind_s", "flag_wait_s")
# per-layer metric -> the scopes whose device time it is a share of
SCOPE_SHARES = {
    "attn_busy_share": ("mx.attn",),
    "ffn_busy_share": ("mx.ffn",),
    "norm_busy_share": ("mx.norm",),
    "embed_head_loss_busy_share": ("mx.embed", "mx.head", "mx.loss"),
    "optimizer_busy_share": ("mx.optimizer",),
    "guard_busy_share": ("mx.guard",),
}
GAP_SHARES = {"gap_in_flag_wait_share": "flag_wait",
              "gap_outside_trainer_share": "outside",
              "gap_in_prepare_share": "prepare",
              "gap_in_dispatch_share": "dispatch"}
SIDE_FILE = os.path.join(manifest.ROOT, "benchmark_out", "attribution.json")


# ------------------------------------------------------------------ spans

def split(steps, n_window, n_traced):
    """(window's spans, traced stretch's spans): no step runs between the
    two, so the last ``n_traced`` are the traced stretch and the
    ``n_window`` before them the window. (None, None) where the recorder's
    ring no longer holds that many."""
    if n_window <= 0 or len(steps) < n_window + n_traced:
        return None, None
    cut = len(steps) - n_traced
    return steps[cut - n_window:cut], steps[cut:]


def boundaries(step):
    """A span's five stamps, in seconds on the host's clock."""
    t = [step["ts"]]
    for phase in PHASES:
        t.append(t[-1] + step[phase])
    return t


def align(traced, bench):
    """Offsets (ns, one a step: trace clock less host clock, start on
    start) and the error bound in seconds: the spread of those offsets,
    which would all be equal if nothing lay between the two starts. None
    where the two do not pair up."""
    if not traced or len(traced) != len(bench):
        return None, None
    offsets = [s - step["ts"] * 1e9 for step, (_, s, _) in zip(traced, bench)]
    return offsets, (max(offsets) - min(offsets)) / 1e9


def causality(runs, traced, offsets):
    """On the aligned clock, the least time from a step's ``dispatch``
    beginning to the device starting it, and from the device ending it to
    its ``flag_wait`` ending, in seconds: a wrong offset makes one of them
    negative. None where runs and spans do not pair up."""
    if offsets is None or len(runs) != len(traced):
        return None
    starts, ends = [], []
    for (m0, m1), step, off in zip(runs, traced, offsets):
        t = [x * 1e9 + off for x in boundaries(step)]
        starts.append(m0 - t[1])
        ends.append(t[4] - m1)
    return min(starts) / 1e9, min(ends) / 1e9


# ------------------------------------------------------------------- gaps

def step_runs(modules):
    """[(start, end)] of the program that took most of device 0's time."""
    if not modules:
        return []
    evs = modules[min(modules)]
    by_name = {}
    for n, s, e in evs:
        by_name.setdefault(n.split("(", 1)[0], []).append((s, e))
    runs = max(by_name.values(), key=lambda iv: sum(e - s for s, e in iv))
    return sorted(runs)


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def cut_gaps(runs, traced, offsets):
    """Gaps between consecutive runs of the step on device 0, each cut by
    host phase. Returns ``([gap ns, ...], {class: ns})`` with the classes
    of ``GAP_SHARES`` and ``other`` (the device stood still while the host
    was still binding the finished step's outputs, or after the next
    dispatch had returned)."""
    if offsets is None or len(runs) != len(traced):
        return None, None
    gaps, acc = [], dict.fromkeys(
        ("flag_wait", "outside", "prepare", "dispatch", "other"), 0.0)
    for k in range(len(runs) - 1):
        g0, g1 = runs[k][1], runs[k + 1][0]
        if g1 <= g0:
            continue
        a = [t * 1e9 + offsets[k] for t in boundaries(traced[k])]
        b = [t * 1e9 + offsets[k + 1] for t in boundaries(traced[k + 1])]
        parts = {"flag_wait": _overlap(g0, g1, a[3], a[4]),
                 "outside": _overlap(g0, g1, a[4], b[0]),
                 "prepare": _overlap(g0, g1, b[0], b[1]),
                 "dispatch": _overlap(g0, g1, b[1], b[2])}
        parts["other"] = max(0.0, (g1 - g0) - sum(parts.values()))
        for name, ns in parts.items():
            acc[name] += ns
        gaps.append(g1 - g0)
    return gaps, acc


# ----------------------------------------------------------------- scopes

def by_scope(devices, table):
    """``({(scope, direction): seconds}, {op base name: {(scope,
    direction): seconds}})`` averaged over the devices. Scope ``""`` holds
    the time of instructions that have no scope or are not in the table."""
    acc, ops = {}, {}
    for evs in devices.values():
        for name, s, e in evs:
            scope, direction = table.get(name, ("", ""))
            key = (scope, direction if scope else "")
            acc[key] = acc.get(key, 0.0) + (e - s)
            op = ops.setdefault(T.base_name(name), {})
            op[key] = op.get(key, 0.0) + (e - s)
    k = max(1, len(devices)) * 1e9
    return ({key: v / k for key, v in acc.items()},
            {op: {key: v / k for key, v in d.items()}
             for op, d in ops.items()})


# ---------------------------------------------------------------- compile

def compile_seconds(events, kind, lo, hi):
    """Seconds covered by the ``kind`` events that ended in [lo, hi]: their
    union, since an inner jit's trace reports itself inside the outer
    one's."""
    spans = [(e["ts"] - e["dur_s"], e["ts"]) for e in events
             if e["kind"] == kind and lo <= e["ts"] <= hi]
    return T.total(T.union(spans))


def compiles_between(events, lo, hi):
    return sum(1 for e in events if lo <= e["ts"] <= hi
               and e["kind"] in ("backend_compile", "cache_miss"))


# ------------------------------------------------------------------ whole

def build(said, n_window, n_traced, reduced):
    """Every reading that can be had, by metric name, and the detail behind
    them. ``said`` is what ``spans_mxtpu.collect`` returned."""
    out, detail = {}, {}
    steps = said["steps"]
    events = said["compile_events"]
    window, traced = split(steps, n_window, n_traced)
    if window:
        ms = {p: [s[p] * 1e3 for s in window] for p in PHASES + ("dur_s",)}
        out["trainer_step_ms_p50"] = statistics.median(ms["dur_s"])
        out["host_prepare_ms_p50"] = statistics.median(ms["prepare_s"])
        out["host_dispatch_ms_p50"] = statistics.median(ms["dispatch_s"])
        out["flag_wait_ms_p50"] = statistics.median(ms["flag_wait_s"])
        lo, hi = window[0]["ts"], window[-1]["ts"] + window[-1]["dur_s"]
        out["backend_compiles_in_window.train"] = \
            compiles_between(events, lo, hi)
        detail["longest_window_step"] = max(window, key=lambda s: s["dur_s"])
    first = steps[0] if steps and steps[0]["step"] == 1 else None
    if first is not None:
        lo, hi = first["ts"], first["ts"] + first["dur_s"]
        for name, kind in (("first_step_trace_s", "trace"),
                           ("first_step_lower_s", "lower"),
                           ("first_step_compile_or_load_s",
                            "backend_compile")):
            out[name] = compile_seconds(events, kind, lo, hi)
        detail["first_step"] = first
        detail["first_step_cache"] = {
            k: sum(1 for e in events if e["kind"] == k and lo <= e["ts"] <= hi)
            for k in ("cache_hit", "cache_miss")}
        detail["first_step_cache"]["retrieval_s"] = compile_seconds(
            events, "cache_retrieval", lo, hi)
    if reduced is None or not reduced.devices:
        return out, detail
    table = said["scope_table"]
    if table is not None:
        table = {k: tuple(v) for k, v in table.items()}
        secs, ops = by_scope(reduced.devices, table)
        total = sum(secs.values())
        detail["scope_seconds"] = secs
        detail["ops"] = ops
        if total > 0:
            for metric, scopes in SCOPE_SHARES.items():
                out[metric] = 100.0 * sum(
                    v for (sc, _), v in secs.items() if sc in scopes) / total
            out["scope_unattributed_share"] = \
                100.0 * secs.get(("", ""), 0.0) / total
    if traced:
        bench = [e for e in reduced.host if e[0] == STEP_SPAN]
        offsets, err_s = align(traced, bench)
        runs = step_runs(reduced.modules)
        gaps, cut = cut_gaps(runs, traced, offsets)
        detail["clock_error_bound_s"] = err_s
        detail["clock_causality_s"] = causality(runs, traced, offsets)
        if offsets:
            # what the harness's span holds beyond the trainer's own
            detail["harness_beyond_trainer_ms_p50"] = statistics.median(
                (e - s) / 1e6 - step["dur_s"] * 1e3
                for step, (_, s, e) in zip(traced, bench))
        if gaps:
            out["step_gap_ms_p50"] = statistics.median(gaps) / 1e6
            whole = sum(gaps)
            detail["gap_seconds"] = {k: v / 1e9 for k, v in cut.items()}
            for metric, cls in GAP_SHARES.items():
                out[metric] = 100.0 * cut[cls] / whole
        detail["traced_step_ms_p50"] = statistics.median(
            s["dur_s"] for s in traced) * 1e3
    return out, detail


def report(out, detail):
    """The full table and the longest step, on standard error."""
    secs = detail.get("scope_seconds")
    if secs:
        total = sum(secs.values())
        log("device seconds by scope and direction (share of "
            f"{total:.4f} s of operations):")
        for (scope, direction), v in sorted(secs.items(),
                                            key=lambda kv: -kv[1]):
            log(f"  {scope or '(no scope)':14s} {direction or '-':4s} "
                f"{v:9.5f} s {100 * v / total:6.2f}%")
        tops = sorted(detail["ops"].items(),
                      key=lambda kv: -sum(kv[1].values()))[:12]
        for op, parts in tops:
            log(f"  op {op}: " + ", ".join(
                f"{sc or '(no scope)'}{'/' + d if d else ''} {v:.4f}"
                for (sc, d), v in sorted(parts.items(),
                                         key=lambda kv: -kv[1])[:5]))
    if detail.get("clock_error_bound_s") is not None:
        log(f"clock alignment error bound "
            f"{detail['clock_error_bound_s'] * 1e3:.4f} ms (spread of the "
            f"per-step offsets); device start after dispatch began, flag "
            f"read's end after device end, least (s): "
            f"{detail['clock_causality_s']}; the harness's span holds "
            f"{detail['harness_beyond_trainer_ms_p50']:.3f} ms beyond the "
            f"trainer's (p50); traced stretch step p50 "
            f"{detail['traced_step_ms_p50']:.3f} ms against the window's "
            f"{out.get('trainer_step_ms_p50', float('nan')):.3f}")
    if "gap_seconds" in detail:
        log("between-step gaps of device 0 by host phase (s): " + " ".join(
            f"{k}={v:.5f}" for k, v in detail["gap_seconds"].items()))
    step = detail.get("longest_window_step")
    if step:
        log(f"longest window step {step['step']}: {step['dur_s'] * 1e3:.3f} "
            "ms = " + " ".join(f"{p[:-2]} {step[p] * 1e3:.3f}"
                               for p in PHASES))
    first = detail.get("first_step")
    if first:
        log(f"first step {first['dur_s']:.3f} s (prepare "
            f"{first['prepare_s']:.3f}, dispatch {first['dispatch_s']:.3f}, "
            f"flag wait {first['flag_wait_s']:.3f}): trace "
            f"{out['first_step_trace_s']:.3f}, lower "
            f"{out['first_step_lower_s']:.3f}, compile or load "
            f"{out['first_step_compile_or_load_s']:.3f}; cache "
            f"{detail['first_step_cache']}")


def _keyed(d):
    return {"|".join(k): v for k, v in d.items()}


def save_side_file(said, out, detail, reduced, path=SIDE_FILE):
    """The last traced run's attribution, whole, beside the profiler's
    scratch: the readings, the table behind them, the spans, and the rows of
    the scope table that the trace names (what ``save_fixture`` merges into a
    kept trace)."""
    names = set()
    if reduced is not None:
        for evs in reduced.devices.values():
            names.update(n for n, _, _ in evs)
    table = said["scope_table"] or {}
    # an inner jit's trace of under a millisecond, nested in its outer
    # one's: thousands a first step, and no second of any union
    keep = [e for e in said["compile_events"]
            if e["kind"] != "trace" or e["dur_s"] >= 1e-3]
    doc = {"readings": out,
           "scope_seconds": _keyed(detail.get("scope_seconds", {})),
           "ops": {op: _keyed(p) for op, p in detail.get("ops", {}).items()},
           "gap_seconds": detail.get("gap_seconds"),
           "clock_error_bound_s": detail.get("clock_error_bound_s"),
           "clock_causality_s": detail.get("clock_causality_s"),
           "harness_beyond_trainer_ms_p50":
               detail.get("harness_beyond_trainer_ms_p50"),
           "traced_step_ms_p50": detail.get("traced_step_ms_p50"),
           "steps": said["steps"],
           "scope_table": {n: table[n] for n in sorted(names) if n in table},
           "compile_events": keep}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, separators=(",", ":"))


def save_fixture(trace_path, side_path, out_path, n_window, n_traced):
    """Merge a trace kept with ``--keep-trace`` and that run's side file
    into one fixture: the trace's planes, with ``steps``, ``scope_table``,
    ``compile_events`` and the run's step counts beside them."""
    raw = T.load_fixture(trace_path)
    side = manifest.load_json(side_path)
    names = {ev[0] for p in raw["planes"] for ln in p["lines"]
             for ev in ln["events"]}
    raw["steps"] = side["steps"]
    raw["scope_table"] = {n: v for n, v in side["scope_table"].items()
                          if n in names}
    raw["compile_events"] = side["compile_events"]
    raw["n_window"], raw["n_traced"] = int(n_window), int(n_traced)
    with gzip.open(out_path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))

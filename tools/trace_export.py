"""Perfetto/Chrome trace export of flight-recorder timelines, and the
``obssmoke`` CI gate.

``tools/trace_summary.py`` reads the DEVICE side of a profile (XLA
op lanes, overlap ratios); this tool renders the HOST side — the
semantic spans the flight recorder (serve/events.py) captured: what
every router/engine/slot/trainer lane was doing and when. Load the
output at https://ui.perfetto.dev (or chrome://tracing): one timeline
with

  - a process per component (router, replica<i>/engine, trainer,
    checkpoint, supervisor);
  - per-slot lanes inside an engine: each request's residency
    (ADMIT → TERMINAL/PREEMPT, named by request id and outcome) and
    every prefill chunk as duration spans;
  - a ``steps`` lane of decode/verify steps (width + live occupancy
    in the args);
  - a ``steps`` lane of training steps on the trainer's process, each
    ``TRAIN_STEP`` a span from its start over ``dur_s`` with its host
    phases (prepare, dispatch, bind, flag_wait) on a ``phases`` lane
    beneath it; a ``TRAIN_STEP`` that carries no span (``gluon.Trainer``)
    stays an instant;
  - instants for the control plane: SUBMIT, DISPATCH, REQUEUE,
    BROWNOUT, REPLICA_HEALTH, CHECKPOINT_COMMIT, SUPERVISOR_*, CHAOS
    injections.

Usage:
  python tools/trace_export.py --events events.json --out trace.json
      # events.json = FlightRecorder.dump_events() output
  python tools/trace_export.py --smoke
      # the obssmoke CI stage (ci/run.sh): runs a seeded chaos
      # scenario with the recorder on, asserts the postmortem dump
      # names the injected fault and validates against the schema,
      # and asserts the Perfetto export of a mixed prefill/decode/
      # preemption run validates and shows per-slot lanes.

The export is pure host-side JSON shaping — no jax, no device work.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

# request-lifecycle instants that are NOT span endpoints; everything
# else is handled structurally below
_INSTANT_TYPES = ("SUBMIT", "DISPATCH", "REQUEUE", "BROWNOUT",
                  "REPLICA_HEALTH", "CHECKPOINT_COMMIT", "TRAIN_STEP",
                  "SUPERVISOR_RESTART", "SUPERVISOR_GIVEUP", "CHAOS")


def _as_dicts(events):
    out = []
    for e in events:
        out.append(e if isinstance(e, dict) else e.to_dict())
    return out


def to_perfetto(events) -> dict:
    """Convert a flight-recorder event list (``Event`` objects or
    their ``to_dict`` form, any mix of components) into a Chrome
    trace-JSON dict: ``{"traceEvents": [...], "displayTimeUnit":
    "ms"}``. Timestamps are rebased to the earliest event (Perfetto
    wants microseconds from a zero-ish origin, not perf_counter's
    arbitrary epoch)."""
    events = _as_dicts(events)
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    origin = min(e["ts"] for e in events)

    def us(ts):
        return (ts - origin) * 1e6

    pids = {}                            # component -> pid
    trace = []

    def pid_of(component):
        if component not in pids:
            pids[component] = len(pids) + 1
            trace.append({"ph": "M", "name": "process_name",
                          "pid": pids[component], "tid": 0,
                          "args": {"name": component}})
        return pids[component]

    # request residency spans: (component, request_id) -> the open
    # ADMIT event; closed by the same request's TERMINAL or PREEMPT
    open_admit = {}
    for e in events:
        comp = e["component"]
        pid = pid_of(comp)
        et = e["etype"]
        data = dict(e.get("data", {}))
        rid = e.get("request_id")
        if et == "ADMIT":
            open_admit[(comp, rid)] = e
            continue
        if et in ("TERMINAL", "PREEMPT"):
            adm = open_admit.pop((comp, rid), None)
            if adm is not None:
                slot = adm.get("data", {}).get("slot", 0)
                name = f"req {rid}"
                if et == "TERMINAL":
                    name += f" ({data.get('outcome', '?')})"
                else:
                    name += " (preempted)"
                trace.append({
                    "ph": "X", "name": name, "cat": "request",
                    "pid": pid, "tid": f"slot{slot}",
                    "ts": us(adm["ts"]),
                    "dur": max(us(e["ts"]) - us(adm["ts"]), 1.0),
                    "args": {**adm.get("data", {}), **data,
                             "request_id": rid}})
            else:
                # terminal without residency (shed/cancel-from-queue):
                # an instant on the events lane
                trace.append({
                    "ph": "i", "s": "t", "name": f"{et} req {rid} "
                    f"{data.get('outcome', '')}".strip(),
                    "cat": "request", "pid": pid, "tid": "events",
                    "ts": us(e["ts"]), "args": data})
            if et == "PREEMPT":          # the instant marks the cause
                trace.append({
                    "ph": "i", "s": "t", "name": f"PREEMPT req {rid}",
                    "cat": "request", "pid": pid, "tid": "events",
                    "ts": us(e["ts"]), "args": data})
            continue
        if et == "PREFILL_CHUNK":
            trace.append({
                "ph": "X", "cat": "prefill",
                "name": f"prefill[{data.get('start', 0)}:+"
                        f"{data.get('n', 0)}]",
                "pid": pid, "tid": f"slot{data.get('slot', 0)}",
                "ts": us(e["ts"]),
                "dur": max(data.get("dur_s", 0.0) * 1e6, 1.0),
                "args": {**data, "request_id": rid}})
            continue
        if et == "DECODE_STEP":
            w = data.get("width", 1)
            trace.append({
                "ph": "X", "cat": "decode",
                "name": "verify" if w > 1 else "decode",
                "pid": pid, "tid": "steps", "ts": us(e["ts"]),
                "dur": max(data.get("dur_s", 0.0) * 1e6, 1.0),
                "args": data})
            continue
        if et == "TRAIN_STEP" and "dur_s" in data:
            trace.append({
                "ph": "X", "cat": "train",
                "name": f"step {data.get('step', '?')} "
                        f"({data.get('outcome', '?')})",
                "pid": pid, "tid": "steps", "ts": us(e["ts"]),
                "dur": max(data["dur_s"] * 1e6, 1.0), "args": data})
            at = e["ts"]
            for phase in ("prepare_s", "dispatch_s", "bind_s",
                          "flag_wait_s"):
                trace.append({
                    "ph": "X", "cat": "train", "name": phase[:-2],
                    "pid": pid, "tid": "phases", "ts": us(at),
                    "dur": max(data.get(phase, 0.0) * 1e6, 0.0),
                    "args": {}})
                at += data.get(phase, 0.0)
            continue
        if et in _INSTANT_TYPES:
            name = et
            if rid is not None:
                name += f" req {rid}"
            elif e.get("entity"):
                name += f" {e['entity']}"
            trace.append({"ph": "i", "s": "t", "name": name,
                          "cat": "control", "pid": pid,
                          "tid": "events", "ts": us(e["ts"]),
                          "args": data})
            continue
        trace.append({"ph": "i", "s": "t", "name": et, "cat": "other",
                      "pid": pid, "tid": "events", "ts": us(e["ts"]),
                      "args": data})
    # a still-open residency at export time renders as a span to the
    # last event (the honest "it was live when the recording stopped")
    end = max(e["ts"] for e in events)
    for (comp, rid), adm in open_admit.items():
        trace.append({
            "ph": "X", "name": f"req {rid} (live)", "cat": "request",
            "pid": pid_of(comp),
            "tid": f"slot{adm.get('data', {}).get('slot', 0)}",
            "ts": us(adm["ts"]),
            "dur": max(us(end) - us(adm["ts"]), 1.0),
            "args": {**adm.get("data", {}), "request_id": rid}})
    return {"traceEvents": trace, "displayTimeUnit": "ms"}


def validate_trace(trace: dict) -> None:
    """Raise ValueError unless ``trace`` is a loadable Chrome/Perfetto
    trace-JSON object: a traceEvents list whose entries carry the
    required phase fields with sane types, and the whole thing
    JSON-serializable (the obssmoke "export loads" gate)."""
    if not isinstance(trace, dict) or "traceEvents" not in trace:
        raise ValueError("trace must be a dict with 'traceEvents'")
    if not isinstance(trace["traceEvents"], list):
        raise ValueError("traceEvents must be a list")
    for ev in trace["traceEvents"]:
        for key in ("ph", "name", "pid", "tid"):
            if key not in ev:
                raise ValueError(f"trace event missing {key!r}: {ev}")
        if ev["ph"] not in ("X", "i", "M", "B", "E", "C"):
            raise ValueError(f"unknown phase {ev['ph']!r}")
        if ev["ph"] != "M":
            if not isinstance(ev.get("ts"), (int, float)) or \
                    ev["ts"] < 0:
                raise ValueError(f"bad ts in {ev}")
        if ev["ph"] == "X" and not (isinstance(ev.get("dur"),
                                               (int, float))
                                    and ev["dur"] >= 0):
            raise ValueError(f"X event needs dur >= 0: {ev}")
    try:
        json.dumps(trace)
    except (TypeError, ValueError) as e:
        raise ValueError(f"trace is not JSON-serializable: {e}")


def export_file(events_path: str, out_path: str) -> dict:
    with open(events_path) as f:
        payload = json.load(f)
    events = payload["events"] if isinstance(payload, dict) else payload
    trace = to_perfetto(events)
    validate_trace(trace)
    with open(out_path, "w") as f:
        json.dump(trace, f, indent=1)
        f.write("\n")
    return trace


# --------------------------------------------------------------------- #
# obssmoke (ci/run.sh): the end-to-end observability gate
# --------------------------------------------------------------------- #

def _smoke(tmpdir: str) -> int:
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.models import gpt as g
    from incubator_mxnet_tpu.serve import (InferenceEngine, Request,
                                           Tier, build_fleet)
    from incubator_mxnet_tpu.serve.chaos import (KillReplica,
                                                 run_fleet_chaos)
    from incubator_mxnet_tpu.serve.events import (EventType,
                                                  validate_postmortem)
    errors = []

    mx.random.seed(0)
    model = g.gpt_mini(vocab_size=64, max_length=64)
    model.initialize()
    rng = np.random.RandomState(0)

    def _prompt(n):
        return rng.randint(0, 64, size=(n,)).astype(np.int32)

    # -- 1. seeded replica kill with the recorder on: the postmortem
    #       must name the injected fault, the killed replica, and the
    #       re-queued requests, and validate against the schema ------- #
    print("== obssmoke: seeded replica kill → postmortem")
    rt = build_fleet(model, 2,
                     engine_kw=dict(num_slots=2, page_size=8,
                                    max_len=64),
                     max_requeues=0)
    rt.flight.postmortem_dir = tmpdir
    reqs = [Request(_prompt(5), max_new_tokens=6) for _ in range(4)]
    inj = KillReplica(0, at_step=1, phase="decode")
    run_fleet_chaos(rt, reqs, [inj])
    if not inj.fired:
        errors.append("obssmoke: the kill never fired")
    failed = [r for r in reqs
              if r.outcome is not None and
              r.outcome.value == "FAILED_REPLICA"]
    if not failed:
        errors.append("obssmoke: no FAILED_REPLICA at the requeue "
                      "bound — the postmortem trigger never ran")
    pms = list(rt.flight.postmortems)
    if not pms:
        errors.append("obssmoke: no postmortem dumped")
    for pm in pms:
        try:
            validate_postmortem(pm)
        except ValueError as e:
            errors.append(f"obssmoke: postmortem fails schema: {e}")
    if pms:
        # a bound-hit dumps per request as it lands; the recorder
        # keeps the OLDEST max_postmortems dumps (the first failure is
        # the root cause), so no single dump is guaranteed to name
        # every later casualty — assert over the UNION of the kept
        # dumps' timelines
        all_evs = [e for pm in pms for e in pm["events"]]
        ets = [(e["etype"], e.get("data", {})) for e in all_evs]
        if not any(t == "CHAOS" for t, _ in ets):
            errors.append("obssmoke: postmortems lack the injected "
                          "fault's CHAOS event")
        if not any(t == "REPLICA_HEALTH" and
                   d.get("to_state") == "DEAD" for t, d in ets):
            errors.append("obssmoke: postmortems lack the replica "
                          "death event")
        # max_requeues=0: every killed in-flight request goes straight
        # to FAILED_REPLICA — the TERMINAL/REQUEUE events must name
        # them all somewhere across the kept dumps
        named = {e.get("request_id") for e in all_evs
                 if e["etype"] in ("TERMINAL", "REQUEUE")}
        missing = [r.request_id for r in failed
                   if r.request_id not in named]
        if missing:
            errors.append(f"obssmoke: postmortem timelines do not "
                          f"name re-queued/failed requests {missing}")
        on_disk = pms[0].get("path")
        if not (on_disk and os.path.exists(on_disk)):
            errors.append("obssmoke: postmortem file was not written")
        else:
            with open(on_disk) as f:
                validate_postmortem(json.load(f))

    # -- 2. mixed prefill/decode/preemption run → Perfetto export
    #       validates and shows per-slot lanes ----------------------- #
    print("== obssmoke: mixed prefill/decode/preemption → Perfetto")
    eng = InferenceEngine(model, num_slots=2, page_size=8, max_len=64,
                          chunk_pages=1, max_preemptions=4)
    batch = [Request(_prompt(20), max_new_tokens=8, tier=Tier.BATCH)
             for _ in range(3)]
    for r in batch:
        eng.submit(r)
    for _ in range(6):
        eng.step()
    lat = [Request(_prompt(5), max_new_tokens=4, tier=Tier.LATENCY)
           for _ in range(2)]
    for r in lat:
        eng.submit(r)
    steps = 0
    while any(r.outcome is None for r in batch + lat):
        eng.step()
        steps += 1
        if steps > 2000:
            errors.append("obssmoke: engine failed to drain")
            break
    if eng.preemptions < 1:
        errors.append("obssmoke: the LATENCY arrivals never preempted "
                      "a BATCH slot — the mix is not exercising "
                      "preemption")
    events_path = os.path.join(tmpdir, "events.json")
    trace_path = os.path.join(tmpdir, "trace.json")
    eng.flight.dump_events(events_path)
    try:
        trace = export_file(events_path, trace_path)
    except ValueError as e:
        errors.append(f"obssmoke: Perfetto export invalid: {e}")
        trace = {"traceEvents": []}
    tids = {ev["tid"] for ev in trace["traceEvents"]
            if ev["ph"] == "X"}
    slot_lanes = {t for t in tids if str(t).startswith("slot")}
    if len(slot_lanes) < 2:
        errors.append(f"obssmoke: expected >=2 per-slot lanes in the "
                      f"export, got {sorted(slot_lanes)}")
    cats = {ev.get("cat") for ev in trace["traceEvents"]}
    for want in ("request", "prefill", "decode"):
        if want not in cats:
            errors.append(f"obssmoke: export lacks {want!r} spans")
    evs = eng.flight.events()
    if not any(e.etype is EventType.PREEMPT for e in evs):
        errors.append("obssmoke: no PREEMPT event recorded")

    # -- 2.5 client edge: the HTTP front end's lane must ride the
    #        same timeline — request residency spans on a "frontend"
    #        process carrying the HTTP status and disconnect cause --- #
    print("== obssmoke: HTTP/SSE client edge → frontend lane")
    from incubator_mxnet_tpu.serve import (ServeFrontend,
                                           stream_completion)
    eng_f = InferenceEngine(model, num_slots=2, page_size=8,
                            max_len=64)
    with ServeFrontend(eng_f) as fe:
        ok = stream_completion("127.0.0.1", fe.bound_port,
                               {"prompt": [3, 4, 5],
                                "max_new_tokens": 6})
        cut = stream_completion("127.0.0.1", fe.bound_port,
                                {"prompt": [6, 7, 8],
                                 "max_new_tokens": 48},
                                abort_after_tokens=2)
        tdead = time.perf_counter() + 30
        while len(fe.finished) < 2 and time.perf_counter() < tdead:
            time.sleep(0.02)
    if ok["final"] is None or not cut["aborted"]:
        errors.append("obssmoke: frontend drive did not produce one "
                      "completion + one disconnect")
    ftrace = to_perfetto(eng_f.flight.events())
    try:
        validate_trace(ftrace)
    except ValueError as e:
        errors.append(f"obssmoke: frontend export invalid: {e}")
    fprocs = {ev["args"]["name"] for ev in ftrace["traceEvents"]
              if ev["ph"] == "M" and ev["name"] == "process_name"}
    if "frontend" not in fprocs:
        errors.append(f"obssmoke: export lacks the frontend lane: "
                      f"{sorted(fprocs)}")
    fe_spans = [ev for ev in ftrace["traceEvents"]
                if ev["ph"] == "X" and ev.get("cat") == "request" and
                "http_status" in ev.get("args", {})]
    statuses = {ev["args"]["http_status"] for ev in fe_spans}
    if not {200, 499} <= statuses:
        errors.append(f"obssmoke: frontend request spans lack the "
                      f"200-completion/499-disconnect statuses: "
                      f"{sorted(statuses)}")
    if not any("disconnect" in str(ev["args"].get("cause", ""))
               for ev in fe_spans):
        errors.append("obssmoke: no frontend span carries the "
                      "client-disconnect cause")

    # -- 3. fleet timeline export (router + replica lanes merge) ----- #
    fleet_trace = to_perfetto(rt.flight_events())
    try:
        validate_trace(fleet_trace)
    except ValueError as e:
        errors.append(f"obssmoke: fleet export invalid: {e}")
    procs = {ev["args"]["name"] for ev in fleet_trace["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "process_name"}
    if "router" not in procs or not any(p.startswith("replica")
                                        for p in procs):
        errors.append(f"obssmoke: fleet export lacks router/replica "
                      f"lanes: {sorted(procs)}")

    # -- 4. the training step from inside: TRAIN_STEP spans -------- #
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.serve.events import validate_event_dict
    tr = parallel.SPMDTrainer(
        model, forward_loss=g.lm_loss, optimizer="adamw",
        optimizer_params={"learning_rate": 1e-3})
    n_dev = len(tr.mesh.devices.flat)
    for _ in range(2):
        ids = rng.randint(0, 64, size=(n_dev, 16)).astype(np.int32)
        tr.step(ids, ids)
    steps = [e.to_dict() for e in tr.flight.events("trainer")]
    ttrace = to_perfetto(steps)
    try:
        for d in steps:
            validate_event_dict(d)
        validate_trace(ttrace)
    except ValueError as e:
        errors.append(f"obssmoke: trainer export invalid: {e}")
    names = [ev["name"] for ev in ttrace["traceEvents"]
             if ev["ph"] == "X"]
    if sum(n.startswith("step ") for n in names) != 2 or \
            not {"prepare", "dispatch", "bind", "flag_wait"} <= set(names):
        errors.append(f"obssmoke: trainer export lacks the step spans "
                      f"and their host phases: {names}")

    for e in errors:
        print(f"FAIL: {e}", file=sys.stderr)
    if not errors:
        print(f"obssmoke ok: postmortem + schema + Perfetto export "
              f"({len(trace['traceEvents'])} engine trace events, "
              f"{len(fleet_trace['traceEvents'])} fleet trace events, "
              f"{len(ttrace['traceEvents'])} trainer trace events)")
    return 1 if errors else 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--events", help="events JSON "
                    "(FlightRecorder.dump_events output)")
    ap.add_argument("--out", help="trace JSON output path")
    ap.add_argument("--smoke", action="store_true",
                    help="run the obssmoke CI gate")
    args = ap.parse_args()
    if args.smoke:
        import tempfile
        with tempfile.TemporaryDirectory() as td:
            sys.exit(_smoke(td))
    if not args.events or not args.out:
        ap.error("need --events and --out (or --smoke)")
    trace = export_file(args.events, args.out)
    print(f"wrote {args.out} ({len(trace['traceEvents'])} events) — "
          f"load at https://ui.perfetto.dev")


if __name__ == "__main__":
    main()

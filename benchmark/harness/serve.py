"""Drives a serving cell: ``InferenceEngine.submit`` + ``InferenceEngine.step``
from the benchmark's own open loop, in one thread.

The stream is one run of arrivals at the cell's fixed rate: a pre-roll that
brings the engine to a steady state (set-up), the measured window, and a
post-roll that keeps the load up until the window's last requests have
finished (and, with ``--trace 1``, through a traced stretch after that).
Every request is timed from the instant it was due, not from its submit.
"""

import json
import time

import jax
import numpy as np

from . import check, device, refserve, stats, traffic, weights as W
from .device import log

POSTROLL_S = 140.0         # longest the run waits for the window's requests


class ServeRun:
    def __init__(self, cell, seed, rehearsal, t_process):
        self.cell, self.seed, self.rehearsal = cell, int(seed), rehearsal
        self.t_process = t_process
        self.mix = cell.traffic
        self.config = cell.config
        self.ref = cell.reference()
        self.devs = device.require_chips(cell.chips, rehearsal)
        self.vocab = int(self.config["vocab_size"])
        self.phases = {}
        self.keep_fixture = None

    def _phase(self, name, t0):
        self.phases[name] = round(time.perf_counter() - t0, 3)

    # -------------------------------------------------------------- set-up
    def setup(self):
        t0 = time.perf_counter()
        spec = self.ref.param_spec(self.config)
        self.fresh_weights = lambda: W.make_weights(spec, self.seed)
        weights = self.fresh_weights()
        jax.block_until_ready(weights)
        self._phase("weights_s", t0)
        t0 = time.perf_counter()
        self.sut = self.cell.adapter().build_engine(
            self.config, self.mix["engine"], weights, self.rehearsal)
        del weights
        self._phase("build_s", t0)
        t0 = time.perf_counter()
        self._warm_shapes()
        self._phase("warm_shapes_s", t0)

    def _until_idle(self, reqs):
        for r in reqs:
            self.sut.submit(r)
        while self.sut.busy():
            self.sut.step()
        bad = [r for r in reqs if not self.sut.ok(r)]
        if bad:
            raise SystemExit(f"benchmark: warm-up request failed: "
                             f"{bad[0].outcome} {bad[0].detail}")

    def _warm_shapes(self):
        """Every program this cell's traffic uses, and no other: the decode
        step, one chunk-prefill program for each power-of-two page count up
        to the engine's chunk, the page copy behind a shared partial page,
        and, for a mix with shared prefixes, each prefix once."""
        eng = self.mix["engine"]
        page = int(eng["page_size"])
        rng = np.random.default_rng(7)
        reqs = []
        b = 1
        while b <= int(eng["chunk_pages"]):
            ids = rng.integers(0, self.vocab, b * page - 1, dtype=np.int32)
            reqs.append(self.sut.request(ids, 2))
            b *= 2
        long_ids = rng.integers(0, self.vocab,
                                int(eng["chunk_pages"]) * page * 2 + 5,
                                dtype=np.int32)
        reqs.append(self.sut.request(long_ids, 2))
        self._until_idle(reqs)
        if eng["prefix_cache"]:
            base = rng.integers(0, self.vocab, 2 * page + page // 2,
                                dtype=np.int32)
            self._until_idle([self.sut.request(base, 2)])
            again = np.concatenate([base, rng.integers(
                0, self.vocab, 5, dtype=np.int32)])
            self._until_idle([self.sut.request(again, 2)])
        prefixes = traffic.persona_prefixes(self.mix, self.vocab)
        for p in prefixes:
            own = rng.integers(0, self.vocab, page, dtype=np.int32)
            self._until_idle([self.sut.request(np.concatenate([p, own]), 1)])

    # ---------------------------------------------------------------- loop
    def _stream(self, rate, seconds, trace_s, postroll_s=POSTROLL_S):
        reqs, _ = traffic.serve_requests(self.mix, rate, seconds, self.seed,
                                         self.vocab, postroll_s)
        for r in reqs:
            r["req"] = self.sut.request(r.pop("prompt_ids"),
                                        r["max_new_tokens"])
            r["n_prompt"] = int(r["req"].prompt_ids.size)
        pre = float(self.mix["preroll_s"])
        window_reqs = [r for r in reqs if r["in_window"]]
        steps = []                      # (start, end, live) of engine.step
        tracer, traced = None, None
        marks = {}
        i, n = 0, len(reqs)
        t_s = time.perf_counter()
        end_at = pre + seconds
        hard_stop = end_at + postroll_s - 1.0
        while True:
            now = time.perf_counter() - t_s
            while i < n and reqs[i]["due"] <= now:
                with device.annotate("bench.submit"):
                    self.sut.submit(reqs[i]["req"])
                i += 1
            if "before" not in marks and now >= pre:
                marks["before"] = self.sut.counters()
            if "after" not in marks and now >= end_at:
                marks["after"] = self.sut.counters()
            if now >= end_at and all(
                    r["req"].outcome is not None for r in window_reqs):
                if not trace_s:
                    break
                if tracer is None:
                    tracer = device.Tracer(self.cell.name)
                    tracer.start()
                    traced = [time.perf_counter(), None]
                elif time.perf_counter() - traced[0] >= trace_s:
                    traced[1] = time.perf_counter()
                    tracer.stop()
                    break
            if now >= hard_stop:
                if tracer is not None and traced[1] is None:
                    traced[1] = time.perf_counter()
                    tracer.stop()
                break
            if self.sut.busy():
                t0 = time.perf_counter()
                with device.annotate("bench.engine_step"):
                    live = self.sut.step()
                steps.append((t0, time.perf_counter(), live))
            else:
                nxt = reqs[i]["due"] - now if i < n else 0.001
                time.sleep(max(0.0, min(0.0005, nxt)))
        marks.setdefault("before", self.sut.counters())
        marks.setdefault("after", self.sut.counters())
        return {"reqs": reqs, "window_reqs": window_reqs, "t_s": t_s,
                "w0": t_s + pre, "w1": t_s + pre + seconds,
                "seconds": seconds, "steps": steps, "marks": marks,
                "tracer": tracer, "traced": traced,
                "end": time.perf_counter(), "rate": rate}

    def _measure(self, run):
        """End-to-end numbers of a finished stream."""
        w0, w1 = run["w0"], run["w1"]
        t_s = run["t_s"]
        emitted = 0
        for r in run["reqs"]:
            stamps = r["req"].token_stamps
            emitted += sum(1 for s in stamps if w0 <= s < w1)
        ttft, gaps, late, failed = [], [], [], 0
        for r in run["window_reqs"]:
            q = r["req"]
            due = t_s + r["due"]
            late.append((q.submit_time or run["end"]) - due)
            if self.sut.ok(q) and q.token_stamps:
                ttft.append(q.token_stamps[0] - due)
                st = q.token_stamps
                gaps.extend(b - a for a, b in zip(st, st[1:]))
            else:
                failed += 1
                ttft.append(run["end"] - due)
        return {
            "serve_tok_s": emitted / run["seconds"],
            "ttft_p95_ms": stats.percentile(ttft, 95) * 1e3,
            "itl_p95_ms": (stats.percentile(gaps, 95) or 0.0) * 1e3,
        }, {"ttft_s": ttft, "gaps_s": gaps, "late_s": late,
            "failed": failed, "attempted": len(run["window_reqs"]),
            "emitted": emitted}

    def _print_stamps(self, run, detail):
        log(f"requests due in window {detail['attempted']} failed "
            f"{detail['failed']} tokens emitted in window "
            f"{detail['emitted']} engine steps {len(run['steps'])}")
        for name, xs in (("ttft_ms", detail["ttft_s"]),
                         ("itl_ms", detail["gaps_s"]),
                         ("lateness_ms", detail["late_s"])):
            if xs:
                log(f"{name}: n={len(xs)} " + " ".join(
                    f"p{q}={stats.percentile(xs, q) * 1e3:.2f}"
                    for q in (5, 25, 50, 75, 90, 95, 99, 100)))
        last = max((r["req"].token_stamps[-1] for r in run["window_reqs"]
                    if r["req"].token_stamps), default=run["w1"])
        log(f"window's last request finished {last - run['w1']:.2f} s after "
            f"the window closed")

    # ----------------------------------------------------------------- run
    def run(self, seconds, trace):
        self.setup()
        rate = float(self.mix["rate_per_s"])
        sweep = self.mix.get("sweep") or {}
        log(f"offered rate {rate}/s; knee {sweep.get('knee_per_s')}/s from "
            f"the sweep of {sweep.get('found')}")
        for row in sweep.get("table") or []:
            log("sweep " + " ".join(f"{k}={v}" for k, v in row.items()))
        t0 = time.perf_counter()
        trace_s = float(self.mix.get("trace_seconds", 5)) if trace else 0.0
        run = self._stream(rate, seconds, trace_s)
        self.phases["preroll_s"] = round(run["w0"] - t0, 3)
        setup_s = run["w0"] - self.t_process
        log("set-up seconds: " + " ".join(
            f"{k}={v}" for k, v in self.phases.items())
            + f" total={setup_s:.2f}")
        e2e, detail = self._measure(run)
        self._print_stamps(run, detail)
        e2e["setup_s"] = setup_s
        reduced = None
        if run["tracer"] is not None:
            reduced = run["tracer"].reduce(self.keep_fixture)
        dev = device.device_block(self.devs)
        self.finished = run
        ctx = {"kind": "serve", "config": self.config, "traffic": self.mix,
               "reference": self.ref, "chips": len(self.devs),
               "device_kind": dev["kind"], "run": run, "detail": detail,
               "trace": reduced, "e2e": e2e, "sut": self.sut}
        ctx["events"] = {k: self.sut.events(k)
                         for k in ("ADMIT", "DECODE_STEP", "PREFILL_CHUNK")} \
            if trace else {}
        return e2e, ctx, dev, detail["attempted"], detail["failed"]

    def sweep(self, rates, seconds):
        """Offer each rate for ``seconds`` (after the pre-roll) and print the
        table the knee is read from: the highest rate at which the engine
        still completes what it is offered without a growing backlog."""
        self.setup()
        rows = []
        for rate in rates:
            run = self._stream(rate, seconds, 0.0, postroll_s=25.0)
            e2e, detail = self._measure(run)
            offered = sum(r["max_new_tokens"] for r in run["window_reqs"]) \
                / seconds
            backlog = sum(1 for r in run["window_reqs"]
                          if not r["req"].token_stamps
                          or r["req"].token_stamps[0] > run["w1"])
            row = {"rate": rate, "offered_tok_s": offered,
                   "drain_s": max((r["req"].token_stamps[-1]
                                   for r in run["window_reqs"]
                                   if r["req"].token_stamps),
                                  default=run["w1"]) - run["w1"],
                   "first_token_after_close": backlog,
                   "failed": detail["failed"], **e2e}
            rows.append(row)
            log("SWEEP " + " ".join(
                f"{k}={v:.2f}" if isinstance(v, float) else f"{k}={v}"
                for k, v in row.items()))
            if backlog > 0.05 * len(run["window_reqs"]):
                break               # past the knee: higher rates say no more
            while self.sut.busy():
                self.sut.step()
        print("SWEEP_TABLE " + json.dumps(rows), flush=True)

    # ------------------------------------------------------------- correct
    def free(self):
        self.sut.close()
        self.sut = None

    def compare(self, extra_modes=()):
        """Once the window has closed and the engine is freed: a sample of
        the window's finished requests, drawn from the seed, with the longest
        in it, is scored by the plain reference. ``extra_modes`` (calibration
        only): ``control`` puts the reference in the lower precision in the
        program's place, through the same comparison."""
        t0 = time.perf_counter()
        done = [r for r in self.finished["window_reqs"]
                if r["req"].outcome is not None
                and r["req"].outcome.name in ("EOS", "MAX_TOKENS")
                and r["req"].token_ids]
        failed = len(self.finished["window_reqs"]) - len(done)
        chk = self.mix["check"]
        sample = refserve.sample(done, int(chk["sample_requests"]), self.seed)
        weights = self.fresh_weights()
        numbers, readings = refserve.score(
            self.ref, self.config, weights, sample,
            control=chk["control"] if "control" in extra_modes else None)
        numbers["requests_failed"] = float(failed)
        for nums in readings.values():
            nums["requests_failed"] = float(failed)
        log(f"reference took {time.perf_counter() - t0:.1f} s over "
            f"{len(sample)} requests, "
            f"{sum(len(r['req'].token_ids) for r in sample)} served tokens")
        log("numbers " + " ".join(f"{k}={v:.3g}" for k, v in numbers.items()))
        limits = chk["limits"][self.cell.entry["config"]]
        ok, table = check.compare(numbers, limits)
        verdicts = {who: check.compare(nums, limits)[0]
                    for who, nums in readings.items()}
        return ok, table, readings, verdicts

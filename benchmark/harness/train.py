"""Drives a training cell: ``SPMDTrainer.step`` from the benchmark's own loop.

Set-up builds one compiled step with its state, drives it through its first
three steps on seeded rows that all differ (the plain reference later follows
those three), warms it until step time has settled, and hands that same
object to the window. The rate is all tokens of every step completed in the
window over the time from the window's start to the end of its last step.
"""

import time

import jax
import numpy as np

from . import check, device, reftrain, stats, traffic, weights as W
from .device import log


def _settled(times, rel):
    last = times[-3:]
    med = stats.median(last)
    return len(last) == 3 and max(abs(t - med) for t in last) <= rel * med


class TrainRun:
    def __init__(self, cell, seed, rehearsal, t_process):
        self.cell, self.seed, self.rehearsal = cell, int(seed), rehearsal
        self.t_process = t_process
        self.job = cell.traffic
        self.config = cell.config
        self.ref = cell.reference()
        self.devs = device.require_chips(cell.chips, rehearsal)
        self.rows = int(self.job["batch_per_chip"]) * cell.chips
        self.tokens_per_step = self.rows * int(self.job["seq_len"])
        self.phases = {}
        self.sut = None
        self.keep_fixture = None        # --keep-trace: where to save it

    def _phase(self, name, t0):
        self.phases[name] = round(time.perf_counter() - t0, 3)

    # -------------------------------------------------------------- set-up
    def setup(self):
        t0 = time.perf_counter()
        spec = self.ref.param_spec(self.config)
        sharding = None
        if len(self.devs) > 1:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            mesh = Mesh(np.asarray(self.devs), ("all",))
            sharding = NamedSharding(mesh, PartitionSpec())
        # the trainer donates the arrays it is given, so the weights are
        # made anew from the seed wherever they are needed again
        self.fresh_weights = lambda: W.make_weights(spec, self.seed, sharding)
        weights = self.fresh_weights()
        jax.block_until_ready(weights)
        self._phase("weights_s", t0)

        t0 = time.perf_counter()
        self.sut = self.cell.adapter().build_trainer(
            self.config, self.job, weights, len(self.devs), self.rehearsal)
        del weights
        self.sut.parts = {name: W.parts(kind) for name, _, _, kind in spec}
        self._phase("build_s", t0)

        self.batches = traffic.train_batches(
            self.ref.batch_fields(self.config, self.job),
            self.ref.finish_batch, self.rows, self.config["vocab_size"],
            int(self.job["seq_len"]), self.seed)

        # the first three steps, through the window's own call and feed
        t0 = time.perf_counter()
        self.check_batches, self.got = [], {"loss": []}
        for i in range(int(self.job["check"]["steps"])):
            batch = next(self.batches)
            self.check_batches.append(batch)
            ts = time.perf_counter()
            loss = self.sut.step(batch)
            self.got["loss"].append(float(jax.device_get(loss)))
            log(f"check step {i + 1}: loss {self.got['loss'][-1]:.6f} "
                f"{time.perf_counter() - ts:.3f} s")
            if i == 0:
                self.got["grad"] = self.sut.gradient_norms()
                self._phase("first_step_s", t0)
        fresh = self.fresh_weights()
        self.got["change"] = self.sut.change_norms(fresh)
        self.got["copy"] = self.sut.copy_gap()
        log(f"copy_gap {self.got['copy']:.3g}; a compute copy never refreshed "
            f"would read {self.sut.copy_gap(stale=fresh):.3g}")
        del fresh
        self._phase("check_steps_s", t0)

        # warm up until the step time has settled: the last three steps
        # within settle_rel of their median, between min_steps and max_steps
        t0 = time.perf_counter()
        wu = self.job["warmup"]
        times = []
        while len(times) < wu["max_steps"]:
            times.append(self._timed_step())
            if len(times) >= wu["min_steps"] and \
                    _settled(times, wu["settle_rel"]):
                break
        self.warm_times = times
        log("warm-up step ms: " + " ".join(f"{t * 1e3:.1f}" for t in times))
        self._phase("warmup_s", t0)

    def _timed_step(self):
        batch = next(self.batches)
        t0 = time.perf_counter()
        with device.annotate("bench.train_step"):
            jax.block_until_ready(self.sut.step(batch))
        return time.perf_counter() - t0

    # -------------------------------------------------------------- window
    def window(self, seconds):
        """Steps until ``seconds`` have passed; whole steps only."""
        before = self.sut.counters()
        times = []
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            times.append(self._timed_step())
        elapsed = time.perf_counter() - t0
        after = self.sut.counters()
        return {"step_s": times, "elapsed_s": elapsed,
                "steps": len(times), "before": before, "after": after,
                "tokens": len(times) * self.tokens_per_step}

    def reference_only(self):
        """Calibration without the program: the batches the check would use,
        for reading the control and planted faults against the reference."""
        spec = self.ref.param_spec(self.config)
        self.fresh_weights = lambda: W.make_weights(spec, self.seed, None)
        batches = traffic.train_batches(
            self.ref.batch_fields(self.config, self.job),
            self.ref.finish_batch, self.rows, self.config["vocab_size"],
            int(self.job["seq_len"]), self.seed)
        self.check_batches = [next(batches) for _ in range(
            int(self.job["check"]["steps"]))]
        self.got = None

    def run(self, seconds, trace):
        self.setup()
        setup_s = time.perf_counter() - self.t_process
        log("set-up seconds: " + " ".join(
            f"{k}={v}" for k, v in self.phases.items())
            + f" total={setup_s:.2f}")
        win = self.window(seconds)
        log("window step ms: " + " ".join(
            f"{t * 1e3:.1f}" for t in win["step_s"]))
        reduced = None
        if trace:
            tracer = device.Tracer(self.cell.name)
            tracer.start()
            traced = self.window(float(self.job.get("trace_seconds", 5)))
            tracer.stop()
            reduced = tracer.reduce(self.keep_fixture)
            win["traced"] = traced
        dev = device.device_block(self.devs)
        nonapplied = win["steps"] - (win["after"]["steps_applied"]
                                     - win["before"]["steps_applied"])
        e2e = {
            "train_tok_s_chip": win["tokens"] / win["elapsed_s"]
            / len(self.devs),
            "setup_s": setup_s,
        }
        ctx = {"kind": "train", "config": self.config, "traffic": self.job,
               "reference": self.ref, "chips": len(self.devs),
               "device_kind": dev["kind"], "window": win, "trace": reduced,
               "e2e": e2e, "rows": self.rows, "nonapplied": nonapplied}
        return e2e, ctx, dev, win["steps"], nonapplied

    # ------------------------------------------------------------- correct
    def free(self):
        if self.sut is not None:
            self.sut.close()
        self.sut = None

    def compare(self, extra_modes=()):
        """Once the window has closed and the program's state is freed: the
        reference follows the first three steps. ``extra_modes`` (calibration
        only) puts the control, another precision of the reference or a
        planted fault in the program's place; each goes through the same
        comparison. Returns (correct, table, {who: numbers}, {who: correct})."""
        t0 = time.perf_counter()
        batches = self.check_batches
        weights = self.fresh_weights()
        want = reftrain.reference_steps(self.ref, self.config, self.job,
                                        weights, batches)
        if self.got is None:            # reference only: nothing to judge
            self.got = want
        numbers, detail = reftrain.gaps(self.got, want)
        log(f"reference took {time.perf_counter() - t0:.1f} s; worst leaves "
            f"grad {detail['grad_norm']} change {detail['change_norm']}; "
            f"{len(detail['leaves_left_out'])} leaves left out of the change")
        log("numbers " + " ".join(f"{k}={v:.3g}" for k, v in numbers.items()))
        log("losses program " + " ".join(f"{x:.6f}" for x in self.got["loss"])
            + " reference " + " ".join(f"{x:.6f}" for x in want["loss"]))
        readings = {"program": numbers}
        shares = int(self.job["check"].get("exchange_shares", len(self.devs)))
        variants = {
            "control": {"mode": self.job["check"]["control"]},
            "bf16": {"mode": "bf16"},
            "half_batch": {"rows": slice(0, self.rows // 2)},
            "no_exchange": {"rows": slice(0, self.rows // shares)},
        }
        for mode in extra_modes:
            if mode not in variants:
                raise SystemExit(f"unknown calibration mode {mode!r}")
            alt = reftrain.reference_steps(self.ref, self.config, self.job,
                                           weights, batches, **variants[mode])
            readings[mode] = reftrain.gaps(alt, want)[0]
            log(f"losses {mode} " + " ".join(f"{x:.6f}" for x in alt["loss"]))
        limits = self.job["check"]["limits"][self.cell.entry["config"]]
        ok, table = check.compare(numbers, limits)
        verdicts = {who: check.compare(nums, limits)[0]
                    for who, nums in readings.items()}
        return ok, table, readings, verdicts

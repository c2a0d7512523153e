"""``correct`` has to be able to come out false. Run on the CPU:

    python3 -m pytest benchmark/tests/test_controls.py -q

Each test drives the rest of a run at the rehearsal size (a configuration file
that no cell lists; the harness's look for a chip is skipped with
``--rehearsal``). First the sound program: correct. Then the control, the
reference in the nearest precision below the configuration's, put in the
program's place: not correct. Then the timed path broken underneath, once for
each fault a cell can have: a step that returns its state unchanged, half of
the batch left out, the exchange between chips left out, a compute copy that
the update never refreshes, a token altered where it is produced: not correct
each time. The control and the faults read off the reference go through the
harness's own comparison (``--calibrate``), not one written here.
"""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, ROOT)

from harness import manifest  # noqa: E402

REHEARSAL = os.path.join(BENCH, "tests", "rehearsal", "manifest.json")


def drive(workload, seed, seconds, extra=()):
    """One run of the command in this process; its result line as a dict,
    and everything else it printed."""
    run = manifest.load_module(os.path.join(BENCH, "run.py"), "bm_run")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--rehearsal",
                       "--manifest", REHEARSAL, *extra])
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return lines


def result(lines):
    res = json.loads(lines[-1])
    assert list(res)[-1] == "compared"          # the numbers come last
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    return res


def readings(lines):
    return [json.loads(l.split(" ", 1)[1]) for l in lines
            if l.startswith("CALIBRATION ")]


def failing(table):
    return [k for k, (v, lim) in table.items() if v is None or v > lim]


# --------------------------------------------------------------- training

@pytest.mark.parametrize("workload", ["tiny-train", "tiny-train-dp4",
                                      "tiny-mlm"])
def test_sound_training_run_is_correct(workload):
    res = result(drive(workload, 41, 0.3))
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_training_control_is_not_correct():
    """fp8 in the reference's matmuls, put in the program's place, comes out
    of the harness's comparison as not correct on every seed; so does the
    reference trained on half of the batch."""
    rows = readings(drive("tiny-train", 0, 0.1, [
        "--calibrate", "control,half_batch", "--seeds", "51,52,53"]))
    assert len(rows) == 3
    for row in rows:
        assert row["correct"] == {"program": True, "control": False,
                                  "half_batch": False}, row


def _patch_step(monkeypatch, wrap):
    from incubator_mxnet_tpu.parallel import spmd
    real = spmd.SPMDTrainer.step
    monkeypatch.setattr(spmd.SPMDTrainer, "step",
                        lambda self, *batch: wrap(self, real, batch))


def test_step_that_returns_its_state_unchanged(monkeypatch):
    import jax
    import jax.numpy as jnp

    def wrap(trainer, real, batch):
        if trainer._opt_state is None:      # first call builds the state
            return real(trainer, *batch)
        params = [jnp.copy(p._data._data) for p in trainer._params]
        state = jax.tree_util.tree_map(
            lambda s: jnp.copy(s._data), trainer._opt_state,
            is_leaf=lambda s: hasattr(s, "_data"))
        loss = real(trainer, *batch)
        for p, old in zip(trainer._params, params):
            p._data._data = old
        jax.tree_util.tree_map(
            lambda s, old: setattr(s, "_data", old), trainer._opt_state,
            state, is_leaf=lambda s: hasattr(s, "_data"))
        return loss

    _patch_step(monkeypatch, wrap)
    res = result(drive("tiny-train", 42, 0.2))
    assert res["correct"] is False
    assert "change_norm" in failing(res["compared"])


def test_compute_copy_never_refreshed(monkeypatch):
    """The update moves the float32 masters and the forward goes on reading
    the low-precision weights it started with."""
    import jax.numpy as jnp

    def wrap(trainer, real, batch):
        if trainer._opt_state is None:
            return real(trainer, *batch)
        params = [jnp.copy(p._data._data) for p in trainer._params]
        loss = real(trainer, *batch)
        for p, old in zip(trainer._params, params):
            if old.dtype != jnp.float32:
                p._data._data = old
        return loss

    _patch_step(monkeypatch, wrap)
    res = result(drive("tiny-train", 48, 0.2))
    assert res["correct"] is False
    assert "copy_gap" in failing(res["compared"])


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(trainer, real, batch):
        half = batch[0].shape[0] // 2
        return real(trainer, *[b[:half] for b in batch])

    _patch_step(monkeypatch, wrap)
    res = result(drive("tiny-train", 43, 0.2))
    assert res["correct"] is False
    assert "grad_norm" in failing(res["compared"])


def test_exchange_between_chips_left_out(monkeypatch):
    """Every chip trains on the first chip's rows: what the first chip would
    hold had its gradient never been exchanged."""
    def wrap(trainer, real, batch):
        share = batch[0].shape[0] // 4
        return real(trainer, *[np.concatenate([np.asarray(b[:share])] * 4)
                               for b in batch])

    _patch_step(monkeypatch, wrap)
    res = result(drive("tiny-train-dp4", 44, 0.2))
    assert res["correct"] is False
    assert "grad_norm" in failing(res["compared"])


# ---------------------------------------------------------------- serving

@pytest.mark.parametrize("workload", ["tiny-chat", "tiny-sessions"])
def test_sound_serving_run_is_correct(workload):
    res = result(drive(workload, 45, 3))
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_serving_control_is_not_correct():
    """fp8 in the reference's matmuls, read at the served positions and put
    in the program's place, comes out as not correct on every seed."""
    rows = readings(drive("tiny-chat", 0, 3, [
        "--calibrate", "control", "--seeds", "54,55,56"]))
    assert len(rows) == 3
    for row in rows:
        assert row["correct"] == {"program": True, "control": False}, row


def test_token_altered_where_it_is_produced(monkeypatch):
    from incubator_mxnet_tpu.serve import engine as eng
    real = eng.InferenceEngine._finish_token
    count = [0]

    def altered(self, slot_idx, token, dt):
        count[0] += 1
        if count[0] % 7 == 0:
            token = (int(token) + 1) % self.model.vocab_size
        return real(self, slot_idx, token, dt)

    monkeypatch.setattr(eng.InferenceEngine, "_finish_token", altered)
    res = result(drive("tiny-chat", 46, 3))
    assert res["correct"] is False
    assert "logit_gap" in failing(res["compared"])


def test_request_that_never_finishes_is_not_correct(monkeypatch):
    from incubator_mxnet_tpu.serve import engine as eng
    real = eng.InferenceEngine.submit
    count = [0]

    def refuse(self, request):
        count[0] += 1
        if count[0] % 9 == 0:
            self._record_terminal(request, eng.Outcome.SHED, "test")
            return False
        return real(self, request)

    monkeypatch.setattr(eng.InferenceEngine, "submit", refuse)
    res = result(drive("tiny-chat", 47, 3))
    assert res["failed"] > 0 and res["correct"] is False
    assert "requests_failed" in failing(res["compared"])

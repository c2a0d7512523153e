"""The hybrid (state-space + attention) cell at rehearsal size, on the CPU:

    python3 -m pytest benchmark/tests/test_hybrid_cell.py -q

Its manifest (``tests/rehearsal/manifest-hybrid.json``: the tiny twin of the
configuration, a ``tiny-`` mix, the metric entries the cell lists in
BENCHMARK.json, entry for entry) resolves; the sound program is correct; the
reference in the precision below, put in the program's place, is not; and
neither is the program with the state cache broken underneath, once for each
fault recurrent state can have that pages cannot (state_faults.py, which also
reads them on the chip): a slot's state never zeroed at admission, and state
not carried across a chunk boundary. The state kept in bfloat16 goes through
the same comparison, which cannot see it. All through the harness's own
comparison."""

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

from harness import manifest  # noqa: E402

MANIFEST = os.path.join(BENCH, "tests", "rehearsal", "manifest-hybrid.json")
CELL = "tiny-chat-hybrid"


def drive(seed, extra=()):
    run = manifest.load_module(os.path.join(BENCH, "run.py"), "bm_run")
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                       "3", "--rehearsal", "--manifest", MANIFEST, *extra])
    assert rc == 0
    return out.getvalue().strip().splitlines()


def result(lines):
    res = json.loads(lines[-1])
    assert list(res)[-1] == "compared"
    return res


def failing(table):
    return [k for k, (v, lim) in table.items() if v is None or v > lim]


def test_the_hybrid_manifest_resolves_and_mirrors_the_cell():
    """Every file the rehearsal cell names is there, and it lists the metrics
    that the real cell lists in BENCHMARK.json, no more and no fewer."""
    cell = manifest.Cell(MANIFEST, CELL)
    assert cell.entry["config"] in cell.traffic["check"]["limits"]
    assert os.path.exists(os.path.join(BENCH, "adapters",
                                       cell.config["adapter"]))
    assert os.path.exists(os.path.join(BENCH, "configs",
                                       cell.config["reference"]))
    for x in cell.per_layer():
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           x["name"] + ".py"))
    real = manifest.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    [name] = [w["name"] for w in real["workloads"]
              if w["traffic"] == "chat-decode-heavy"]
    for group in ("end_to_end", "per_layer"):
        want = {x["name"] for x in real[group]
                if "workloads" not in x or name in x["workloads"]}
        assert {x["name"] for x in cell.manifest[group]} == want
    full = manifest.load_json(os.path.join(
        BENCH, "configs", "granite-4.0-h-micro.json"))
    # the twin is the same family: the same keys, other sizes
    assert set(cell.config) - {"_note"} <= set(full)
    assert cell.config["reference"] == full["reference"]
    assert cell.config["adapter"] == full["adapter"]


def test_sound_hybrid_run_is_correct():
    res = result(drive(45))
    assert res["correct"] is True, res["compared"]
    assert res["failed"] == 0 and res["attempted"] > 0


def test_hybrid_control_is_not_correct():
    """The reference in bfloat16 (the precision below this float32 twin's),
    put in the program's place, is not correct on any seed."""
    lines = drive(0, ["--calibrate", "control", "--seeds", "54,55,56"])
    rows = [json.loads(l.split(" ", 1)[1]) for l in lines
            if l.startswith("CALIBRATION ")]
    assert len(rows) == 3
    for row in rows:
        assert row["correct"] == {"program": True, "control": False}, row


@pytest.mark.parametrize("fault,seed", [("never_zeroed", 46),
                                        ("dropped_carry", 47)])
def test_a_fault_of_the_state_cache_is_not_correct(fault, seed):
    """state_faults.py says what each is; the sound program on the same seed
    is correct, so the fault is what the comparison saw."""
    import state_faults
    for planted, want in (("sound", True), (fault, False)):
        table, _, verdicts = state_faults.run_one(
            MANIFEST, CELL, seed, planted, 3, True)
        assert verdicts == {"program": want}, (planted, table)
        if not want:
            assert failing(table) == ["logit_gap"]


def test_state_kept_in_bfloat16_serves_and_is_compared():
    """The third control, the state in the precision below the one the
    configuration states, runs through the same comparison with nothing
    failed. It is NOT asked to come out not correct: bfloat16 rows move a
    logit by a hundredth of what the bfloat16 matrices beside them do
    (tests/test_serve.py reads both), which no check of served tokens can
    see; the chip agrees (PERF.md, Findings, PR 36)."""
    import state_faults
    table, _, verdicts = state_faults.run_one(
        MANIFEST, CELL, 48, "bf16_state", 3, True)
    assert set(verdicts) == {"program"}
    assert table["requests_failed"] == [0.0, 0]
    assert table["logit_gap"][0] is not None


# ------------------------------------------------- the new readers, by hand

def _ctx(kernel_events, steps, live, other_ns=0):
    """A traced stretch of ``steps`` decode steps with ``live`` live slots
    each, ``kernel_events`` state-update kernels of 10 us, 2 paged-attention
    kernels of 5 us and ``other_ns`` of another operation."""
    from harness import trace
    cfg = manifest.load_json(os.path.join(
        BENCH, "configs", "granite-4.0-h-micro.json"))
    layers = cfg["layer_types"].count("mamba")
    ops = [["mxtpu_ssm_decode", 1000 * i, 10_000] for i in
           range(kernel_events)]
    ops += [["mxtpu_ragged_decode.3", 10**9 + 1000 * i, 5_000]
            for i in range(2)]
    if other_ns:
        ops.append(["fusion.7", 2 * 10**9, other_ns])
    raw = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops}]}]}
    events = [{"ts": 10.0 + i, "live": live, "state_rows": live * layers}
              for i in range(steps)]
    return {"kind": "serve", "config": cfg, "device_kind": "TPU v5 lite",
            "trace": trace.Reduced(raw, 1.0),
            "run": {"traced": [9.0, 9.0 + steps + 2]},
            "events": {"DECODE_STEP": events}}, layers


def test_state_update_work_by_hand():
    cell = manifest.Cell(MANIFEST, CELL)
    cell.reader("ssm_busy_share")           # puts metrics/ on the path
    import _ssm
    # one position of one sequence in one layer: 64 x 64 x 128 elements,
    # six operations each, read and written once in float32
    assert _ssm.state_update(1, 64, 64, 128, 4) == (
        6.0 * 524_288, 2.0 * 524_288 * 4)


def test_ssm_roofline_scales_by_the_share_of_kernels_the_trace_kept():
    cell = manifest.Cell(MANIFEST, CELL)
    read = cell.reader("ssm_decode_roofline")
    # 4 steps of 36 layers, 40 live: 144 kernels of 10 us = 1.44 ms for
    # 5760 rows x 4,194,304 B = 24,159,191,040 B, 29.50 ms at 819 GB/s (a
    # made-up trace: the share is not held under 100 here)
    ctx, layers = _ctx(4 * 36, 4, 40)
    assert layers == 36
    want = 100.0 * (5760 * 4_194_304 / 819e9) / (144 * 10e-6)
    assert read(ctx) == pytest.approx(want)
    # a trace that kept 3 of 4 steps' kernels: the work is scaled by 0.75
    ctx, _ = _ctx(3 * 36, 4, 40)
    assert read(ctx) == pytest.approx(want)
    # under half kept: nothing
    ctx, _ = _ctx(36, 4, 40)
    assert read(ctx) is None
    # a program whose step events say nothing of state rows: nothing
    ctx, _ = _ctx(4 * 36, 4, 40)
    for e in ctx["events"]["DECODE_STEP"]:
        del e["state_rows"]
    assert read(ctx) is None


def test_busy_shares_by_hand():
    cell = manifest.Cell(MANIFEST, CELL)
    ctx, _ = _ctx(10, 1, 1, other_ns=890_000)
    # 10 x 10 us + 2 x 5 us + 890 us = 1000 us
    assert cell.reader("ssm_busy_share")(ctx) == pytest.approx(10.0)
    assert cell.reader("ragged_attn_busy_share")(ctx) == pytest.approx(1.0)
    ctx, _ = _ctx(0, 1, 1, other_ns=1000)
    ctx["trace"].devices[0] = [e for e in ctx["trace"].devices[0]
                               if "ragged" not in e[0]]
    assert cell.reader("ssm_busy_share")(ctx) is None
    assert cell.reader("ragged_attn_busy_share")(ctx) is None

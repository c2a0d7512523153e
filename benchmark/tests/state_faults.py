"""What a state cache can get wrong that a page pool cannot, planted under
the program and put through the harness's own comparison:

  never_zeroed    a slot's state rows are not zeroed at admission: the last
                  occupant's state leaks into the next request
  dropped_carry   state is not carried across a chunk boundary: every
                  chunk of a prompt starts from nothing
  bf16_state      the recurrent state is kept in bfloat16, the precision
                  below the float32 the configuration file states (a control
                  and no fault: beside bfloat16 matrices it moves a logit by
                  a hundredth of what they do, and reads as the sound
                  program does; tests/test_serve.py has both numbers)

``sound`` is the program as it is. Each run is ``harness.serve.ServeRun``
as ``run.py`` drives it (set-up, pre-roll, window, the reference over a
sample of the window's requests, ``check.compare`` against the mix's
limits). test_hybrid_cell.py runs these at rehearsal size on the CPU; on the
chip the builder reads the two sides of the cell's ``logit_gap`` with

    python3 benchmark/tests/state_faults.py --workload <cell> \\
        sound=a,b,c dropped_carry=a,b never_zeroed=a bf16_state=a \\
        [--control 2] [--seconds 10] [--out chiprun_out/faults.jsonl]

one process, the runs in the order given, a FAULT line a run: the seed, the
fault, the numbers compared and the comparison's verdict at the limits the
mix file holds. ``--control n`` also puts the reference in the mix's control
precision in the program's place in the first n sound runs (``run.py
--calibrate control``).
"""

import argparse
import contextlib
import json
import os
import sys
import time
from unittest import mock

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (BENCH, ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)

from harness import manifest  # noqa: E402

FAULTS = ("sound", "never_zeroed", "dropped_carry", "bf16_state")


@contextlib.contextmanager
def planted(fault, cell):
    """``fault`` under every engine built inside the block, for ``cell``."""
    from incubator_mxnet_tpu.serve import engine as eng
    if fault == "sound":
        yield
    elif fault == "never_zeroed":
        with mock.patch.object(eng.InferenceEngine, "_zero_state",
                               lambda self, slot_idx: False):
            yield
    elif fault == "dropped_carry":
        # from the host, by the program that admission runs, so that nothing
        # new compiles: the slot's rows are zeroed before each of its chunks
        real = eng.InferenceEngine._run_chunk

        def run_chunk(self, slot_idx):
            self._zero_state(slot_idx)
            return real(self, slot_idx)

        with mock.patch.object(eng.InferenceEngine, "_run_chunk", run_chunk):
            yield
    elif fault == "bf16_state":
        with mock.patch.dict(cell.config, {"state_dtype": "bfloat16"}):
            yield
    else:
        raise SystemExit(f"state_faults: no fault {fault!r}; have {FAULTS}")


def run_one(manifest_path, workload, seed, fault, seconds, rehearsal,
            modes=()):
    """One run of the cell with ``fault`` planted -> (numbers compared, each
    beside its limit; {party: its numbers}; {party: correct or not})."""
    from harness.serve import ServeRun
    cell = manifest.Cell(manifest_path, workload)
    with planted(fault, cell):
        run = ServeRun(cell, seed, rehearsal, time.perf_counter())
        run.run(seconds, 0)
        run.free()
    _, table, readings, verdicts = run.compare(modes)
    return table, readings, verdicts


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--manifest",
                    default=os.path.join(ROOT, "BENCHMARK.json"))
    ap.add_argument("runs", nargs="+", metavar="fault=seed,seed")
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    from harness import device
    device.enable_compile_cache()
    controls = args.control
    for item in args.runs:
        fault, _, seeds = item.partition("=")
        for seed in (int(s) for s in seeds.split(",")):
            modes = ()
            if fault == "sound" and controls > 0:
                modes, controls = ("control",), controls - 1
            table, readings, verdicts = run_one(
                args.manifest, args.workload, seed, fault, args.seconds,
                args.rehearsal, modes)
            line = json.dumps({"cell": args.workload, "seed": seed,
                               "fault": fault, "compared": table,
                               "readings": readings, "correct": verdicts})
            print("FAULT " + line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

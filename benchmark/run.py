"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

runs one cell of ``BENCHMARK.json`` on the chips of this machine and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last the numbers compared, each beside its limit. Earlier
lines, on standard error, hold per-step times or request stamps and where
set-up's seconds went. It exits non-zero without printing a result where JAX
finds no TPU or too few chips.

Other modes of the same command, for the builder and not for the driver
(PERF.md says which reading each one gave):
  --calibrate control,half_batch[,no_exchange,bf16]   over ``--seeds a,b,c``
        in one process, also put the control (or the reference in bfloat16)
        and planted faults in the program's place and pass each through the
        same comparison: a CALIBRATION line a seed, with every party's
        numbers and whether it came out correct (--reference-only: training,
        without the program). How the limits' readings were taken.
  --sweep r1,r2,...   serving: offer each rate for --seconds and print the
        rate table from which the knee is read
  --keep-trace <file>   with --trace 1: keep the reduced trace as a fixture
        (how benchmark/fixtures/ was recorded)
  --rehearsal --manifest <file>   CPU at a tiny size, kernels interpreted;
        --manifest alone names the probes in benchmark/tests/probes/
"""

import os
import sys
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import manifest  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--manifest", default=os.path.join(manifest.ROOT,
                                                       "BENCHMARK.json"))
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--calibrate", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--sweep", default="")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--reference-only", action="store_true")
    return ap.parse_args(argv)


def read_metrics(cell, ctx, rehearsal):
    """Each per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and is left out of the line. In a
    rehearsal the CPU has no peak, and a metric that needs one is left out."""
    out = {}
    for m in cell.per_layer():
        try:
            value = cell.reader(m["name"])(ctx)
        except SystemExit:
            if not rehearsal:
                raise
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    args = parse(argv)
    cell = manifest.Cell(args.manifest, args.workload)
    seconds = args.seconds if args.seconds is not None \
        else float(cell.manifest["run_seconds"])
    sys.path.insert(0, cell.root)       # the program, from this checkout

    from harness import check, device
    cache = device.enable_compile_cache()
    device.log(f"cell {cell.name} seed {args.seed} seconds {seconds} trace "
               f"{args.trace} compile cache {cache}")
    kind = cell.traffic["kind"]
    if kind == "train":
        from harness.train import TrainRun as Run
    elif kind == "serve_open_loop":
        from harness.serve import ServeRun as Run
    else:
        raise SystemExit(f"benchmark: unknown traffic kind {kind!r}")

    calibrate = [m for m in args.calibrate.split(",") if m]
    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        Run(cell, args.seed, args.rehearsal, T_PROCESS).sweep(rates, seconds)
        return 0
    if calibrate:
        seeds = [int(s) for s in args.seeds.split(",") if s] or [args.seed]
        for seed in seeds:
            run = Run(cell, seed, args.rehearsal, time.perf_counter())
            if args.reference_only:
                run.reference_only()
            else:
                run.run(seconds, 0)
            run.free()
            ok, table, readings, verdicts = run.compare(calibrate)
            check.print_table(table, ok)
            print("CALIBRATION " + json.dumps(
                {"cell": cell.name, "seed": seed, "correct": verdicts,
                 "readings": readings}), flush=True)
            del run
        return 0

    run = Run(cell, args.seed, args.rehearsal, T_PROCESS)
    run.keep_fixture = args.keep_trace or None
    e2e, ctx, dev, attempted, failed = run.run(seconds, args.trace)
    result = {"correct": False, "attempted": int(attempted),
              "failed": int(failed)}
    if args.trace:
        red = ctx["trace"]
        result["metrics"] = read_metrics(cell, ctx, args.rehearsal)
        dev["busy_s"] = red.busy_s()
        dev["window_s"] = red.window_s
        result["breakdown"] = {"device_ops": red.top_ops(10),
                               "idle_gaps": red.idle_gaps(10)}
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]}
                             for k, v in e2e.items() if k in units}
    result["device"] = dev
    run.free()
    ok, table, _, _ = run.compare()
    result["correct"] = bool(ok)
    result["compared"] = table
    check.print_table(table, ok)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

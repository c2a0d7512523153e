"""Shared by the readers of the decode program's device time by part: the
``mx.`` scopes the serving programs are traced under (the model's and the
engine's own), joined with the trace.

The decode program's runs are those on device 0's ``XLA Modules`` line whose
name holds ``decode_step_fn``; the operations inside them are booked by their
instruction name through the engine's scope table of that program
(``InferenceEngine.scope_table("decode")``, reached through ``ctx["sut"]``).
An operation whose instruction has no ``mx.`` scope, or that the table does
not hold, is unscoped. The reading is worked out once a run, kept in ``ctx``,
logged as a table of part x operation, and written whole beside the
profiler's scratch (``benchmark_out/decode_scopes.json``).

Every reader returns None where the program has no such table (a commit
before it), where the trace holds no decode program, and where the table
holds no ``mx.`` name: an executable that a persistent compile cache kept
from before the scopes existed (JAX leaves metadata out of the cache key)
comes back without them, and its time is no reading of "unscoped".

``decode_weight_roofline``: the bytes of every weight a decode step streams
(each matrix and bias leaf of the reference's ``param_spec`` in the
configuration's types, the tied table through the head included, the
position table, read a row a token, left out), times the decode programs the
trace holds, over the chip's bandwidth, against those programs' device time
(their spans on the ``XLA Modules`` line). Not against the time of the
products alone: the compiler brings the weights into the chip's fast memory
with asynchronous copies that run under other parts' operations (the page
writes above all), so the products' own time holds only part of the stream
and a share of it would read over 100%."""
import bisect
import gzip
import json
import os
import time

import jax.numpy as jnp
import numpy as np

from harness import attribute, manifest, peaks, trace as T
from harness.device import log

PROGRAM = "decode_step_fn"
# per-layer metric -> the scope whose innermost device time it is a share of
PARTS = {"decode_embed_share": "mx.embed",
         "decode_norm_share": "mx.norm",
         "decode_attn_share": "mx.attn",
         "decode_attend_share": "mx.attend",
         "decode_kv_write_share": "mx.kv_write",
         "decode_ffn_share": "mx.ffn",
         "decode_ssm_share": "mx.ssm",
         "decode_ssm_state_share": "mx.ssm_state",
         "decode_head_share": "mx.head",
         "decode_sample_share": "mx.sample"}
UNSCOPED = "decode_unscoped_share"
SIDE_FILE = os.path.join(manifest.ROOT, "benchmark_out",
                         "decode_scopes.json")


def decode_ops(reduced):
    """(the decode programs' runs on device 0 that hold operations, the
    operations inside them as ``[(name, start, end)]``)."""
    if reduced is None or not reduced.modules or not reduced.devices:
        return [], []
    runs = sorted((s, e) for n, s, e in reduced.modules[min(reduced.modules)]
                  if PROGRAM in n)
    starts = [s for s, _ in runs]
    inside, held = [], set()
    for ev in reduced.devices[min(reduced.devices)]:
        k = bisect.bisect_right(starts, ev[1]) - 1
        if k >= 0 and ev[1] < runs[k][1]:
            inside.append(ev)
            held.add(k)
    return [runs[k] for k in sorted(held)], inside


def streamed_bytes(reference, config):
    """Bytes of the weights a decode step streams through its matrix
    products (see the module's docstring)."""
    total = 0
    for name, shape, dtype, kind in reference.param_spec(config):
        if kind.split("/")[0] in ("matrix", "bias") and name != "wpe":
            total += int(np.prod(shape)) * jnp.dtype(dtype).itemsize
    return total


def build(table, reduced, step_bytes=None, device_kind=None):
    """Every reading that can be had from a decode program's scope table
    and a reduced trace, by metric name, and the detail behind them."""
    out, detail = {}, {}
    if not any(sc.startswith("mx.") for sc, _ in table.values()):
        detail["why"] = ("the decode program's scope table holds no mx. "
                         "name: an executable cached before the scopes")
        return out, detail
    runs, ops = decode_ops(reduced)
    if not ops:
        detail["why"] = "the trace holds no decode program's operations"
        return out, detail
    secs, by_op = attribute.by_scope({0: ops}, table)
    parts = {}
    for (scope, _), v in secs.items():
        parts[scope] = parts.get(scope, 0.0) + v
    total = sum(parts.values())
    detail.update(part_seconds=parts, ops=by_op, programs=len(runs),
                  seconds=total,
                  program_seconds=sum(e - s for s, e in runs) / 1e9)
    if total <= 0:
        return out, detail
    for metric, scope in PARTS.items():
        out[metric] = 100.0 * parts.get(scope, 0.0) / total
    known = set(PARTS.values())
    out[UNSCOPED] = 100.0 * sum(v for sc, v in parts.items()
                                if sc not in known) / total
    if step_bytes and device_kind:
        bw = peaks.peak(device_kind)["hbm_bytes_per_s"]
        detail["step_bytes"] = step_bytes
        out["decode_weight_roofline"] = \
            100.0 * step_bytes * len(runs) / bw / detail["program_seconds"]
    return out, detail


def report(out, detail, table_s):
    log(f"decode program scope table read in {table_s:.3f} s")
    if "why" in detail:
        log(f"decode program by part: nothing read: {detail['why']}")
        return
    total = detail["seconds"]
    log(f"decode program by part: {detail['programs']} programs, "
        f"{total:.5f} s of operations ({detail['program_seconds']:.5f} s "
        f"of programs), {1e3 * total / detail['programs']:.3f} ms a "
        "program:")
    for scope, v in sorted(detail["part_seconds"].items(),
                           key=lambda kv: -kv[1]):
        log(f"  {scope or '(no scope)':14s} {v:9.5f} s "
            f"{100 * v / total:6.2f}%")
    for scope in sorted(detail["part_seconds"], key=lambda s: s or "~"):
        ops = sorted(((op, parts[(scope, "")]) for op, parts
                      in detail["ops"].items() if (scope, "") in parts),
                     key=lambda kv: -kv[1])[:8]
        log(f"  part {scope or '(no scope)'}: " + ", ".join(
            f"{op} {v:.5f}" for op, v in ops))
    if "step_bytes" in detail:
        log(f"decode weight roofline: {detail['step_bytes']:.4g} B a "
            f"program x {detail['programs']} programs over "
            f"{detail['program_seconds']:.5f} s of programs: "
            f"{out['decode_weight_roofline']:.2f}%")


def save_side_file(out, detail, table, reduced, path=None):
    """The run's reading, whole, with the rows of the decode table that
    the trace names (what a fixture merges into a kept trace)."""
    path = path or SIDE_FILE
    names = {n for n, _, _ in decode_ops(reduced)[1]}
    doc = {"readings": out,
           "why": detail.get("why"),
           "part_seconds": detail.get("part_seconds"),
           "ops": {op: {sc or "": v for (sc, _), v in p.items()}
                   for op, p in detail.get("ops", {}).items()},
           "programs": detail.get("programs"),
           "step_bytes": detail.get("step_bytes"),
           "scope_table": {n: list(table[n]) for n in sorted(names)
                           if n in table}}
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, separators=(",", ":"))


def save_fixture(trace_path, side_path, out_path):
    """Merge a trace kept with ``--keep-trace`` and that run's side file
    into one fixture: the trace's planes, with the rows of the decode
    program's scope table that the trace names beside them."""
    raw = T.load_fixture(trace_path)
    side = manifest.load_json(side_path)
    names = {ev[0] for p in raw["planes"] for ln in p["lines"]
             for ev in ln["events"]}
    raw["scope_table"] = {n: v for n, v in side["scope_table"].items()
                          if n in names}
    with gzip.open(out_path, "wt") as f:
        json.dump(raw, f, separators=(",", ":"))


def _build(ctx):
    if ctx.get("kind") != "serve" or ctx.get("trace") is None:
        return {}
    engine = getattr(ctx.get("sut"), "engine", None)
    if engine is None or not hasattr(engine, "scope_table"):
        log("decode program by part: nothing read: the engine keeps no "
            "scope table of its programs")
        return {}
    t0 = time.perf_counter()
    try:
        table = engine.scope_table("decode")
    except Exception as e:      # a reader returns nothing; it does not raise
        log(f"decode program by part: no scope table: {e!r}")
        return {}
    table_s = time.perf_counter() - t0
    step_bytes = None
    if hasattr(ctx.get("reference"), "param_spec"):
        step_bytes = streamed_bytes(ctx["reference"], ctx["config"])
    out, detail = build(table, ctx["trace"], step_bytes,
                        ctx.get("device_kind"))
    report(out, detail, table_s)
    save_side_file(out, detail, table, ctx["trace"])
    return out


def reading(ctx, name):
    if "_decode_scopes" not in ctx:
        ctx["_decode_scopes"] = _build(ctx)
    return ctx["_decode_scopes"].get(name)

#!/bin/bash
# CI pipeline (reference parity: ci/build.py + Jenkins stage set,
# SURVEY.md §2.4 — sanity/lint, native build, unit tests, driver entry
# checks). Self-contained: run from anywhere inside the repo.
#
#   ci/run.sh            # all stages
#   ci/run.sh sanity     # just the named stage
#   ci/run.sh native unit
set -e
cd "$(dirname "$0")/.."

stage_sanity() {
  echo "== sanity: byte-compile every python file"
  python -m compileall -q incubator_mxnet_tpu tests tools bench.py \
      chip_smoke.py __graft_entry__.py
  echo "== sanity: import the package on the CPU backend"
  JAX_PLATFORMS=cpu python -c "
import incubator_mxnet_tpu as mx
print('import ok:', mx.__version__)"
}

stage_lintcore() {
  echo "== lintcore: mxlint AST invariant analyzer (trace purity,"
  echo "             terminal outcomes, page refcounts, hot-loop host"
  echo "             syncs, lock discipline — docs/STATIC_ANALYSIS.md.)"
  echo "             Fails on any unbaselined, unwaived finding; the"
  echo "             summary line reports the baseline size so debt"
  echo "             growth is visible per PR. To acknowledge NEW debt:"
  echo "             python -m tools.mxlint --baseline ci/mxlint_baseline.json --update-baseline"
  echo "             then replace every UNREVIEWED reason with a real one."
  python -m tools.mxlint --baseline ci/mxlint_baseline.json
}

stage_native() {
  echo "== native: build the C++ runtime components (make)"
  make -C incubator_mxnet_tpu/src
  echo "== native: CMake configure parity check"
  cmake -S incubator_mxnet_tpu/src -B /tmp/mxtpu_cmake_build \
      >/dev/null && cmake --build /tmp/mxtpu_cmake_build >/dev/null
  echo "cmake build ok"
}

stage_unit() {
  echo "== unit: full pytest suite (virtual 8-device CPU mesh)"
  python -m pytest tests/ -q
}

stage_stepbench() {
  echo "== stepbench: fused-step regression guard (steady-state compile"
  echo "              count must stay at 1 per (shape, dtype) signature)"
  JAX_PLATFORMS=cpu python tools/step_bench.py --smoke
}

stage_mfubench() {
  echo "== mfubench: training-throughput regression guard (round 16"
  echo "             gates: the microbatch-accumulation program must"
  echo "             compile exactly once across accumulation counts,"
  echo "             a non-finite microbatch must veto the WHOLE"
  echo "             accumulated apply as one outcome with params"
  echo "             bit-identical, the guarded accumulated trajectory"
  echo "             must match the unguarded one bitwise on clean"
  echo "             streams, the overlapped bucket issue order must be"
  echo "             deterministic and equal to the plan order, and"
  echo "             every banked arm must carry tokens/s AND an MFU"
  echo "             field computed from the same run."
  echo "             Round-19 pipelined gates: the in-program overlapped"
  echo "             step on dp2 AND fsdp2 must (a) compile its"
  echo "             microbatch program exactly once across accumulation"
  echo "             counts {1,4,8}, (b) hold loss+param parity with the"
  echo "             paired GSPMD baseline over 3 steps — BITWISE on dp2,"
  echo "             allclose under fsdp (GSPMD's per-dot contraction"
  echo "             choice for sharded params is shape-regime noise),"
  echo "             (c) show structural overlap in StableHLO: grad"
  echo "             collectives in plan_grad_buckets order with backward"
  echo "             dots strictly between them (CPU-checkable); the int8"
  echo "             grad all-reduce must stay within 5% convergence"
  echo "             divergence of f32, and any arm tagged arm_kind="
  echo "             overlap that issues 0 buckets fails the stage)"
  JAX_PLATFORMS=cpu python tools/step_bench.py --mfu --smoke
}

stage_servebench() {
  echo "== servebench: continuous-batching regression guard (the decode"
  echo "               family must compile exactly once per program — W=1"
  echo "               narrow + K+1-wide verify — across occupancy churn and"
  echo "               mixed-agreement speculation; cache-hit admission must"
  echo "               compile ZERO new programs; chunked prefill must respect"
  echo "               its per-step token budget; zero-agreement speculation"
  echo "               must stay bit-identical to plain decode at the same"
  echo "               step count and within noise of its tokens/s)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --smoke
}

stage_quantbench() {
  echo "== quantbench: quantized-KV regression guard (int8 pages vs the"
  echo "               f32 jnp oracle: greedy top-1 token match >= 99%,"
  echo "               p99 logit error under the accuracy gate, decode/"
  echo "               verify/prefill each compiled exactly once in the"
  echo "               quantized arm, slots-at-fixed-pool-bytes >= 1.8x"
  echo "               the f32 layout; plus the int8-allreduce seam:"
  echo "               loss-curve divergence vs f32 bounded at 5%)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --quant --smoke
}

stage_chaossmoke() {
  echo "== chaossmoke: resilience guard (seeded faults — NaN weights,"
  echo "               corrupt/dropped page writes, allocator starvation,"
  echo "               host stalls, SIGTERM mid-serve; fails on any"
  echo "               non-terminal request, cross-slot contamination,"
  echo "               page-audit violation, or steady-state retrace)"
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --smoke
}

stage_fleetsmoke() {
  echo "== fleetsmoke: fleet resilience guard (router over N replicas —"
  echo "               replica kills mid-decode/mid-prefill become bounded"
  echo "               structured re-queues with emitted tokens preserved,"
  echo "               breaker opens/half-open-probes/closes under slow and"
  echo "               flapping replicas, fleet-level shedding carries"
  echo "               retry_after_s; fails on any lost/double-finished"
  echo "               request, survivor divergence, page-audit violation"
  echo "               on a surviving replica, or per-replica retrace)"
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --fleet --smoke
}

stage_tiersmoke() {
  echo "== tiersmoke: SLO-tier resilience guard (priority scheduling under"
  echo "              a mixed-tier overload storm — LATENCY preempts BATCH"
  echo "              slots and resumes them bit-identically, shedding"
  echo "              drains BATCH first; client cancel storms land as"
  echo "              exactly-one CANCELLED terminal from any live state;"
  echo "              preemption composes with NaN quarantine; brownout"
  echo "              hysteresis steps degrade levels up and back down;"
  echo "              fails on any non-terminal request, tier-ordering"
  echo "              violation, parity break, page-audit violation, or"
  echo "              steady-state retrace)"
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --tiers --smoke
}

stage_hiersmoke() {
  echo "== hiersmoke: hierarchical KV-cache guard (demote evicted prefix"
  echo "              pages to host DRAM/disk, re-admit by COPY — tiered"
  echo "              serving must be bit-identical to flat and recompute"
  echo "              arms, every page free XOR live XOR demoted at every"
  echo "              step, one promotion program ever; a corrupted demoted"
  echo "              payload must be convicted by crc and recomputed"
  echo "              loudly, a full disk must degrade the tier to a loud"
  echo "              no-op, and a kill mid-promotion must leave a"
  echo "              replacement engine that wipes stale tier dirs and"
  echo "              serves clean)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --hier --smoke
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --hier --smoke
}

stage_migratesmoke() {
  echo "== migratesmoke: page-transport guard (drain a replica under"
  echo "              load — decode-ready slots migrate with ZERO redone"
  echo "              prefill and zero lost requests, vs the replay arm's"
  echo "              full recompute; prefill/decode role split hands"
  echo "              every slot off at publication, bit-identical to"
  echo "              mixed; quantized capsules ship ~4x fewer wire"
  echo "              bytes; chaos: kill source mid-capture leaves the"
  echo "              slot decoding in place, kill destination"
  echo "              mid-install and capsule bit rot fall back to replay"
  echo "              LOUDLY, a migrate-vs-cancel race keeps exactly one"
  echo "              CANCELLED terminal; fails on any parity break,"
  echo "              page-audit violation, or steady-state retrace)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --migrate --smoke
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --migrate --smoke
}

stage_elasticsmoke() {
  echo "== elasticsmoke: elastic-membership guard (wave load against the"
  echo "              autoscaling supervisor — grow on sustained brownout,"
  echo "              shrink in the gaps, zero lost requests either arm;"
  echo "              rolling same-weights upgrade under load stays"
  echo "              bit-identical to the un-upgraded control; chaos:"
  echo "              scale-down racing scale-up in one fleet pass,"
  echo "              supervisor killed mid-roll leaves no replica"
  echo "              stranded DRAINING, replica death mid-drain replays"
  echo "              everything the drain had not moved — each ending"
  echo "              100% exactly-one-terminal with clean page audits"
  echo "              on every survivor and zero retraces)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --elastic --smoke
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --elastic --smoke
}

stage_frontsmoke() {
  echo "== frontsmoke: client-protocol guard (HTTP/SSE front end over"
  echo "               localhost — an end-to-end SSE stream must deliver"
  echo "               tokens incrementally, a mid-stream disconnect must"
  echo "               land as exactly-one CANCELLED terminal with pages"
  echo "               reclaimed, stop-sequence truncation must be correct"
  echo "               over the wire, decode must compile exactly once"
  echo "               through the HTTP path, and the constrained"
  echo "               tool-call arm must stay 100% in-language with the"
  echo "               decode family untraced by grammar masks)"
  JAX_PLATFORMS=cpu python tools/serve_bench.py --frontend --smoke
}

stage_frontchaos() {
  echo "== frontchaos: client-edge resilience guard (real-socket chaos —"
  echo "               disconnect storms and slow-reader backpressure must"
  echo "               each end in exactly one terminal per request with"
  echo "               clean page audits, survivor parity, and no retrace)"
  JAX_PLATFORMS=cpu python tools/chaos_bench.py --frontend --smoke
}

stage_obssmoke() {
  echo "== obssmoke: observability guard (flight recorder + tracing —"
  echo "             a seeded replica kill with the recorder on must dump"
  echo "             a postmortem JSON that validates against the event"
  echo "             schema and names the injected fault, the dead"
  echo "             replica, and every re-queued request; the Perfetto"
  echo "             export of a mixed prefill/decode/preemption run must"
  echo "             validate and show per-slot lanes; recorder overhead"
  echo "             is gated by the servebench stage's smoke run)"
  JAX_PLATFORMS=cpu python tools/trace_export.py --smoke
}

stage_trainchaos() {
  echo "== trainchaos: training resilience guard (seeded faults — NaN"
  echo "               gradients, overflow storms, persistent poison, NaN"
  echo "               batches on an fsdp mesh, kill -9 + supervisor resume,"
  echo "               hung-step watchdog, transient data-iterator IO errors;"
  echo "               fails on any step without exactly one recorded"
  echo "               outcome, a skip that mutated params/optimizer state,"
  echo "               a loss sequence that diverges across kill -9 resume,"
  echo "               a steady-state retrace, or guard+scaler overhead"
  echo "               over the smoke bar)"
  JAX_PLATFORMS=cpu python tools/train_chaos_bench.py --smoke
}

stage_ckptbench() {
  echo "== ckptbench: elastic-checkpoint regression guard (async commit +"
  echo "              keep-last-k GC + bit-exact capsule resume)"
  JAX_PLATFORMS=cpu python tools/ckpt_bench.py --smoke
}

stage_report() {
  echo "== report: bench trajectory (aggregates every banked BENCH_*.json"
  echo "           into BENCH_TRAJECTORY.md — informational, never fails)"
  python tools/bench_report.py || true
}

stage_entry() {
  echo "== entry: the 8-device CPU multichip dryrun must pass. (The chip"
  echo "          itself is checked by \`python chip_smoke.py\`, one process"
  echo "          on a machine that holds a TPU — not a CI stage here;"
  echo "          tests/test_chip_smoke.py keeps its CPU rehearsal green.)"
  JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8" \
  python -c "
import __graft_entry__ as ge
ge.dryrun_multichip(8)"
}

stages=("$@")
[ ${#stages[@]} -eq 0 ] && stages=(sanity lintcore native unit stepbench mfubench servebench quantbench chaossmoke fleetsmoke tiersmoke hiersmoke migratesmoke elasticsmoke frontsmoke frontchaos obssmoke trainchaos ckptbench entry report)
for s in "${stages[@]}"; do
  "stage_$s"
done
echo "CI: all stages green (${stages[*]})"

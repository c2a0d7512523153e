#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py                      # on a machine that holds a TPU
    python chip_smoke.py --sharding fsdp      # four-chip host: leg 2 over fsdp
    python chip_smoke.py --rehearsal          # CPU, gpt_mini, interpreted kernels

One process (a chip belongs to one process at a time), the entry points a
user calls, GPT-2-small at its published width with seeded random weights.
Legs, in order; the first failure exits non-zero naming the leg, and
nothing is caught and downgraded:

  0 device   platform must be ``tpu`` (exit != 0 before anything compiles),
             ``device_kind`` must be a row of the one peak table
  1 kernels  every Pallas attention kernel compiles through Mosaic at
             GPT-2-small shapes and agrees with its jnp reference
  2 train    ``SPMDTrainer`` + ``lm_loss`` + adamw over all local devices,
             1 compile step + 5 steps; the compiled step holds the Mosaic
             attention forward AND backward custom calls
  3 serve    ``InferenceEngine`` behind ``ServeFrontend``, 8 HTTP requests;
             the compiled decode and chunk-prefill programs (the model's
             ``cached_forward`` under the engine's paged ``attend``) hold
             the ragged Mosaic custom calls

  4 hybrid   Granite-4.0-H-Micro at its published widths and a cut depth
             (5 Mamba-2 layers, 1 grouped-query attention layer, 1 more
             Mamba-2 layer; the whole vocabulary) through the same
             ``InferenceEngine``: a handful of prompts, chunked prefill then
             decoding through the page pool and the state cache, and the
             gap of every served token to the benchmark's plain reference
             (float32, the recurrence as a recurrence); the decode program
             holds ``mxtpu_ssm_decode`` and ``mxtpu_ragged_decode``

The last-but-one line of stdout is one JSON object with every leg's
verdict and its smoke timings (compile seconds and the rest kept apart —
NOT metrics: nothing here is a benchmark). The last line is the success
marker ``{"ok": true, "device": {...}}`` with the device as JAX reports
it. A rehearsal never prints the marker: its last line says
``rehearsal platform=cpu``.
"""

import argparse
import concurrent.futures
import gc
import json
import os
import re
import sys
import time

# normalized max error |kernel - reference|_max / |reference|_max allowed
# between a bf16 kernel (f32 accumulation, probabilities rounded to bf16
# before the PV matmul, bf16 output) and the f32 `highest`-precision jnp
# reference on the same bf16 inputs. bf16 carries 8 significant bits
# (eps 2^-8 = 3.9e-3); two roundings plus the output cast stay under 2e-2.
BF16_TOL = 2e-2

_MOSAIC = 'custom_call_target="tpu_custom_call"'


class LegFailed(Exception):
    pass


def log(msg):
    print(msg, flush=True)


def check(cond, what):
    if not cond:
        raise LegFailed(what)


def mosaic_kernels(hlo_text):
    """{kernel name: count} of the Mosaic custom calls in a compiled
    program's HLO text (every pallas_call in ops/ carries a stable
    ``mxtpu_*`` name that lands in the instruction's op_name)."""
    found = {}
    for line in hlo_text.splitlines():
        if _MOSAIC not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        segs = m.group(1).split("/") if m else []
        name = next((s for s in segs if s.startswith("mxtpu_")),
                    "unnamed")
        found[name] = found.get(name, 0) + 1
    return found


def collectives(hlo_text):
    return {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo_text))
            for op in ("all-reduce", "all-gather", "reduce-scatter")}


class CompileClock:
    """Seconds the backend spent compiling programs (or fetching them
    from the persistent cache), and the cache's hit/miss counts — read
    per leg so compile time and the rest are reported apart."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.hits = self.misses = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


# --------------------------------------------------------------------- #
# leg 0: device
# --------------------------------------------------------------------- #

def leg_device(ctx):
    import jax
    import jaxlib
    devs = jax.devices()
    d0 = devs[0]
    try:
        import libtpu
        libtpu_ver = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_ver = "absent"
    log(f"device: platform={d0.platform} kind={d0.device_kind!r} "
        f"count={len(devs)} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_ver} "
        f"compile_cache_dir={ctx['cache_dir']}")
    if not ctx["rehearsal"]:
        from incubator_mxnet_tpu.utils.flops import device_peaks
        peaks = device_peaks(d0)         # unknown device_kind raises
        log(f"device: peak table row {peaks}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------- #
# leg 1: kernels against their references
# --------------------------------------------------------------------- #

def _norm_err(got, want):
    import jax.numpy as jnp
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    check(bool(jnp.all(jnp.isfinite(got))), "kernel output not finite")
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-6))


def _compile_run(fn, *args):
    """Compile ``fn`` for ``args``, run the compiled program, and return
    (outputs, {mosaic kernel: count}) — the kernel proof is read from
    the program that produced the outputs."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    out = compiled(*args)
    jax.block_until_ready(out)
    return out, mosaic_kernels(compiled.as_text())


def _reference(fn, *args):
    import jax
    with jax.default_matmul_precision("highest"):
        out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    return out


def _report(ctx, results, name, kernels, want, errs):
    impl = "interpret" if ctx["rehearsal"] else "mosaic"
    worst = max(errs.values())
    log(f"kernels: {name}: impl={impl} mosaic_calls={kernels} "
        + " ".join(f"{k}={v:.2e}" for k, v in errs.items())
        + f" (tol {BF16_TOL:.0e})")
    if not ctx["rehearsal"]:
        for k in want:
            check(kernels.get(k, 0) >= 1,
                  f"{name}: compiled program has no Mosaic call {k!r} "
                  f"(found {kernels})")
    check(worst <= BF16_TOL,
          f"{name}: kernel-vs-reference error {worst:.3e} over the bf16 "
          f"tolerance {BF16_TOL:.0e} ({errs})")
    results[name] = {"impl": impl, "max_err": round(worst, 5)}


def _flash_case(ctx, results, name, B, H, T, D, causal, want,
                packed=False):
    """One flash case against the jnp reference. ``packed``: through the
    dense pair that takes one (B, T, 3*H*D) projection and writes one
    gradient (the relayout from and to (B, H, T, D) is this test's,
    outside the program whose Mosaic calls are read)."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import pallas_attention as pa

    ks = jax.random.split(jax.random.PRNGKey(T + H), 4)
    q, k, v, g = (jax.random.normal(kk, (B, H, T, D), jnp.bfloat16)
                  for kk in ks)
    # one full row and one with a padded tail
    vl = jnp.asarray([T] + [T - 37] * (B - 1), jnp.int32)
    interp = ctx["rehearsal"]

    def flat(x):                           # (B, H, T, D) -> (B, T, H*D)
        return x.transpose(0, 2, 1, 3).reshape(B, T, H * D)

    def kernel(q, k, v, vl, g):
        out, vjp = jax.vjp(
            lambda q_, k_, v_: pa.flash_attention_bhtd(
                q_, k_, v_, vl, causal, None, interp), q, k, v)
        return (out,) + vjp(g)

    def kernel_packed(qkv, vl, g):
        out, vjp = jax.vjp(lambda x: pa.flash_dense_packed(
            x, vl, H, causal, None, interp), qkv)
        return (out,) + tuple(jnp.split(vjp(g)[0], 3, axis=-1))

    def reference(q, k, v, vl, g):
        out, vjp = jax.vjp(
            lambda q_, k_, v_: pa._dense_attn_lse(
                q_, k_, v_, vl, causal, None)[0], q, k, v)
        return (out,) + vjp(g)

    if packed:
        got, kernels = _compile_run(
            kernel_packed, jnp.concatenate([flat(q), flat(k), flat(v)], -1),
            vl, flat(g))
        got = tuple(x.reshape(B, T, H, D).transpose(0, 2, 1, 3)
                    for x in got)
    else:
        got, kernels = _compile_run(kernel, q, k, v, vl, g)
    ref = _reference(reference, q, k, v, vl, g)
    errs = {n: _norm_err(a, b)
            for n, a, b in zip(("out", "dq", "dk", "dv"), got, ref)}
    _report(ctx, results, name, kernels, want, errs)


def _ragged_inputs(S, H, D, ps, max_pages, page_need, seed, quant):
    """A random fused pool (keys | values on the lanes) + page tables;
    ``page_need[s]`` live pages per slot (distinct, never the null
    page). The null page is poisoned (NaN payload, or a NaN scale for
    int8 pools): a masked read that leaked would show."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    P = 1 + sum(page_need) + 3
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    k_pool = jax.random.normal(kk, (P, H, ps, D), jnp.bfloat16)
    v_pool = jax.random.normal(kv, (P, H, ps, D), jnp.bfloat16)
    perm = list(rng.permutation(np.arange(1, P)))
    table = np.zeros((S, max_pages), np.int32)
    for s, n in enumerate(page_need):
        for j in range(n):
            table[s, j] = perm.pop()
    if not quant:
        pool = jnp.concatenate([k_pool, v_pool], -1).at[0].set(jnp.nan)
        return pool, jnp.asarray(table), None, None

    def q8(pool):
        f = pool.astype(jnp.float32)
        scale = jnp.max(jnp.abs(f), axis=(1, 2, 3)) / 127.0
        codes = jnp.clip(jnp.round(f / scale[:, None, None, None]),
                         -127, 127).astype(jnp.int8)
        return codes, scale.at[0].set(jnp.nan)

    (k8, ksc), (v8, vsc) = q8(k_pool), q8(v_pool)
    return jnp.concatenate([k8, v8], -1), jnp.asarray(table), ksc, vsc


def _ragged_cases(ctx, results, quant):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu.ops import ragged_attention as ra

    z = ctx["size"]
    H, D, ps, max_pages = z["ragged_H"], z["D"], 16, z["max_len"] // 16
    lengths = z["ragged_lengths"]
    S = len(lengths)
    interp = True if ctx["rehearsal"] else None
    sfx = "_q" if quant else ""
    tag = "[int8]" if quant else ""
    pages = lambda n: -(-n // ps)

    # decode: one query per slot
    pool, table, ksc, vsc = _ragged_inputs(
        S, H, D, ps, max_pages, [pages(n) for n in lengths], 11, quant)
    q = jax.random.normal(jax.random.PRNGKey(1), (S, H, D), jnp.bfloat16)
    ln = jnp.asarray(lengths, jnp.int32)
    got, kern = _compile_run(
        lambda q, pool, t, ln, ks, vs: ra.ragged_paged_attention(
            q, pool, t, ln, interpret=interp, k_scale=ks, v_scale=vs),
        q, pool, table, ln, ksc, vsc)
    ref = _reference(
        lambda q, pool, t, ln, ks, vs: ra.ragged_attention_reference(
            q, pool, t, ln, k_scale=ks, v_scale=vs),
        q, pool, table, ln, ksc, vsc)
    for s, n in enumerate(lengths):
        if n == 0:
            check(not bool(jnp.any(got[s] != 0)),
                  f"ragged_decode{tag}: length-0 slot output not zero")
    _report(ctx, results, f"ragged_decode{tag}", kern,
            [f"mxtpu_ragged_decode{sfx}"], {"out": _norm_err(got, ref)})

    # verify: W query rows per slot, ragged real draft counts
    W = 4
    dl = np.asarray([(s * 3) % W for s in range(S)], np.int32)
    pool, table, ksc, vsc = _ragged_inputs(
        S, H, D, ps, max_pages,
        [pages(n + W - 1) if n else 0 for n in lengths], 13, quant)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, W, H, D),
                          jnp.bfloat16)
    got, kern = _compile_run(
        lambda q, pool, t, ln, dl, ks, vs: ra.ragged_verify_attention(
            q, pool, t, ln, draft_len=dl, interpret=interp,
            k_scale=ks, v_scale=vs),
        q, pool, table, ln, jnp.asarray(dl), ksc, vsc)
    ref = _reference(
        lambda q, pool, t, ln, ks, vs: ra.ragged_verify_reference(
            q, pool, t, ln, k_scale=ks, v_scale=vs),
        q, pool, table, ln, ksc, vsc)
    # rows past a slot's real draft count are discarded by the engine
    live = (np.arange(W)[None, :] <= dl[:, None])[:, :, None, None]
    _report(ctx, results, f"ragged_verify{tag}", kern,
            [f"mxtpu_ragged_verify{sfx}"],
            {"out": _norm_err(jnp.where(live, got, 0.0),
                              jnp.where(live, ref, 0.0))})

    # chunked prefill: one slot, C chunk rows at q_start, n_real live
    for C, spans in z["prefill_cases"]:
        errs = {}
        for start, n_real in spans:
            pool, table, ksc, vsc = _ragged_inputs(
                1, H, D, ps, max_pages, [pages(start + n_real)],
                17 + start, quant)
            q = jax.random.normal(jax.random.PRNGKey(3 + start),
                                  (C, H, D), jnp.bfloat16)
            qs, nr = jnp.int32(start), jnp.int32(n_real)
            got, kern = _compile_run(
                lambda q, pool, row, qs, nr, ks, vs:
                ra.ragged_prefill_attention(
                    q, pool, row, qs, n_real=nr, interpret=interp,
                    k_scale=ks, v_scale=vs),
                q, pool, table[0], qs, nr, ksc, vsc)
            ref = _reference(
                lambda q, pool, row, qs, nr, ks, vs:
                ra.ragged_prefill_reference(
                    q, pool, row, qs, n_real=nr, k_scale=ks,
                    v_scale=vs),
                q, pool, table[0], qs, nr, ksc, vsc)
            errs[f"start{start}+{n_real}"] = _norm_err(got[:n_real],
                                                       ref[:n_real])
        _report(ctx, results, f"ragged_prefill{tag}[C={C}]", kern,
                [f"mxtpu_ragged_prefill{sfx}"], errs)


def leg_kernels(ctx):
    z = ctx["size"]
    results = {}
    dense = ["mxtpu_flash_dense_fwd", "mxtpu_flash_dense_bwd"]
    stream = ["mxtpu_flash_stream_fwd", "mxtpu_flash_stream_dq",
              "mxtpu_flash_stream_dkv"]
    for H in z["dense_heads"]:
        for causal in (True, False):
            _flash_case(ctx, results,
                        f"flash_dense[H={H},T={z['dense_T']},"
                        f"{'causal' if causal else 'full'}]",
                        2, H, z["dense_T"], z["D"], causal, dense)
    # the dense pair that reads the projection's own (B, T, 3*H*D)
    # layout: what a one-device training step's layers call
    for H, causal in z["packed_cases"]:
        _flash_case(ctx, results,
                    f"flash_dense_packed[H={H},T={z['dense_T']},"
                    f"{'causal' if causal else 'full'}]",
                    2, H, z["dense_T"], 64, causal, dense, packed=True)
    for causal in (True, False):
        _flash_case(ctx, results,
                    f"flash_stream[H={z['H']},T={z['stream_T']},"
                    f"{'causal' if causal else 'full'}]",
                    2, z["H"], z["stream_T"], z["D"], causal, stream)
    _ragged_cases(ctx, results, quant=False)
    _ragged_cases(ctx, results, quant=True)
    return {"kernels": results}


# --------------------------------------------------------------------- #
# leg 2: train
# --------------------------------------------------------------------- #

def leg_train(ctx):
    import jax
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, parallel, profiler
    from incubator_mxnet_tpu.models import gpt as gpt_mod
    from incubator_mxnet_tpu.ndarray import NDArray
    from incubator_mxnet_tpu.parallel import mesh as pmesh

    z = ctx["size"]
    profiler.attention_dispatch(reset=True)   # drop the kernel leg's sites
    n_dev = len(jax.devices())
    sharding = ctx["sharding"]
    axis = "fsdp" if sharding == "fsdp" else "dp"
    mesh = pmesh.build_mesh(axis_sizes={axis: n_dev})
    B, T = z["train_B"], z["train_T"]

    mx.random.seed(0)
    model = z["model"](dtype="bfloat16", flash=True, dropout=0.0)
    model.initialize()
    trainer = parallel.SPMDTrainer(
        model, forward_loss=gpt_mod.lm_loss, optimizer="adamw",
        optimizer_params={"learning_rate": 3e-4, "multi_precision": True},
        mesh=mesh, sharding=sharding)

    rng = np.random.RandomState(0)
    V = model.vocab_size
    tokens = rng.randint(0, V, (B, T + 1))
    batch = (nd.array(tokens[:, :-1], dtype="int32"),
             nd.array(tokens[:, 1:], dtype="int32"))

    t0 = time.perf_counter()
    first = trainer.step(*batch)
    jax.block_until_ready(first._data)
    t_first = time.perf_counter() - t0
    # which implementation each attention call site got, at trace time
    tally = profiler.attention_dispatch()
    losses = [float(first.asnumpy())]
    t0 = time.perf_counter()
    for _ in range(5):
        loss = trainer.step(*batch)
        jax.block_until_ready(loss._data)
        losses.append(float(loss.asnumpy()))
    t_steps = time.perf_counter() - t0
    log(f"train: mesh {axis}={n_dev} sharding={sharding} B={B} T={T} "
        f"losses={[round(x, 4) for x in losses]} "
        f"first_step_s={t_first:.1f} five_steps_s={t_steps:.2f} "
        f"(smoke timings)")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall: {losses[0]:.4f} -> {losses[-1]:.4f}")
    check(trainer.step_trace_count == 1,
          f"step_trace_count {trainer.step_trace_count} != 1")

    # proof from the compiled program
    text = trainer.compiled_step_text()
    check(trainer.step_trace_count == 1,
          "compiled_step_text moved step_trace_count")
    kernels = mosaic_kernels(text)
    coll = collectives(text)
    log(f"train: compiled step mosaic_calls={kernels} collectives={coll}")
    log(f"train: attention dispatch tally={tally}")
    if not ctx["rehearsal"]:
        L = model.num_layers
        # one device: the pair that reads the projection; a mesh of
        # several keeps the (B, H, T, D) pair (ops/pallas_attention.py,
        # packed_dense_eligible)
        site = "dense_packed" if n_dev == 1 else "dense_bhtd"
        check(tally == {site: L},
              f"attention call sites: want {L} x {site}, got {tally}")
        check(kernels.get("mxtpu_flash_dense_fwd", 0) >= L and
              kernels.get("mxtpu_flash_dense_bwd", 0) >= L,
              f"compiled step lacks the Mosaic attention forward/backward "
              f"calls for {L} layers: {kernels}")
    if n_dev > 1:
        if sharding == "fsdp":
            check(coll["all-gather"] >= 1 and
                  coll["reduce-scatter"] + coll["all-reduce"] >= 1,
                  f"fsdp step lacks all-gather / gradient reduction: "
                  f"{coll}")
        else:
            check(coll["all-reduce"] >= 1,
                  f"dp step has no all-reduce: {coll}")

    # state resident on every device of the mesh
    want = set(mesh.devices.flat)
    arrays = [p.data()._data for p in trainer._params] + [
        leaf._data for leaf in jax.tree_util.tree_leaves(
            trainer._opt_state) if isinstance(leaf, NDArray)]
    for a in arrays:
        check(set(a.sharding.device_set) == want,
              f"array {a.shape} lives on {len(a.sharding.device_set)} of "
              f"{len(want)} mesh devices")
    if sharding == "fsdp" and n_dev > 1:
        split = sum(1 for a in arrays
                    if a.addressable_shards[0].data.size < a.size)
        check(split > 0, "fsdp: no array is actually sharded")
        log(f"train: fsdp shards {split}/{len(arrays)} arrays")
    in_use = []
    for d in jax.devices():
        stats = d.memory_stats()
        if stats is not None:
            in_use.append(stats["bytes_in_use"])
            check(stats["bytes_in_use"] > 0, f"{d} holds no bytes")
    log(f"train: {len(arrays)} param/optimizer arrays on all {n_dev} "
        f"devices; bytes_in_use per device={in_use}")
    return {"mesh": {axis: n_dev}, "sharding": sharding,
            "first_loss": losses[0], "last_loss": losses[-1],
            "step_trace_count": trainer.step_trace_count,
            "mosaic_calls": kernels, "collectives": coll,
            "attention_dispatch": tally,
            "first_step_s": round(t_first, 2),
            "five_steps_s": round(t_steps, 3)}


# --------------------------------------------------------------------- #
# leg 3: serve
# --------------------------------------------------------------------- #

def leg_serve(ctx):
    import jax
    import numpy as np
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, serve
    from incubator_mxnet_tpu.models import gpt as gpt_mod
    from incubator_mxnet_tpu.serve.frontend import (http_request,
                                                    stream_completion)

    z = ctx["size"]
    mx.random.seed(0)
    model = z["model"](dtype="bfloat16")
    model.initialize()
    engine = serve.InferenceEngine(
        model, page_size=16, num_slots=8, max_len=z["max_len"],
        prefix_cache=True, chunk_pages=z["chunk_pages"],
        interpret=True if ctx["rehearsal"] else None)
    chunk = z["chunk_pages"] * 16

    rng = np.random.RandomState(1)
    V = model.vocab_size
    toks = lambda n: [int(t) for t in rng.randint(0, V, n)]
    prefix = toks(z["prefix_len"])
    long_prompt = toks(z["long_len"])
    check(len(long_prompt) > chunk, "long prompt must span chunks")
    # (name, payload, streamed over SSE?)
    wave1 = [
        ("short-greedy", {"prompt": toks(9), "max_new_tokens": 16}, False),
        ("short-temp-sse", {"prompt": toks(12), "max_new_tokens": 24,
                            "temperature": 0.8, "seed": 7}, True),
        ("long-chunked", {"prompt": long_prompt, "max_new_tokens": 16},
         False),
        ("prefix-a", {"prompt": prefix + toks(10), "max_new_tokens": 16},
         False),
    ]
    wave2 = [
        ("prefix-b", {"prompt": prefix + toks(10), "max_new_tokens": 16},
         False),
        ("short-greedy-2", {"prompt": toks(14), "max_new_tokens": 32},
         False),
        ("short-temp-2", {"prompt": toks(11), "max_new_tokens": 20,
                          "temperature": 1.0, "seed": 11}, False),
        ("short-greedy-3", {"prompt": toks(16), "max_new_tokens": 16},
         False),
    ]

    fe = serve.ServeFrontend(engine, port=0).start()
    try:
        host, port = fe.host, fe.bound_port

        def send(item):
            name, payload, sse = item
            if sse:
                r = stream_completion(host, port, payload, timeout=1100)
                return name, r["status"], r["final"], r["tokens"]
            status, _, body = http_request(
                host, port, "POST", "/v1/completions",
                dict(payload, stream=False), timeout=1100)
            return name, status, body, body.get("tokens")

        done = []
        with concurrent.futures.ThreadPoolExecutor(4) as pool:
            for wave in (wave1, wave2):
                done += [f.result() for f in
                         [pool.submit(send, it) for it in wave]]
        asked = {n: p for n, p, _ in wave1 + wave2}
        for name, status, final, tokens in done:
            check(status == 200, f"{name}: HTTP {status} ({final})")
            check(final is not None and final.get("done"),
                  f"{name}: no terminal event")
            check(final["outcome"] in ("EOS", "MAX_TOKENS"),
                  f"{name}: outcome {final['outcome']}")
            want = asked[name]["max_new_tokens"]
            check(final["n_tokens"] == want and len(tokens) == want,
                  f"{name}: {final['n_tokens']}/{len(tokens)} tokens, "
                  f"asked {want}")
        with fe._lock:
            driver_error = fe._driver_error
        check(driver_error is None, f"frontend driver: {driver_error}")
        finished = list(fe.finished)
        check(len(finished) == len(done) and
              len({r.request_id for r in finished}) == len(done),
              f"{len(finished)} terminal records for {len(done)} "
              f"requests (exactly-one-terminal)")
    finally:
        fe.stop()

    engine.audit_pages()
    log(f"serve: {len(done)} requests 200 with the tokens they asked "
        f"for; decode_trace_count={engine.decode_trace_count} "
        f"prefill_trace_counts={engine.prefill_trace_counts} "
        f"prefix_hits={engine.prefix_hits} decode_steps="
        f"{engine.decode_steps}; audit_pages clean")
    check(engine.decode_trace_count == 1,
          f"decode_trace_count {engine.decode_trace_count} != 1")
    check(all(n == 1 for n in engine.prefill_trace_counts.values()),
          f"a prefill bucket traced twice: {engine.prefill_trace_counts}")
    check(engine.prefix_hits >= 1, "no prefix-cache hit")
    check(("chunk", chunk) in engine.prefill_trace_counts,
          f"no full {chunk}-token chunk ran: "
          f"{engine.prefill_trace_counts}")

    # proof from the compiled programs
    calls = {"decode": mosaic_kernels(
        engine.compiled_program_text("decode"))}
    for key in sorted(engine.prefill_trace_counts):
        calls[f"{key[0]}{key[1]}"] = mosaic_kernels(
            engine.compiled_program_text(key))
    check(engine.decode_trace_count == 1,
          "compiled_program_text moved decode_trace_count")
    log(f"serve: compiled programs mosaic_calls={calls}")
    if not ctx["rehearsal"]:
        L = model.num_layers
        check(calls["decode"].get("mxtpu_ragged_decode", 0) >= L,
              f"decode program lacks the ragged Mosaic calls: "
              f"{calls['decode']}")
        for name, k in calls.items():
            if name.startswith("chunk"):
                check(k.get("mxtpu_ragged_prefill", 0) >= L,
                      f"{name} program lacks the ragged Mosaic calls: "
                      f"{k}")
        for arr, what in ((engine._kvpools[0], "kv pool"),
                          (engine._param_vals[0], "weights")):
            plats = {d.platform for d in arr.devices()}
            check(plats == {"tpu"}, f"{what} lives on {plats}")

    # printed, not gated: bf16 argmax ties on seeded random weights can
    # flip between the paged and the dense-cache decode, two callers of
    # the one GPTModel.cached_forward (leg 1 is the numeric gate)
    name, _, _, tokens = done[0]
    prompt = asked[name]["prompt"]
    ref = gpt_mod.cached_generate(
        model, np.asarray([prompt], np.int32),
        max_new_tokens=len(tokens)).asnumpy()[0, len(prompt):]
    agree = int(np.sum(np.asarray(tokens) == ref))
    # teacher-forced: score the engine's OWN sequence with the dense
    # forward and ask how far below the row maximum each emitted token
    # sits — ties show as a deficit of a few bf16 ulps, a wrong program
    # as a deficit of the logits' spread
    logits = model(nd.array([prompt + tokens], dtype="int32")) \
        .asnumpy().astype(np.float32)[0]
    rows = logits[len(prompt) - 1:len(prompt) - 1 + len(tokens)]
    deficit = float(np.max(rows.max(axis=-1)
                           - rows[np.arange(len(tokens)), tokens]))
    log(f"serve: greedy agreement with models.gpt.cached_generate on "
        f"{name!r}: {agree}/{len(tokens)} tokens; teacher-forced, the "
        f"engine's tokens sit at most {deficit:.4f} below the dense "
        f"forward's row maximum (logit std {float(rows.std()):.4f}) "
        f"(printed, not gated)")
    return {"requests": len(done),
            "decode_trace_count": engine.decode_trace_count,
            "prefill_trace_counts": {f"{k[0]}{k[1]}": v for k, v in
                                     engine.prefill_trace_counts.items()},
            "prefix_hits": engine.prefix_hits,
            "mosaic_calls": calls,
            "greedy_agreement": f"{agree}/{len(tokens)}",
            "teacher_forced_max_deficit": round(deficit, 4)}


# --------------------------------------------------------------------- #

# widest gap allowed between a served token's reference logit and the
# reference's best at its position in the hybrid leg: bf16 matrices against
# the float32 reference on logits of spread 0.0094; read 0.00023 on the chip
# at this cut depth with ``dt_bias`` drawn about -3 (the cell, at full depth
# and about -4.6, reads 0.0007-0.0009 and allows 0.0033; PERF.md section 4)
HYBRID_GAP_TOL = 0.002


def leg_hybrid(ctx):
    """The second served block family, end to end against its reference.
    ``dt_bias`` is drawn about -3 (dt about 0.05; the configuration file's
    own mean is -4.6) so that state some twenty positions old still reaches a
    logit and a carry lost between chunks or steps would show: the draw the
    leg's tolerance was read with on the chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from incubator_mxnet_tpu import profiler, serve

    here = os.path.dirname(os.path.abspath(__file__))
    bench = os.path.join(here, "benchmark")
    sys.path.insert(0, bench)
    from harness import manifest, refops, weights as hw

    ref = manifest.load_module(os.path.join(
        bench, "configs", "granite_hybrid_reference.py"))
    adapter = manifest.load_module(os.path.join(
        bench, "adapters", "granite_hybrid_mxtpu.py"))
    z = ctx["size"]
    with open(os.path.join(bench, "configs", z["hybrid_config"])) as f:
        cfg = json.load(f)
    cfg["layer_types"] = cfg["layer_types"][:7]
    cfg["n_positions"] = z["hybrid_max_len"]
    cfg["dt_bias_mean"] = -3.0
    weights = dict(hw.make_weights(ref.param_spec(cfg), 1))
    profiler.ssm_dispatch(reset=True)
    model = adapter._model(cfg, dict(weights))
    engine = serve.InferenceEngine(
        model, num_slots=4, page_size=16, max_len=z["hybrid_max_len"],
        prefix_cache=False, chunk_pages=z["hybrid_chunk_pages"],
        interpret=True if ctx["rehearsal"] else None)
    rng = np.random.RandomState(2)
    reqs = [serve.Request(rng.randint(0, cfg["vocab_size"], n)
                          .astype(np.int32), max_new_tokens=k,
                          temperature=0.0, eos_id=-1)
            for n, k in z["hybrid_requests"]]
    t0 = time.perf_counter()
    engine.run(reqs, arrival_times=[0.0, 0.0, 0.05, 0.1, 0.15, 0.2])
    serve_s = time.perf_counter() - t0
    engine.audit_pages()
    check(all(r.outcome is not None and r.outcome.name == "MAX_TOKENS"
              for r in reqs), "every hybrid request runs to its length")
    check(engine.decode_trace_count == 1, "one decode program")
    tally = profiler.ssm_dispatch()
    n_state = cfg["layer_types"].count("mamba")
    check(tally.get("ssm_decode_pallas") == n_state,
          f"the decode program's state layers took the kernel: {tally}")
    text = engine.compiled_program_text("decode")
    kernels = mosaic_kernels(text)
    if not ctx["rehearsal"]:
        for name in ("mxtpu_ssm_decode", "mxtpu_ragged_decode"):
            check(any(name in k for k in kernels),
                  f"decode program holds {name}: {sorted(kernels)}")
    snap = engine.health_snapshot()
    ops = refops.Ops("f32")

    @jax.jit
    def gap_of(weights, ids, pos, toks):    # weights: traced, not baked in
        logits = ref.logits_at(weights, ids, pos, cfg, ops)
        got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
        return jnp.max(logits.max(-1) - got), jnp.std(logits)

    gaps, spreads = [], []
    with jax.default_matmul_precision("highest"):
        for r in reqs:
            toks = np.asarray(r.token_ids, np.int32)
            n_p = r.prompt_ids.size
            ids = np.zeros(z["hybrid_max_len"], np.int32)
            ids[:n_p] = r.prompt_ids
            ids[n_p:n_p + toks.size - 1] = toks[:-1]
            pos = np.arange(n_p - 1, n_p - 1 + toks.size, dtype=np.int32)
            g, sd = gap_of(weights, ids, pos, toks)
            gaps.append(float(g))
            spreads.append(float(sd))
    log(f"hybrid: {len(reqs)} requests, "
        f"{sum(len(r.token_ids) for r in reqs)} tokens in {serve_s:.1f}s "
        f"(smoke timing); widest gap to the reference "
        f"{max(gaps):.4f} on logits of spread {np.mean(spreads):.3f}; "
        f"state cache {snap['state_cache_bytes'] / 1e6:.1f} MB in "
        f"{snap['state_layers']} layers; dispatch tally {tally}")
    check(max(gaps) <= HYBRID_GAP_TOL,
          f"served tokens within {HYBRID_GAP_TOL} of the reference's best: "
          f"{gaps}")
    engine.shutdown()
    return {"requests": len(reqs), "logit_gap_max": max(gaps),
            "logit_std": float(np.mean(spreads)),
            "state_cache_bytes": snap["state_cache_bytes"],
            "ssm_dispatch": tally, "serve_s": round(serve_s, 2)}


def _sizes(rehearsal):
    from incubator_mxnet_tpu.models import gpt as gpt_mod
    if rehearsal:
        # gpt_mini: 2 layers, 128 units, 4 heads of 32, context 128
        return {"model": gpt_mod.gpt_mini, "H": 4, "D": 32,
                "ragged_H": 5,
                "dense_heads": (4,), "dense_T": 128, "stream_T": 640,
                "packed_cases": ((4, False), (2, True)),
                "max_len": 128, "ragged_lengths": (0, 1, 16, 17, 100),
                "prefill_cases": ((16, ((0, 16), (32, 5))),
                                  (32, ((64, 32),))),
                "train_B": 4, "train_T": 64,
                "chunk_pages": 2, "prefix_len": 32, "long_len": 70,
                "hybrid_config": "granite-tiny-rehearsal.json",
                "hybrid_max_len": 96, "hybrid_chunk_pages": 2,
                "hybrid_requests": ((9, 6), (40, 8), (70, 5), (33, 12),
                                    (17, 4), (50, 7))}
    # GPT-2-small: 12 layers, 768 units, 12 heads of 64, context 1024;
    # the dense flash pair also at BERT-large's 16 heads, the ragged
    # kernels over the fused page pool at GPT-2-XL's 25
    return {"model": gpt_mod.gpt_small, "H": 12, "D": 64,
            "ragged_H": 25,
            "dense_heads": (12, 16), "dense_T": 512, "stream_T": 1024,
            "packed_cases": ((16, False), (12, True)),
            "max_len": 1024,
            "ragged_lengths": (0, 1, 16, 17, 1000, 255, 512, 33),
            "prefill_cases": ((16, ((0, 16), (32, 5))),
                              (128, ((0, 128), (128, 44), (896, 104)))),
            "train_B": 16, "train_T": 512,
            "chunk_pages": 8, "prefix_len": 128, "long_len": 300,
            "hybrid_config": "granite-4.0-h-micro.json",
            "hybrid_max_len": 640, "hybrid_chunk_pages": 16,
            "hybrid_requests": ((9, 24), (40, 32), (300, 24), (530, 16),
                                (257, 24), (100, 48))}


LEGS = (("device", leg_device), ("kernels", leg_kernels),
        ("train", leg_train), ("serve", leg_serve),
        ("hybrid", leg_hybrid))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dress rehearsal at gpt_mini size with the "
                         "kernels in interpret mode; never prints the "
                         "success marker")
    ap.add_argument("--sharding", choices=("replicated", "fsdp"),
                    default="replicated",
                    help="leg 2's layout over all local devices")
    ap.add_argument("--legs", default=",".join(n for n, _ in LEGS),
                    help="comma-separated subset (the success marker "
                         "needs all five)")
    args = ap.parse_args(argv)
    chosen = args.legs.split(",")
    unknown = set(chosen) - {n for n, _ in LEGS}
    if unknown or "device" not in chosen:
        ap.error(f"--legs takes {[n for n, _ in LEGS]} and always "
                 f"includes 'device'")

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        os.environ["MXTPU_FLASH_INTERPRET"] = "1"
    import jax

    platform = jax.devices()[0].platform
    if args.rehearsal:
        if platform != "cpu":
            sys.exit(f"chip_smoke: FAIL leg=device: a rehearsal runs on "
                     f"the CPU, found platform={platform!r}")
    elif platform != "tpu":
        sys.exit(f"chip_smoke: FAIL leg=device: no TPU — "
                 f"jax.devices()[0].platform={platform!r}. Run through "
                 f"the chip tool, or ask for --rehearsal explicitly.")

    from incubator_mxnet_tpu.utils import compile_cache
    ctx = {"rehearsal": args.rehearsal, "sharding": args.sharding,
           "cache_dir": compile_cache.enable(),
           "size": _sizes(args.rehearsal)}
    clock = CompileClock()
    report = {"smoke_timings_not_metrics": True, "legs": {}}
    device = None
    for name, leg in LEGS:
        if name not in chosen:
            continue
        s0, h0, m0 = clock.snapshot()
        t0 = time.perf_counter()
        try:
            out = leg(ctx)
        except Exception as e:
            log(f"chip_smoke: FAIL leg={name}: {type(e).__name__}: {e}")
            if not isinstance(e, LegFailed):
                raise
            return 1
        wall = time.perf_counter() - t0
        s1, h1, m1 = clock.snapshot()
        if name == "device":
            device = out
        report["legs"][name] = dict(
            out, verdict="pass", compile_s=round(s1 - s0, 2),
            rest_s=round(wall - (s1 - s0), 2),
            cache_hits=h1 - h0, cache_misses=m1 - m0)
        log(f"chip_smoke: leg {name} passed (compile {s1 - s0:.1f}s, "
            f"rest {wall - (s1 - s0):.1f}s, cache hits {h1 - h0} "
            f"misses {m1 - m0}; smoke timings)")
        gc.collect()
    report["compile_s_total"] = round(clock.seconds, 2)
    report["cache_hits"], report["cache_misses"] = clock.hits, clock.misses
    print(json.dumps(report), flush=True)
    if args.rehearsal:
        print(f"rehearsal platform={platform} legs={','.join(chosen)} "
              f"passed — not a chip result", flush=True)
    elif len(chosen) == len(LEGS):
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Standalone attention-kernel micro-benchmark (TPU).

Times the Pallas attention paths WITHOUT the surrounding model: dense
single-tile kernels (default head-grouping and hpp=1) vs the streaming
FlashAttention-2 kernels vs the jnp blockwise fallback, fwd-only and
fwd+bwd, across sequence lengths. Seconds per data point after the
first compile — the cheap way to characterize kernels on the chip (the
full bench workloads cost minutes each).

Env knobs are flipped BETWEEN calls inside this one process; that is
sound because every knob (dense threshold, hpp, blocks) is resolved in
the non-jitted wrappers and threaded as a static jit arg, so each
setting retraces instead of hitting a stale cache entry.

Prints ONE JSON line: {"kernel_bench": [{...per config...}]}.
"""

import json
import os
import sys
import time


def _bench_one(T, reps=20):
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops.pallas_attention import (
        flash_attention_bhtd)

    # interpret mode off-TPU lets the harness self-check on CPU
    interp = not any(d.platform != "cpu" for d in jax.devices())
    B, H, D = 8, 12, 64
    kq = jax.random.PRNGKey(0)
    q, k, v = (jax.random.normal(jax.random.fold_in(kq, i),
                                 (B, H, T, D), jnp.bfloat16)
               for i in range(3))
    vl = jnp.full((B,), T, jnp.int32)
    g = jax.random.normal(jax.random.fold_in(kq, 9), (B, H, T, D),
                          jnp.bfloat16)

    # fresh jit-wrapped callables per _bench_one call: a new function
    # object forces a retrace, so the env knobs read by the non-jitted
    # inner wrappers are honored for THIS config (and eager per-op
    # dispatch never pollutes the timing)
    @jax.jit
    def _fwd_j(q_, k_, v_):
        return flash_attention_bhtd(q_, k_, v_, vl, False, None, interp)

    def _loss(q_, k_, v_):
        o = flash_attention_bhtd(q_, k_, v_, vl, False, None, interp)
        return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32))

    _bwd_j = jax.jit(jax.grad(_loss, argnums=(0, 1, 2)))

    def fwd():
        return _fwd_j(q, k, v)

    def fwdbwd():
        return _bwd_j(q, k, v)

    from incubator_mxnet_tpu.utils.flops import peak_flops_per_device
    peak = peak_flops_per_device()["flops"]
    out = {}
    for name, fn in (("fwd", fwd), ("fwdbwd", fwdbwd)):
        jax.block_until_ready(fn())                   # compile + fence
        t0 = time.perf_counter()
        for _ in range(reps):
            r = fn()
        jax.block_until_ready(r)
        dt = (time.perf_counter() - t0) / reps
        flops = (4 if name == "fwd" else 14) * B * H * T * T * D
        out[name] = {"ms": round(dt * 1e3, 3),
                     "mxu_pct": round(100 * flops / dt / peak, 1)}
    return out


def main():
    import jax
    if not any(d.platform != "cpu" for d in jax.devices()):
        print(json.dumps({"error": "no TPU visible"}))
        return 1

    results = []
    # (label, env overrides) — resolved per call in the non-jit wrappers
    configs = [
        ("dense-grouped-T512", 512, {}),
        ("dense-hpp1-T512", 512, {"MXTPU_FLASH_FWD_HPP": "1",
                                  "MXTPU_FLASH_BWD_HPP": "1"}),
        ("streaming-T512", 512, {"MXTPU_FLASH_DENSE_T": "0"}),
        ("jnpfallback-T512", 512, {"MXTPU_FLASH_FORCE_FALLBACK": "1"}),
        ("dense-grouped-T1024", 1024, {"MXTPU_FLASH_DENSE_T": "1024"}),
        ("streaming-T1024", 1024, {"MXTPU_FLASH_DENSE_T": "0"}),
        ("streaming-T2048", 2048, {"MXTPU_FLASH_DENSE_T": "0"}),
    ]
    saved = {}
    for label, T, env in configs:
        for k_, v_ in env.items():
            saved.setdefault(k_, os.environ.get(k_))
            os.environ[k_] = v_
        try:
            r = _bench_one(T)
            results.append({"config": label, "T": T, **r})
        except Exception as e:          # a failing variant must not
            results.append({"config": label, "T": T,   # kill the rest
                            "error": f"{type(e).__name__}: {e}"[:300]})
        finally:
            for k_ in env:
                if saved.get(k_) is None:
                    os.environ.pop(k_, None)
                else:
                    os.environ[k_] = saved[k_]
        # cumulative line after EVERY config: a timeout mid-run still
        # leaves the last complete JSON
        print(json.dumps({"kernel_bench": results}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""The chip: refusing to run without one, the compile cache, memory, tracing."""

import os
import shutil
import sys
import time

from . import manifest, peaks


def log(msg):
    print(f"[bench {time.perf_counter():9.2f}] {msg}", file=sys.stderr,
          flush=True)


def enable_compile_cache():
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR`` if
    that is set, else at a fixed path inside the checkout (the path is part of
    the cache's key). Every program is kept, however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(manifest.ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_chips(n_chips, rehearsal):
    """The devices this cell runs on; exits non-zero where JAX finds no
    accelerator or fewer chips than the cell asks for."""
    import jax
    devs = jax.devices()
    if rehearsal:
        if len(devs) < n_chips:
            raise SystemExit(f"benchmark: rehearsal needs {n_chips} devices, "
                             f"have {len(devs)}")
        return devs[:n_chips]
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU (platform "
                         f"{devs[0].platform!r}); a cell has no CPU form")
    if len(devs) < n_chips:
        raise SystemExit(f"benchmark: cell needs {n_chips} chips, JAX "
                         f"reports {len(devs)}")
    peaks.peak(devs[0].device_kind)
    return devs[:n_chips]


def device_block(devs):
    stats = [d.memory_stats() or {} for d in devs]
    peak_bytes = max((s.get("peak_bytes_in_use", 0) for s in stats),
                     default=0)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak_bytes)}


class Tracer:
    """``jax.profiler`` around a stretch of the run, written under the
    checkout's ``benchmark_out/`` and removed once it has been reduced."""

    def __init__(self, name):
        safe = "".join(ch if ch.isalnum() else "_" for ch in name)
        self.dir = os.path.join(manifest.ROOT, "benchmark_out", "trace_" + safe)
        self.window_s = None
        self._t0 = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        self._t0 = time.perf_counter()

    def stop(self):
        import jax
        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def reduce(self, keep_fixture=None):
        from . import trace
        raw = trace.load_xplane(self.dir)
        if raw is None:
            raise SystemExit("benchmark: the profiler wrote no trace")
        if keep_fixture:
            os.makedirs(os.path.dirname(keep_fixture), exist_ok=True)
            trace.save_fixture(raw, keep_fixture)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace.Reduced(raw, self.window_s)


def annotate(name):
    import jax
    return jax.profiler.TraceAnnotation(name)

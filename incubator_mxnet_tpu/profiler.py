"""Profiler.

Re-design of `src/profiler/profiler.{h,cc}` + `python/mxnet/profiler.py`
(file-level citations — SURVEY.md caveat). The reference instruments its
dependency engine around every op dispatch and dumps Chrome trace-event
JSON plus an aggregate stats table (SURVEY.md §5.1).

TPU-native split of responsibilities:

  - **device timeline** → ``jax.profiler`` (XLA's own tracing; TensorBoard/
    perfetto output). ``set_config(profile_all=True)`` + ``start()/stop()``
    drive it; ``mx.profiler.scope``/`named_scope` annotate regions so HLO
    ops attribute to model layers.
  - **host-side events** → recorded here (scoped ``ProfileEvent``s, counters)
    and dumped as Chrome trace-event JSON via ``dump()`` — same format the
    reference emits, loadable in chrome://tracing or perfetto.
  - **aggregate table** → ``dumps()`` (parity: `MXAggregateProfileStatsPrint`
    / ``profiler.dumps()``), per-name count/total/min/max/avg.
  - **compile events** → ``compile_events()``: what ``jax.monitoring``
    reports of tracing, lowering, backend compiles and the persistent
    cache, each with its ``perf_counter`` stamp, in a bounded list
    (docs/OBSERVABILITY.md "Compile events"); beside it
    ``attention_dispatch()``: which kernels each attention call site got.
  - ``mfu(...)`` — model-FLOPs-utilisation meter for the north-star metric
    (SURVEY.md §6); no reference analogue, TPU-specific addition.

Env autostart parity: ``MXTPU_PROFILER_AUTOSTART=1`` (reference:
`MXNET_PROFILER_AUTOSTART`).
"""

from __future__ import annotations

import functools
import json
import os
import re
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from typing import Dict, List, Optional

from .base import MXNetError

from .base import getenv_bool

__all__ = ["set_config", "start", "stop", "dump", "dumps", "pause", "resume",
           "scope", "scoped", "ProfileEvent", "Counter", "Marker", "mfu",
           "state_string", "session_live", "scope_table",
           "compile_events", "compile_counts", "attention_dispatch",
           "ssm_dispatch"]

_lock = threading.Lock()
_config = {
    "filename": "profile.json",
    "profile_all": False,
    "profile_symbolic": True,
    "profile_imperative": True,
    "profile_memory": False,
    "profile_api": False,
    "aggregate_stats": True,
    "tensorboard_logdir": None,
}
_running = False
_paused = False
_device_trace_active = False
_events: List[dict] = []
_agg: Dict[str, List[float]] = defaultdict(list)
_t0 = time.perf_counter()


def set_config(**kwargs) -> None:
    """Parity: ``mx.profiler.set_config`` (`MXSetProcessProfilerConfig`).
    Unknown keys are accepted and ignored for drop-in compatibility."""
    _config.update(kwargs)


def _now_us() -> float:
    # mxlint: allow-trace-host-leak(``scope`` times its region on the host; inside a traced function that is the trace itself, which is what a host event of a scope says)
    return (time.perf_counter() - _t0) * 1e6


def start() -> None:
    """Begin profiling (parity: ``mx.profiler.set_state('run')``). Starts the
    XLA device trace too when a tensorboard_logdir is configured."""
    global _running, _device_trace_active
    with _lock:
        _running = True
        logdir = _config.get("tensorboard_logdir")
        if logdir and not _device_trace_active:
            import jax

            jax.profiler.start_trace(logdir)
            _device_trace_active = True


def stop() -> None:
    """Parity: ``mx.profiler.set_state('stop')``."""
    global _running, _device_trace_active
    with _lock:
        _running = False
        if _device_trace_active:
            import jax

            jax.profiler.stop_trace()
            _device_trace_active = False


def pause() -> None:
    global _paused
    _paused = True


def resume() -> None:
    global _paused
    _paused = False


def state_string() -> str:
    return "run" if _running else "stop"


def session_live() -> bool:
    """True while a ``jax.profiler`` trace session is open in this
    process, whoever opened it (``start()`` here, ``jax.profiler.trace``,
    a benchmark's tracer). One C++ flag read, some 80 ns."""
    import jax

    return jax.profiler.TraceAnnotation.is_enabled()


def is_running() -> bool:
    """Host events are recorded: after ``start()``, or while a
    ``jax.profiler`` session is live; never while paused."""
    return (_running or session_live()) and not _paused


def _record(name: str, cat: str, t_start_us: float, dur_us: float) -> None:
    with _lock:
        _events.append({"name": name, "cat": cat, "ph": "X",
                        "ts": t_start_us, "dur": dur_us,
                        "pid": os.getpid(),
                        "tid": threading.get_ident() % 1_000_000})
        if _config["aggregate_stats"]:
            _agg[name].append(dur_us)


@contextmanager
def scope(name: str, cat: str = "operator"):
    """Scoped profiling region. Host-side timing is recorded when the
    profiler runs; the region is ALWAYS forwarded to ``jax.named_scope`` so
    XLA device traces attribute HLO to it (SURVEY.md §5.1 TPU equivalent)."""
    import jax

    with jax.named_scope(name):
        if not is_running():
            yield
            return
        t = _now_us()
        try:
            yield
        finally:
            _record(name, cat, t, _now_us() - t)


def scoped(name: str, fn):
    """``fn`` with every call under ``scope(name)``: for a function handed
    to other code whole (a pipeline's stem or head)."""

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with scope(name):
            return fn(*args, **kwargs)
    return wrapped


_HLO_NAME = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MX_SCOPE = re.compile(r"mx\.[a-z_]+")


def scope_table(hlo_text: str) -> Dict[str, tuple]:
    """``{HLO instruction name: (scope, direction)}`` from a compiled
    program's text. ``scope`` is the innermost ``mx.`` name of the
    instruction's ``op_name`` metadata (what ``scope`` left on it at trace
    time), ``""`` where it has none; ``direction`` is ``"bwd"`` where the
    op_name passes through ``transpose(`` (the backward pass, its
    rematerialized forward included), ``"fwd"`` where only through
    ``jvp(``, else ``""`` (optimizer, guard). A fusion carries its root's
    op_name. Instruction names are what a device trace calls its events,
    so the table attributes trace time to model parts."""
    table = {}
    for line in hlo_text.splitlines():
        m = _HLO_NAME.match(line)
        if m is None:
            continue
        meta = _HLO_OP_NAME.search(line)
        op_name = meta.group(1) if meta else ""
        scopes = _MX_SCOPE.findall(op_name)
        direction = "bwd" if "transpose(" in op_name else \
            "fwd" if "jvp(" in op_name else ""
        table[m.group(1)] = (scopes[-1] if scopes else "",
                             direction if scopes else "")
    return table


class ProfileEvent:
    """Manually started/stopped event (parity: `profiler::ProfileEvent`)."""

    def __init__(self, name: str, cat: str = "event"):
        self.name = name
        self.cat = cat
        self._t = None

    def start(self):
        self._t = _now_us()

    def stop(self):
        if self._t is not None and is_running():
            _record(self.name, self.cat, self._t, _now_us() - self._t)
        self._t = None


class Task(ProfileEvent):
    """Named task duration event (parity: `profiler.Task` — a domain-
    scoped ProfileEvent; domains are a labeling concept here)."""

    def __init__(self, domain=None, name: str = "task"):
        if isinstance(domain, str) and name == "task":
            domain, name = None, domain  # tolerate Task("name")
        super().__init__(name, cat=getattr(domain, "name", None)
                         or (domain if isinstance(domain, str)
                             else "task"))


class Frame(ProfileEvent):
    """Named frame duration event (parity: `profiler.Frame`)."""

    def __init__(self, domain=None, name: str = "frame"):
        if isinstance(domain, str) and name == "frame":
            domain, name = None, domain
        super().__init__(name, cat=getattr(domain, "name", None)
                         or (domain if isinstance(domain, str)
                             else "frame"))


class Domain:
    """Profiling category label (parity: `profiler.Domain`)."""

    def __init__(self, name: str):
        self.name = name

    def __repr__(self):
        return f"Domain({self.name!r})"


def set_state(state="stop", profile_process="worker"):
    """start/stop by name (parity: profiler.set_state)."""
    if state in ("run", "start"):
        start()
    elif state == "stop":
        stop()
    else:
        raise MXNetError(f"profiler.set_state: unknown state {state!r}")


class Counter:
    """Named monotonically-adjustable counter (parity: `ProfileCounter`)."""

    def __init__(self, name: str, value: int = 0):
        self.name = name
        self.value = value
        self._emit()

    def _emit(self):
        if is_running():
            with _lock:
                _events.append({"name": self.name, "ph": "C",
                                "ts": _now_us(), "pid": os.getpid(),
                                "args": {"value": self.value}})

    def increment(self, delta: int = 1):
        self.value += delta
        self._emit()

    def decrement(self, delta: int = 1):
        self.value -= delta
        self._emit()

    def set_value(self, value: int):
        self.value = value
        self._emit()


class Marker:
    """Instant event (parity: `ProfileMarker` / instant markers)."""

    def __init__(self, name: str, cat: str = "marker"):
        self.name = name
        self.cat = cat

    def mark(self, scope_: str = "process"):
        if is_running():
            with _lock:
                _events.append({"name": self.name, "cat": self.cat,
                                "ph": "i", "ts": _now_us(),
                                "s": {"process": "p", "thread": "t",
                                      "global": "g"}.get(scope_, "p"),
                                "pid": os.getpid()})


def dump(finished: bool = True, filename: Optional[str] = None) -> str:
    """Write Chrome trace-event JSON (parity: ``mx.profiler.dump`` →
    `trace.json`). Returns the path written."""
    path = filename or _config["filename"]
    with _lock:
        payload = {"traceEvents": list(_events), "displayTimeUnit": "ms"}
        if finished:
            _events.clear()
    with open(path, "w") as f:
        json.dump(payload, f)
    return path


def dumps(reset: bool = False) -> str:
    """Aggregate stats table (parity: ``mx.profiler.dumps`` /
    `MXAggregateProfileStatsPrint`)."""
    with _lock:
        rows = []
        for name, durs in sorted(_agg.items()):
            n = len(durs)
            tot = sum(durs)
            rows.append((name, n, tot / 1e3, min(durs) / 1e3,
                         max(durs) / 1e3, tot / n / 1e3))
        if reset:
            _agg.clear()
    header = (f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
              f"{'Max(ms)':>10}{'Avg(ms)':>10}")
    lines = [header, "-" * len(header)]
    for name, n, tot, mn, mx_, avg in rows:
        lines.append(f"{name:<40}{n:>8}{tot:>12.3f}{mn:>10.3f}"
                     f"{mx_:>10.3f}{avg:>10.3f}")
    return "\n".join(lines)


def mfu(model_flops_per_step: float, step_time_s: float,
        n_chips: int = 1, peak_flops_per_chip: Optional[float] = None) -> float:
    """Model-FLOPs-utilisation: achieved FLOP/s over peak (north-star metric,
    SURVEY.md §6). ``peak_flops_per_chip`` defaults to the local device's
    row of the one peak table (``utils.flops.peak_flops_per_device``; an
    unknown accelerator raises)."""
    if peak_flops_per_chip is None:
        from .utils.flops import peak_flops_per_device
        peak_flops_per_chip = peak_flops_per_device()["flops"]
    return model_flops_per_step / step_time_s / (n_chips * peak_flops_per_chip)


# --------------------------------------------------------------------- #
# compile events, from JAX's own instrumentation
# --------------------------------------------------------------------- #

# jax.monitoring's names (jax 0.9.0) -> the short ``kind`` kept here
_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval",
}
_COMPILE_COUNTS = {
    "/jax/compilation_cache/cache_hits": "cache_hit",
    "/jax/compilation_cache/cache_misses": "cache_miss",
}
_COMPILE_RING = 65536
_compile_events: deque = deque(maxlen=_COMPILE_RING)
_compile_counts: Dict[str, int] = defaultdict(int)


def _keep_compile_event(kind: str, dur_s: float) -> None:
    # deque.append and a dict increment under the interpreter lock: the
    # listeners run on whichever thread compiled
    _compile_events.append((time.perf_counter(), kind, dur_s))
    _compile_counts[kind] += 1


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    kind = _COMPILE_DURATIONS.get(event)
    if kind is not None:
        _keep_compile_event(kind, float(duration_secs))


def _on_event(event: str, **_kw) -> None:
    kind = _COMPILE_COUNTS.get(event)
    if kind is not None:
        _keep_compile_event(kind, 0.0)


def _register_compile_listeners() -> None:
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def compile_events() -> List[dict]:
    """The trailing compile events of this process, oldest first:
    ``{"ts", "kind", "dur_s"}`` with ``ts`` the ``time.perf_counter()``
    reading when JAX reported the event (its END: a duration event spans
    ``ts - dur_s`` to ``ts``) and ``kind`` one of ``trace`` (a function
    traced to a jaxpr; inner jits report their own, nested in the outer
    one's), ``lower`` (jaxpr to MLIR module), ``backend_compile`` (the
    backend's compile OR its load from the persistent cache),
    ``cache_retrieval``, ``cache_hit``, ``cache_miss`` (counts,
    ``dur_s`` 0). Bounded: the last 65536 (a first step of 24 layers reports some thousands of nested traces)."""
    return [{"ts": ts, "kind": kind, "dur_s": dur}
            for ts, kind, dur in list(_compile_events)]


def compile_counts() -> Dict[str, int]:
    """Lifetime count of each kind of compile event (the list wraps)."""
    return dict(_compile_counts)


def attention_dispatch(reset: bool = False) -> Dict[str, int]:
    """Which implementation each attention call site got, counted as the
    sites were traced: ``dense_packed`` (the dense flash pair in the
    projection's (B, T, 3*H*D) layout), ``dense_bhtd`` (the (B, H, T, D)
    dense pair), ``stream_bhtd`` (the streaming kernels),
    ``blockwise_jnp`` (no kernel). The tally is
    ``ops.pallas_attention``'s; read it after a first step, beside
    ``compile_events()``."""
    from .ops.pallas_attention import dispatch_tally
    return dispatch_tally(reset=reset)


def ssm_dispatch(reset: bool = False) -> Dict[str, int]:
    """Which implementation each state-space call site got, counted as
    the sites were traced: ``ssm_decode_pallas`` (the one-token state
    update kernel ``mxtpu_ssm_decode``), ``ssm_decode_jnp`` (its twin,
    off the TPU), ``ssm_chunk_jnp`` (the chunked scan of a prefill
    chunk or a whole sequence). The tally is ``ops.ssm_scan``'s."""
    from .ops.ssm_scan import dispatch_tally
    return dispatch_tally(reset=reset)


_register_compile_listeners()

if getenv_bool("MXTPU_PROFILER_AUTOSTART"):
    set_config(profile_all=True)
    start()

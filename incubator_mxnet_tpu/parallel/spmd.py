"""Fused SPMD training: forward + backward + gradient reduction + optimizer
update as ONE jitted XLA program over a device mesh.

This is the TPU-native replacement for the reference's entire hot training
path (SURVEY.md §3.2): Gluon's eager fwd/bwd + `Trainer._allreduce_grads`
(KVStore push/pull over NCCL/ps-lite) + per-param optimizer ops collapse
into a single compiled step. Gradient reduction needs no explicit psum —
parameters are replicated (or FSDP-sharded) and the batch is sharded over
the ``dp`` axis, so XLA inserts the all-reduce/reduce-scatter on ICI/DCN
itself and overlaps it with the backward pass (the reference's P3 priority
propagation, compiler-scheduled — SURVEY.md §2.3).

Sharding modes:
  - ``replicated``: pure data parallelism (reference kvstore=`device`/`nccl`)
  - ``fsdp``: parameters/optimizer state sharded over the ``fsdp`` axis
    (ZeRO-style; beyond reference capability but idiomatic on TPU)
  - per-Parameter ``PartitionSpec`` hints (``Parameter._sharding``) override
    both — used by models/ for tensor parallelism.
"""

from __future__ import annotations

import contextlib
import contextvars
import time
import weakref
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as _host_np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import autograd, profiler, random as _random
from ..base import MXNetError, getenv_bool
from ..ndarray import NDArray
from ..optimizer import create as opt_create
from ..train.outcomes import StepOutcome, StepRecorder
from . import mesh as _mesh

__all__ = ["SPMDTrainer", "shard_params", "replicate", "constrain",
           "activation_sharding_scope", "live_trainers"]

_LIVE_TRAINERS: "weakref.WeakSet[SPMDTrainer]" = weakref.WeakSet()


def live_trainers() -> List["SPMDTrainer"]:
    """The ``SPMDTrainer``s alive in this process: what a debug hook or a
    benchmark's reader walks to reach a trainer it was not handed (its
    ``flight`` events, ``scope_table()``, ``health_snapshot()``). Held
    weakly: a trainer that is dropped is forgotten."""
    return list(_LIVE_TRAINERS)


_NO_SPAN = contextlib.nullcontext()


def _host_spans():
    """The context manager a step wraps each host phase in: a
    ``jax.profiler.TraceAnnotation`` while a profiler session is live, so
    the phases lie beside the device's ops in its trace; else nothing."""
    if profiler.session_live():
        return jax.profiler.TraceAnnotation
    return lambda name: _NO_SPAN

# Mesh active while SPMDTrainer traces the fused step — models call
# ``constrain`` on activations against it (a no-op everywhere else).
_ACTIVE_MESH: contextvars.ContextVar[Optional[Mesh]] = \
    contextvars.ContextVar("mxtpu_active_mesh", default=None)


@contextlib.contextmanager
def activation_sharding_scope(mesh: Mesh):
    tok = _ACTIVE_MESH.set(mesh)
    try:
        yield
    finally:
        _ACTIVE_MESH.reset(tok)


def constrain(x, *spec, mesh: Optional[Mesh] = None):
    """Pin an activation's sharding inside the fused SPMD step
    (``lax.with_sharding_constraint`` against the trainer's mesh, or an
    explicitly passed ``mesh``).

    Models sprinkle this on attention/FFN activations so the partitioner
    never falls back to replicate-then-repartition between fsdp-placed
    and tp-hinted params (VERDICT r2 weak #3). Each ``spec`` entry is an
    axis name, a tuple of axis names, or None; axes absent from the mesh
    or of size 1 are dropped, and with no mesh (active or given) the
    call returns ``x`` unchanged — so model code is mesh-agnostic.

    NOTE: a combined batch entry over {dp, fsdp} is CANONICALIZED to
    ``("fsdp", "dp")`` regardless of the order the caller wrote — the
    batch dim is semantically "sharded over both", and fsdp-major is the
    natural tile order of every fsdp-derived NamedSharding, so a single
    canonical order here keeps batch constraints permutation-compatible
    with the fsdp all-gather (the dp>=4 full-remat fix, PERF_NOTES
    round 6). Callers needing dp-major tiles for this axis pair must
    call ``lax.with_sharding_constraint`` directly."""
    if mesh is None:
        mesh = _ACTIVE_MESH.get()
    if mesh is None:
        return x
    entries = []
    for e in spec:
        axes = tuple(e) if isinstance(e, (tuple, list)) else \
            ((e,) if e is not None else ())
        kept = tuple(a for a in axes
                     if a in mesh.shape and mesh.shape[a] > 1)
        if set(kept) == {"dp", "fsdp"}:
            kept = ("fsdp", "dp")
        entries.append(kept if len(kept) > 1 else
                       (kept[0] if kept else None))
    if all(e is None for e in entries):
        return x
    is_nd = isinstance(x, NDArray)
    val = x._data if is_nd else x
    entries += [None] * (val.ndim - len(entries))
    out = jax.lax.with_sharding_constraint(
        val, NamedSharding(mesh, PartitionSpec(*entries)))
    return NDArray(out) if is_nd else out


def replicate(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


def _fsdp_spec(shape, mesh: Mesh,
               base: Optional[PartitionSpec] = None) -> PartitionSpec:
    """Shard the largest divisible still-unsharded dim over the fsdp axis.

    ``base`` (e.g. a tp hint from the model) is preserved: fsdp extends it
    on a free dim instead of fighting it — keeping param layouts
    consistent so the partitioner never reshards activations between
    tp-hinted and fsdp-placed params (VERDICT r2 weak #3)."""
    import os
    size = mesh.shape["fsdp"]
    entries = list(base) if base is not None else []
    entries += [None] * (len(shape) - len(entries))
    n_elems = 1
    for s in shape:
        n_elems *= s
    min_elems = int(os.environ.get("MXTPU_FSDP_MIN_SIZE", "16384"))
    if size == 1 or (base is None and (len(shape) < 2
                                       or n_elems < min_elems)):
        # rank-1 params (biases, layernorm scales) and small tensors stay
        # replicated: the bytes saved are trivial and sharding them forces
        # the partitioner to reshard every activation that touches them
        return PartitionSpec(*entries) if base is not None \
            else PartitionSpec()
    used = {a for e in entries if e is not None
            for a in (e if isinstance(e, tuple) else (e,))}
    if "fsdp" in used:
        return PartitionSpec(*entries)
    # prefer EARLIER dims (vocab for embeddings, out-features for Dense):
    # sharding a trailing feature dim makes every lookup/matmul output
    # feature-sharded, which fights the batch-sharded activation layout
    for d in range(len(shape)):
        if entries[d] is None and shape[d] % size == 0 and shape[d] >= size:
            entries[d] = "fsdp"
            break
    return PartitionSpec(*entries)


def _fit_spec(shape, spec: PartitionSpec, mesh: Mesh) -> PartitionSpec:
    """Drop, last first, the mesh axes a dimension cannot be split over
    evenly. A hint is written against a family of shapes; GPT-2's
    50257-row embedding under ``P(("tp", "fsdp"), None)`` on fsdp=4 is
    not divisible (first contact on four chips, 2026-09), and a freed
    ``fsdp`` then lands on another dim in ``_fsdp_spec``."""
    import math
    entries = []
    for dim, e in zip(shape, spec):
        axes = list(e) if isinstance(e, (tuple, list)) else \
            ([e] if e is not None else [])
        while axes and dim % math.prod(mesh.shape.get(a, 1)
                                       for a in axes):
            axes.pop()
        entries.append(tuple(axes) if len(axes) > 1 else
                       (axes[0] if axes else None))
    return PartitionSpec(*entries)


def _param_sharding(p, mesh: Mesh, mode: str) -> NamedSharding:
    hint = getattr(p, "_sharding", None)
    if hint is not None:
        hint = _fit_spec(p.shape, PartitionSpec(*hint), mesh)
    if mode == "fsdp":
        return NamedSharding(mesh, _fsdp_spec(p.shape, mesh, base=hint))
    if hint is not None:
        return NamedSharding(mesh, hint)
    return NamedSharding(mesh, PartitionSpec())


def shard_params(block, mesh: Mesh, mode: str = "replicated"):
    """Place every initialized Parameter of ``block`` onto ``mesh`` with its
    resolved sharding (eager re-placement; the jitted step then runs with
    arrays already resident)."""
    multiproc = jax.process_count() > 1
    for p in block.collect_params().values():
        if p._data is None:
            continue
        sh = _param_sharding(p, mesh, mode)
        arr = p._data._data
        if multiproc and len(arr.devices()) == 1:
            # promote a process-local array to the multi-host mesh: go
            # through the host copy (identical on every process — SPMD
            # programs compute the same init on each rank), the only
            # legal source for a cross-process device_put
            arr = _np_host(arr)
        p._data._data = jax.device_put(arr, sh)


def _np_host(arr):
    import numpy as _np
    return _np.asarray(arr)


class SPMDTrainer:
    """One-fused-step trainer over a mesh (the Trainer fast path).

    Parameters
    ----------
    block : HybridBlock — the model (must be initialized, shapes known).
    loss : callable ``loss(out, *labels) -> NDArray`` (a gluon loss works).
    optimizer : str | Optimizer, with ``optimizer_params`` as for Trainer.
    mesh : jax mesh (default: all devices on ``dp``).
    sharding : 'replicated' | 'fsdp'.
    forward_loss : optional ``fn(block, *batch) -> scalar NDArray`` override
        for models whose loss is not ``loss(block(x), y)`` (e.g. BERT MLM).
    pipeline : optional ``parallel.pipelined.PipelineSpec`` — run the
        step as the pipelined-backward program with in-program bucket
        collectives interleaved between block pullbacks (ROADMAP item 5;
        bit-identical to the GSPMD step on clean streams, asserted in
        tests). ``forward_loss``/``loss`` are ignored when set: the
        spec's head/finalize ARE the loss.
    int8_allreduce : traced in-program int8 gradient all-reduce
        (quantize → psum int32 codes → dequantize, per-bucket scale;
        the PR-11 compression promoted from host-side seam to program
        ops). Default from ``MXTPU_INT8_ALLREDUCE``. Pipeline-only.
    grad_collective : 'psum' (default) or 'ring' — how pipelined bucket
        collectives are emitted; 'ring' uses a collective-permute chunk
        ring for schedulers that cluster all-reduce ops. Env:
        ``MXTPU_GRAD_COLLECTIVE``.
    remat_plan : optional per-pipeline-block remat policy list (entries
        False | True | 'dots'), e.g. from
        ``models._remat.plan_remat_from_profile`` fed by
        ``trace_summary overlap_stats``. Pipeline-only.
    """

    def __init__(self, block, loss=None, optimizer="sgd",
                 optimizer_params=None, mesh: Optional[Mesh] = None,
                 sharding: str = "replicated",
                 forward_loss: Optional[Callable] = None,
                 donate: bool = True, loss_scaler=None,
                 guard: Optional[bool] = None,
                 max_consecutive_nonfinite: Optional[int] = None,
                 pipeline=None, int8_allreduce: Optional[bool] = None,
                 grad_collective: Optional[str] = None,
                 remat_plan: Optional[Sequence] = None):
        if loss is None and forward_loss is None and pipeline is None:
            raise MXNetError("provide loss, forward_loss or pipeline")
        self.block = block
        self.loss = loss
        self.forward_loss = forward_loss
        self.mesh = mesh if mesh is not None else _mesh.default_mesh()
        self.sharding_mode = sharding
        self.donate = donate
        # round-13 resilience (docs/RESILIENCE.md "Training resilience"):
        # the fused step carries an all-finite guard over the gradients
        # as pure traced data (a where-select skip — no retrace, and the
        # skip decision is GLOBAL because the reduction runs inside the
        # SPMD program: every rank sees the same flag by construction);
        # the dynamic loss scale rides as a traced scalar input.
        if guard is None:
            guard = getenv_bool("MXTPU_STEP_GUARD", True)
        self.guard = bool(guard)
        self.loss_scaler = loss_scaler
        if loss_scaler is not None and not self.guard:
            import warnings
            warnings.warn(
                "loss_scaler attached but the in-step guard is off — "
                "overflow detection never fires, so the scale would "
                "only ever grow; scale updates are disabled",
                UserWarning, stacklevel=2)
        self._recorder = StepRecorder(max_consecutive_nonfinite)
        self.step_trace_count = 0    # fused-step compiles (jit-once)
        # round 16 (docs/TRAINING_PERF.md): in-step traced gradient
        # accumulation — ONE once-compiled microbatch program whose
        # accumulation count is pure host data (see step_microbatches)
        self.accum_step_trace_count = 0
        # round 19 (ROADMAP item 5): pipelined-backward step with
        # in-program bucket collectives (parallel/pipelined.py)
        self._pipeline = pipeline
        if int8_allreduce is None:
            int8_allreduce = getenv_bool("MXTPU_INT8_ALLREDUCE", False)
        self._int8_allreduce = bool(int8_allreduce)
        if grad_collective is None:
            import os
            grad_collective = os.environ.get(
                "MXTPU_GRAD_COLLECTIVE", "psum")
        if grad_collective not in ("psum", "ring"):
            raise MXNetError(
                f"grad_collective must be 'psum' or 'ring', got "
                f"{grad_collective!r}")
        if grad_collective == "ring" and self._int8_allreduce:
            raise MXNetError(
                "int8_allreduce composes with grad_collective='psum' "
                "only (the ring carries f32 chunks)")
        self._grad_collective = grad_collective
        self._remat_plan = list(remat_plan) if remat_plan is not None \
            else None
        if pipeline is None and (self._int8_allreduce
                                 or remat_plan is not None):
            raise MXNetError(
                "int8_allreduce / remat_plan require pipeline= (the "
                "GSPMD step has no in-program collective seam)")
        self.pipelined_step_trace_count = 0
        self.pipelined_accum_step_trace_count = 0
        self.pipelined_issue_ledger = None   # set at trace time
        self.pipelined_bucket_order = None
        self._pipe_lowering = False          # suppress counters in .lower
        self._pipe_example_args = None       # ShapeDtypeStruct snapshot
        self._pipe_example_accum_args = None
        self._accum_step_fn = None
        self._accum_bufs = None      # f32 grad accumulators (jax arrays)
        self._accum_ok = None        # carried combined-verdict scalar
        self._accum_loss = None      # carried loss-sum scalar
        self.last_accum_count = 0    # k of the last accumulated round

        params = list(block.collect_params().values())
        not_ready = [p.name for p in params
                     if p._data is None and p._deferred_init is None]
        if not_ready:
            raise MXNetError(
                f"uninitialized parameters: {not_ready}; call "
                f"block.initialize() first")
        self._params = params
        self._train_idx = [i for i, p in enumerate(params)
                           if p.grad_req != "null"]

        if isinstance(optimizer, str):
            pd = {p.name: p for p in params}
            self._optimizer = opt_create(
                optimizer, param_dict=pd,
                param_idx2name={i: params[i].name
                                for i in range(len(params))},
                **(optimizer_params or {}))
        else:
            self._optimizer = optimizer

        self._step_fn = None
        self._opt_state = None  # list aligned with self._train_idx
        self.step_count = 0
        _LIVE_TRAINERS.add(self)

    # ------------------------------------------------------------------ #
    @property
    def learning_rate(self):
        return self._optimizer.learning_rate

    def set_learning_rate(self, lr):
        self._optimizer.set_learning_rate(lr)

    # -- resilience surface (docs/RESILIENCE.md, round 13) --------------- #
    @property
    def health(self) -> dict:
        return self._recorder.health

    @property
    def last_outcome(self):
        return self._recorder.last_outcome

    @property
    def flight(self):
        """The flight recorder that holds this trainer's ``TRAIN_STEP``
        events (``flight.events("trainer")``)."""
        return self._recorder.flight

    def health_snapshot(self) -> dict:
        snap = self._recorder.snapshot()
        snap["compile_events"] = profiler.compile_counts()
        snap["loss_scale"] = (None if self.loss_scaler is None
                              else float(self.loss_scaler.loss_scale))
        snap["guard"] = self.guard
        snap["step_trace_count"] = self.step_trace_count
        snap["accum_step_trace_count"] = self.accum_step_trace_count
        snap["last_accum_count"] = self.last_accum_count
        if self._pipeline is not None:
            snap["pipelined"] = True
            snap["pipelined_step_trace_count"] = \
                self.pipelined_step_trace_count
            snap["pipelined_accum_step_trace_count"] = \
                self.pipelined_accum_step_trace_count
            snap["pipelined_bucket_order"] = self.pipelined_bucket_order
            snap["grad_collective"] = self._grad_collective
            snap["int8_allreduce"] = self._int8_allreduce
        return snap

    # -- pipelined-step structure surface (parallel/pipelined.py) ------- #
    @staticmethod
    def _abstract_args(args, static=frozenset()):
        """Freeze a call's arguments as ShapeDtypeStructs (static
        positions kept verbatim) so `.lower()` can re-derive the HLO
        later without holding donated buffers alive. Each keeps the
        sharding of an array that is committed to one (parameters and
        state; a batch fresh from the host is not): the abstract call
        then has the signature of the call that ran, and `.lower()`
        finds that call's jaxpr and module in JAX's caches and traces
        nothing again."""

        def _abs(a):
            sharding = a.sharding if getattr(a, "committed", False) \
                else None
            return jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a),
                                        sharding=sharding)
        return tuple(
            a if pos in static else jtu.tree_map(_abs, a)
            for pos, a in enumerate(args))

    def pipelined_hlo(self, accum: bool = False) -> str:
        """Lowered StableHLO text of the pipelined step program (the
        substrate of the structural overlap assertion). Requires one
        prior dispatch (step / step_microbatches) so the example
        signature exists. The re-trace for lowering is excluded from
        the trace counters (`_pipe_lowering`)."""
        if self._pipeline is None:
            raise MXNetError("pipelined_hlo: trainer has no pipeline=")
        fn = self._accum_step_fn if accum else self._step_fn
        args = self._pipe_example_accum_args if accum \
            else self._pipe_example_args
        if fn is None or args is None:
            raise MXNetError(
                "pipelined_hlo: run one step first (the lowering "
                "snapshot is captured at first dispatch)")
        self._pipe_lowering = True
        try:
            return fn.lower(*args).as_text()
        finally:
            self._pipe_lowering = False

    def compiled_step_text(self) -> str:
        """Optimized HLO text of the fused step as the backend compiled
        it — kernels included, so ``chip_smoke.py`` reads the Mosaic
        custom calls and the collectives out of the program that ran
        rather than out of a flag. Requires one prior ``step``. The
        lowering is the one the step's own call left in JAX's caches
        (should it trace again, the trace counters are put back), and
        with the persistent compile cache on the compile is a cache
        hit: the text is that of the executable that ran."""
        if self._step_fn is None or self._pipe_example_args is None:
            raise MXNetError("compiled_step_text: run one step first")
        count = self.step_trace_count
        self._pipe_lowering = True      # the pipelined body's own guard
        try:
            return self._step_fn.lower(
                *self._pipe_example_args).compile().as_text()
        finally:
            self._pipe_lowering = False
            self.step_trace_count = count

    def scope_table(self) -> Dict[str, Tuple[str, str]]:
        """``{HLO instruction name: (scope, "fwd"|"bwd"|"")}`` of the
        compiled step (``profiler.scope_table`` over
        ``compiled_step_text()``): which model part, optimizer or guard
        each instruction belongs to, by the ``mx.`` scopes the step was
        traced under. A device trace names its events by instruction, so
        joined with one this says where the step's device time goes.
        An executable the persistent cache kept from before the scopes
        existed comes back without them (JAX leaves metadata out of the
        cache key): every scope then reads ``""``."""
        return profiler.scope_table(self.compiled_step_text())

    def pipelined_structure(self, accum: bool = False) -> dict:
        """`pipelined.structure_report` over the compiled program: grad
        collectives present per bucket, in plan order, interleaved
        between block backwards (not clustered after them)."""
        from .pipelined import structure_report
        if self.pipelined_issue_ledger is None:
            raise MXNetError(
                "pipelined_structure: run one step first")
        return structure_report(self.pipelined_hlo(accum=accum),
                                self.pipelined_issue_ledger)

    # ------------------------------------------------------------------ #
    def _materialize(self, batch_nds):
        """Finish deferred init with one eager forward, then place params
        (and build optimizer state) with their mesh shardings."""
        if any(p._deferred_init is not None for p in self._params):
            with autograd.pause():
                if self.forward_loss is not None:
                    self.forward_loss(self.block, *batch_nds)
                else:
                    self.block(batch_nds[0])
            self._params = list(self.block.collect_params().values())
            self._train_idx = [i for i, p in enumerate(self._params)
                               if p.grad_req != "null"]
        multiproc = jax.process_count() > 1
        if self._opt_state is None:
            # create optimizer state BEFORE params go onto the global mesh:
            # eager ops (e.g. the multi-precision f32 master cast) are not
            # legal on non-fully-addressable multi-host arrays
            self._opt_state = []
            for i in self._train_idx:
                p = self._params[i]
                st = self._optimizer.create_state_multi_precision(
                    i, p.data())
                sh = _param_sharding(p, self.mesh, self.sharding_mode)

                def _place(s, sh=sh):
                    if not isinstance(s, NDArray):
                        return s
                    arr = s._data
                    if multiproc and len(arr.devices()) == 1:
                        arr = _np_host(arr)
                    return NDArray(jax.device_put(arr, sh))

                st = jtu.tree_map(
                    _place, st, is_leaf=lambda s: isinstance(s, NDArray))
                self._opt_state.append(st)
        shard_params(self.block, self.mesh, self.sharding_mode)

    def _build_step(self, n_batch):
        params = self._params
        train_idx = self._train_idx
        train_set = set(train_idx)
        optimizer = self._optimizer
        block = self.block
        loss = self.loss
        forward_loss = self.forward_loss
        self_mesh = self.mesh
        from ..gluon.block import _hybrid_trace_scope

        def pure_loss(train_vals, frozen_vals, key, *batch):
            """loss + aux (mutated frozen params, e.g. BN running stats)."""
            saved = [p._data for p in params]
            it_t, it_f = iter(train_vals), iter(frozen_vals)
            for i, p in enumerate(params):
                p._data = NDArray(next(it_t) if i in train_set else next(it_f))
            try:
                with _hybrid_trace_scope(), _random.key_provider(key), \
                        autograd._ModeScope(recording=False, training=True), \
                        activation_sharding_scope(self_mesh):
                    batch_nd = [NDArray(b) for b in batch]
                    if forward_loss is not None:
                        L = forward_loss(block, *batch_nd)
                    else:
                        out = block(batch_nd[0])
                        L = loss(out, *batch_nd[1:])
                    if L.ndim > 0:
                        L = L.mean()
                    aux = []
                    for i, p in enumerate(params):
                        if i not in train_set:
                            aux.append(p._data._data)
            finally:
                for p, s in zip(params, saved):
                    p._data = s
            return L._data, tuple(aux)

        guard = self.guard
        trainer = self
        base_rescale = float(optimizer.rescale_grad)

        def step(train_vals, frozen_vals, opt_leaves, opt_tree, t, lr,
                 scale, key, *batch):
            trainer.step_trace_count += 1   # python body = trace time only
            (loss_val, aux), grads = jax.value_and_grad(
                profiler.scoped("mx.loss", lambda tv, fv, k, *b: (
                    # dynamic loss scaling as a traced scalar: scale the
                    # loss INSIDE the program, divide back through the
                    # (traced) rescale_grad below — growth/decay never
                    # retraces
                    (lambda L, a: (L * scale, a))(*pure_loss(tv, fv, k, *b))
                )), argnums=0, has_aux=True)(
                    train_vals, frozen_vals, key, *batch)
            with profiler.scope("mx.loss"):
                loss_val = loss_val / scale
            opt_state = jtu.tree_unflatten(opt_tree, opt_leaves)
            # whole-tree fused apply (optimizer/fused.py — shared with the
            # eager Trainer's jitted group path); the step counter and lr
            # arrive as traced scalars so schedules and Adam/LAMB bias
            # correction advance without recompiling
            from ..optimizer.fused import apply_updates
            with profiler.scope("mx.optimizer"):
                new_train, new_states = apply_updates(
                    optimizer, train_idx, train_vals, grads, opt_state, t,
                    lr, rescale_grad=jnp.float32(base_rescale) / scale)
            new_train = tuple(new_train)
            aux = tuple(aux)
            new_leaves = tuple(jtu.tree_leaves(tuple(new_states)))
            if guard:
                # in-step non-finite guard, pure traced data: the
                # all-finite reduction over the (scaled) gradients runs
                # inside the SPMD program — XLA inserts the cross-device
                # reduction itself, so every rank computes the SAME flag
                # — and a skip-step is a where-select of the old params,
                # optimizer state AND mutated frozen params (BN stats)
                from ..optimizer.fused import all_finite
                # the selects sit inside the scope with the reduction: a
                # fusion carries its root's name, and an update fused
                # into a select outside any scope would read as unscoped
                with profiler.scope("mx.guard"):
                    ok_flag = all_finite(grads)
                    apply_p = ok_flag > 0
                    new_train = tuple(
                        jnp.where(apply_p, nw, w)
                        for nw, w in zip(new_train, train_vals))
                    aux = tuple(jnp.where(apply_p, na, fv)
                                for na, fv in zip(aux, frozen_vals))
                    new_leaves = tuple(
                        jnp.where(apply_p, nl, ol)
                        for nl, ol in zip(new_leaves, opt_leaves))
            else:
                ok_flag = jnp.float32(1.0)
            return new_train, aux, new_leaves, loss_val, ok_flag

        repl, batch_sh, train_sh, frozen_sh, state_sh = \
            self._step_shardings()

        donate = (0, 2) if self.donate else ()
        return jax.jit(
            step,
            static_argnums=(3,),
            in_shardings=(train_sh, frozen_sh, tuple(state_sh), repl, repl,
                          repl, repl) + (batch_sh,) * n_batch,
            # pin outputs to the param/state shardings: otherwise the
            # partitioner may emit its preferred layout and step N+1's
            # donated inputs no longer match in_shardings
            out_shardings=(train_sh, frozen_sh, tuple(state_sh), repl,
                           repl),
            donate_argnums=donate)

    def _step_shardings(self):
        mesh = self.mesh
        params = self._params
        train_set = set(self._train_idx)
        repl = NamedSharding(mesh, PartitionSpec())
        batch_sh = NamedSharding(mesh, PartitionSpec(("fsdp", "dp")))
        train_sh = tuple(
            _param_sharding(params[i], mesh, self.sharding_mode)
            for i in self._train_idx)
        frozen_sh = tuple(
            _param_sharding(params[i], mesh, self.sharding_mode)
            for i in range(len(params)) if i not in train_set)
        # optimizer-state leaves share their parameter's sharding
        state_sh = []
        for slot, i in enumerate(self._train_idx):
            n_leaves = len(jtu.tree_leaves(
                jtu.tree_map(lambda s: 0, self._opt_state[slot],
                             is_leaf=lambda s: isinstance(s, NDArray))))
            state_sh.extend(
                [_param_sharding(params[i], mesh, self.sharding_mode)]
                * n_leaves)
        return repl, batch_sh, train_sh, frozen_sh, state_sh

    # ------------------------------------------------------------------ #
    # in-step traced gradient accumulation (round 16,
    # docs/TRAINING_PERF.md). ONE once-compiled program processes one
    # microbatch per call and carries (f32 grad accumulators, combined
    # all-finite verdict, loss sum) as donated state; ``is_last`` and
    # ``inv_k`` ride as traced scalars, so the accumulation count k is
    # PURE HOST DATA — changing k between rounds never retraces
    # (``accum_step_trace_count`` asserted; the scan-over-k alternative
    # recompiles per count because the reshaped batch changes shape).
    # The apply is a where-select on ``is_last AND all-micros-finite``:
    # a NaN in microbatch 2 of 8 poisons the carried verdict and the
    # whole apply skips, params/optimizer state bit-identical — ONE
    # combined verdict, ONE StepOutcome, ONE loss-scaler update per
    # accumulated step (the PR-8 guard/scaler contract, composed).
    # ------------------------------------------------------------------ #
    def _build_accum_step(self, n_batch):
        params = self._params
        train_idx = self._train_idx
        train_set = set(train_idx)
        optimizer = self._optimizer
        block = self.block
        loss = self.loss
        forward_loss = self.forward_loss
        self_mesh = self.mesh
        from ..gluon.block import _hybrid_trace_scope

        def pure_loss(train_vals, frozen_vals, key, *batch):
            saved = [p._data for p in params]
            it_t, it_f = iter(train_vals), iter(frozen_vals)
            for i, p in enumerate(params):
                p._data = NDArray(next(it_t) if i in train_set
                                  else next(it_f))
            try:
                with _hybrid_trace_scope(), _random.key_provider(key), \
                        autograd._ModeScope(recording=False,
                                            training=True), \
                        activation_sharding_scope(self_mesh):
                    batch_nd = [NDArray(b) for b in batch]
                    if forward_loss is not None:
                        L = forward_loss(block, *batch_nd)
                    else:
                        out = block(batch_nd[0])
                        L = loss(out, *batch_nd[1:])
                    if L.ndim > 0:
                        L = L.mean()
                    aux = []
                    for i, p in enumerate(params):
                        if i not in train_set:
                            aux.append(p._data._data)
            finally:
                for p, s in zip(params, saved):
                    p._data = s
            return L._data, tuple(aux)

        guard = self.guard
        trainer = self
        base_rescale = float(optimizer.rescale_grad)

        def astep(train_vals, frozen_vals, opt_leaves, opt_tree,
                  acc_vals, acc_ok, acc_loss, t, lr, scale, inv_k,
                  is_last, key, *batch):
            trainer.accum_step_trace_count += 1   # trace time only
            (loss_val, aux), grads = jax.value_and_grad(
                profiler.scoped("mx.loss", lambda tv, fv, k, *b: (
                    (lambda L, a: (L * scale, a))(*pure_loss(tv, fv, k,
                                                             *b))
                )), argnums=0, has_aux=True)(
                    train_vals, frozen_vals, key, *batch)
            with profiler.scope("mx.loss"):
                loss_val = loss_val / scale
            # fold this microbatch into the f32 accumulators; non-finite
            # values propagate through the sum AND the explicit verdict
            # product below, so the round's apply decision is combined
            with profiler.scope("mx.optimizer"):
                new_acc = tuple(a + g.astype(jnp.float32)
                                for a, g in zip(acc_vals, grads))
            from ..optimizer.fused import all_finite, apply_updates
            if guard:
                with profiler.scope("mx.guard"):
                    ok_round = acc_ok * all_finite(grads)
            else:
                ok_round = jnp.float32(1.0)
            loss_round = acc_loss + loss_val
            # the apply (mean of the accumulated f32 gradients), always
            # traced, selected only on the last microbatch of a clean
            # round — the where-select skip idiom of the PR-8 guard,
            # extended with the is_last gate
            opt_state = jtu.tree_unflatten(opt_tree, opt_leaves)
            with profiler.scope("mx.optimizer"):
                apply_grads = tuple(a * inv_k for a in new_acc)
                new_train, new_states = apply_updates(
                    optimizer, train_idx, train_vals, apply_grads,
                    opt_state, t, lr,
                    rescale_grad=jnp.float32(base_rescale) / scale)
            new_leaves = tuple(jtu.tree_leaves(tuple(new_states)))
            last_p = is_last > 0
            with profiler.scope("mx.guard"):
                apply_p = jnp.logical_and(last_p, ok_round > 0)
                new_train = tuple(jnp.where(apply_p, nw, w)
                                  for nw, w in zip(new_train, train_vals))
                new_leaves = tuple(
                    jnp.where(apply_p, nl, ol)
                    for nl, ol in zip(new_leaves, opt_leaves))
            # accumulators reset at round end regardless of verdict (a
            # vetoed round's batch is discarded, PR-8 skip semantics)
            with profiler.scope("mx.optimizer"):
                acc_out = tuple(jnp.where(last_p, jnp.zeros_like(na), na)
                                for na in new_acc)
            acc_ok_out = jnp.where(last_p, jnp.float32(1.0), ok_round)
            acc_loss_out = jnp.where(last_p, jnp.float32(0.0),
                                     loss_round)
            return (new_train, tuple(aux), new_leaves, acc_out,
                    acc_ok_out, acc_loss_out, loss_round * inv_k,
                    ok_round)

        repl, batch_sh, train_sh, frozen_sh, state_sh = \
            self._step_shardings()
        acc_sh = train_sh                 # accumulators shard like params
        donate = (0, 2, 4) if self.donate else ()
        return jax.jit(
            astep,
            static_argnums=(3,),
            in_shardings=(train_sh, frozen_sh, tuple(state_sh), acc_sh,
                          repl, repl, repl, repl, repl, repl, repl,
                          repl) + (batch_sh,) * n_batch,
            out_shardings=(train_sh, frozen_sh, tuple(state_sh), acc_sh,
                           repl, repl, repl, repl),
            donate_argnums=donate)

    def step_microbatches(self, microbatches):
        """Run ONE optimizer step over ``microbatches`` (a sequence of
        batch tuples of identical shapes), accumulating gradients in
        f32 inside the once-compiled microbatch program and applying
        the mean once at the end. The accumulation count is pure host
        data — rounds of 1, 4 and 8 microbatches all run the same
        compiled program (``accum_step_trace_count`` stays 1; changing
        the MICROBATCH SHAPE retraces, changing the count never does).
        The PR-8 guard/scaler contract composes as one round-level
        verdict: a non-finite gradient in ANY microbatch skips the
        whole apply (params, optimizer state and BN aux bit-identical
        to the round start), records ONE ``SKIPPED_NONFINITE`` and
        halves the loss scale ONCE. Returns the round's mean loss."""
        span = _host_spans()
        t0 = time.perf_counter()
        with span("mx.trainer.step"):
            with span("mx.trainer.prepare"):
                rounds = self._prepare_round(microbatches)
            applied, loss_report, frozen_saved, host_span = \
                self._run_round(rounds, span, t0)
        k = len(rounds)
        self.last_accum_count = k
        if not applied:
            # roll the per-microbatch BN/aux mutations back to the
            # round start — a vetoed round rolls NOTHING forward
            train_set = set(self._train_idx)
            it_f = iter(frozen_saved)
            for i, p in enumerate(self._params):
                if i not in train_set:
                    p._data._data = next(it_f)
        self._record_outcome(
            applied, host_span,
            lambda: (f"non-finite gradient in accumulated SPMD round "
                     f"(k={k}) at step_count={self.step_count}"))
        return NDArray(loss_report)

    def _prepare_round(self, microbatches):
        """The microbatches as arrays, checked; the first call's
        placement, build and accumulators."""
        batches = [b if isinstance(b, (tuple, list)) else (b,)
                   for b in microbatches]
        if not batches:
            raise MXNetError("step_microbatches needs >= 1 microbatch")
        dp = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        rounds = []
        for batch in batches:
            nds = [b if isinstance(b, NDArray)
                   else NDArray(jnp.asarray(b)) for b in batch]
            for b in nds:
                if b.ndim and b.shape[0] % dp != 0:
                    raise MXNetError(
                        f"microbatch dim {b.shape[0]} not divisible by "
                        f"the mesh's dp×fsdp size {dp}")
            rounds.append(nds)
        if self._opt_state is None:
            self._materialize(rounds[0])
        if self._accum_step_fn is None:
            if self._pipeline is not None:
                from .pipelined import build_pipelined_accum_step
                self._accum_step_fn = build_pipelined_accum_step(
                    self, len(rounds[0]))
            else:
                self._accum_step_fn = self._build_accum_step(
                    len(rounds[0]))
        if self._accum_bufs is None:
            # f32 accumulators placed with their parameter's sharding
            repl, _, train_sh, _, _ = self._step_shardings()
            self._accum_bufs = [
                jax.device_put(
                    jnp.zeros(self._params[i].shape, jnp.float32), sh)
                for i, sh in zip(self._train_idx, train_sh)]
            # carried scalars start COMMITTED to the mesh like the arrays
            # the program hands back: an uncommitted jnp scalar has a
            # different abstract value (no mesh) and the second
            # microbatch would retrace the whole program
            self._accum_ok = jax.device_put(jnp.float32(1.0), repl)
            self._accum_loss = jax.device_put(jnp.float32(0.0), repl)
        return rounds

    def _run_round(self, rounds, span, t0):
        """Dispatch every microbatch of a round, then read the combined
        verdict. Returns ``(applied, loss, frozen_saved, host_span)``;
        ``host_span`` is the round on the host's clock from ``t0`` as
        ``StepRecorder.record`` takes it, each phase summed over the
        microbatches."""
        k = len(rounds)
        train_set = set(self._train_idx)
        self._optimizer.num_update = self.step_count
        t = _host_np.float32(self.step_count + 1)
        lr = _host_np.float32(float(self._optimizer.learning_rate))
        scale = _host_np.float32(
            1.0 if self.loss_scaler is None
            else self.loss_scaler.loss_scale)
        inv_k = _host_np.float32(1.0 / k)
        # round-start frozen-param snapshot (array refs, not copies):
        # BN running stats advance per microbatch, and a vetoed round
        # must roll NOTHING forward — restored by the caller on veto
        frozen_saved = [p._data._data
                        for i, p in enumerate(self._params)
                        if i not in train_set]

        self._recorder.open_step()
        loss_report = ok_report = None
        prepare_s = dispatch_s = bind_s = 0.0
        mark = t0
        try:
            for m, batch_nds in enumerate(rounds):
                with span("mx.trainer.prepare"):
                    is_last = _host_np.float32(
                        1.0 if m == k - 1 else 0.0)
                    key = _random.new_key()
                    train_vals = tuple(self._params[i]._data._data
                                       for i in self._train_idx)
                    frozen_vals = tuple(
                        p._data._data for i, p in enumerate(self._params)
                        if i not in train_set)
                    opt_leaves, opt_tree = self._flat_opt_state()
                    batch_vals = self._global_batch_vals(
                        [b._data for b in batch_nds])
                    if jax.process_count() > 1:
                        key = _host_np.asarray(key)
                    args = (train_vals, frozen_vals, tuple(opt_leaves),
                            opt_tree, tuple(self._accum_bufs),
                            self._accum_ok, self._accum_loss, t, lr,
                            scale, inv_k, is_last, key) + tuple(batch_vals)
                    if self._pipeline is not None and \
                            self._pipe_example_accum_args is None:
                        self._pipe_example_accum_args = \
                            self._abstract_args(args, static={3})
                t1 = time.perf_counter()
                with span("mx.trainer.dispatch"):
                    (new_train, aux, new_leaves, acc_out, acc_ok_out,
                     acc_loss_out, loss_report, ok_report) = \
                        self._accum_step_fn(*args)
                t2 = time.perf_counter()
                with span("mx.trainer.bind"):
                    self._bind_outputs(new_train, aux, new_leaves,
                                       opt_tree)
                    self._accum_bufs = list(acc_out)
                    self._accum_ok = acc_ok_out
                    self._accum_loss = acc_loss_out
                t3 = time.perf_counter()
                prepare_s += t1 - mark
                dispatch_s += t2 - t1
                bind_s += t3 - t2
                mark = t3
        except BaseException:
            # dispatch died mid-round: close the step and drop the
            # half-accumulated state (re-zeroed on the next round)
            self._recorder.abort_step()
            self._accum_bufs = None
            raise

        # the ONE designed readback per accumulated round: the combined
        # verdict steers host counters, the scaler and the outcome —
        # read after every microbatch is dispatched
        with span("mx.trainer.flag_wait"):
            applied = (not self.guard) or \
                bool(_host_np.asarray(ok_report) > 0)
        t4 = time.perf_counter()
        return applied, loss_report, frozen_saved, (
            t0, t4 - t0, prepare_s, dispatch_s, bind_s, t4 - mark)

    def _global_batch_vals(self, batch_vals):
        """Multi-host batch placement (every process holds the SAME full
        batch; build global dp-sharded arrays from the host copies) —
        identity in single-process runs."""
        if jax.process_count() <= 1:
            return batch_vals
        batch_sh = NamedSharding(self.mesh,
                                 PartitionSpec(("fsdp", "dp")))

        def _globalize(b):
            if len(b.devices()) > 1:
                return b
            host = _host_np.asarray(b)
            if host.ndim == 0:
                return host
            return jax.make_array_from_callback(
                host.shape, batch_sh, lambda idx: host[idx])

        return [_globalize(b) for b in batch_vals]

    # ------------------------------------------------------------------ #
    def step(self, *batch):
        """Run one fused train step; returns the (device-resident) loss.

        The step stamps the host's clock at five points and hands the
        phases to the recorder, which puts them on the step's one
        ``TRAIN_STEP`` event: ``prepare_s`` (batch to arrays, the first
        step's build, parameter and state gathering, flattening, key and
        scalars), ``dispatch_s`` (the compiled step's call: the first one
        traces and compiles), ``bind_s`` (outputs bound to parameters and
        state), ``flag_wait_s`` (the guard's flag read, which waits for
        the device). While a ``jax.profiler`` session is live the same
        phases are ``mx.trainer.*`` annotations in its trace."""
        span = _host_spans()
        t0 = time.perf_counter()
        with span("mx.trainer.step"):
            with span("mx.trainer.prepare"):
                args = self._prepare_step(batch)
                self._recorder.open_step()
            t1 = time.perf_counter()
            with span("mx.trainer.dispatch"):
                try:
                    outs = self._step_fn(*args)
                except BaseException:
                    # dispatch died before any outcome existed — close
                    # the step so the next one is not falsely accused of
                    # a missing record
                    self._recorder.abort_step()
                    raise
            t2 = time.perf_counter()
            with span("mx.trainer.bind"):
                new_train, aux, new_state_leaves, loss_val, ok_flag = outs
                opt_tree = args[3]
                self._bind_outputs(new_train, aux, new_state_leaves,
                                   opt_tree)
            t3 = time.perf_counter()
            # the guard verdict is read AFTER the outputs are bound (the
            # update was already selected on device); it only steers host
            # counters, the scaler and the outcome record
            with span("mx.trainer.flag_wait"):
                applied = (not self.guard) or \
                    bool(_host_np.asarray(ok_flag) > 0)
            t4 = time.perf_counter()
        self._record_outcome(
            applied, (t0, t4 - t0, t1 - t0, t2 - t1, t3 - t2, t4 - t3),
            lambda: (f"non-finite gradient in fused SPMD step at "
                     f"step_count={self.step_count} "
                     f"(loss={float(_host_np.asarray(loss_val)):g})"))
        return NDArray(loss_val)

    def _record_outcome(self, applied, host_span, detail):
        """The one outcome of a step or accumulated round, with its span
        on the host's clock; the loss scaler follows it. ``detail`` is
        asked for only when the step was skipped."""
        if applied:
            self.step_count += 1
            self._recorder.record(StepOutcome.APPLIED, span=host_span)
            if self.loss_scaler is not None and self.guard:
                # without the guard overflow can never be observed, so
                # growing the scale would be a one-way ratchet to inf
                self.loss_scaler.update_scale(overflow=False)
            return
        if self.loss_scaler is not None:
            self.loss_scaler.update_scale(overflow=True)
        detail = detail()
        outcome = self._recorder.record(
            StepOutcome.SKIPPED_NONFINITE, detail, span=host_span)
        if outcome is StepOutcome.HALTED_POISONED:
            raise self._recorder.halt_error(
                detail,
                loss_scale=None if self.loss_scaler is None
                else self.loss_scaler.loss_scale)

    def _prepare_step(self, batch):
        """Everything ``step`` does on the host before the dispatch: the
        batch as arrays, the first call's placement and build, and the
        compiled step's arguments gathered from parameters and state."""
        batch_nds = [b if isinstance(b, NDArray) else NDArray(jnp.asarray(b))
                     for b in batch]
        dp = self.mesh.shape["dp"] * self.mesh.shape["fsdp"]
        for b in batch_nds:
            if b.ndim and b.shape[0] % dp != 0:
                raise MXNetError(
                    f"batch dim {b.shape[0]} not divisible by the mesh's "
                    f"dp×fsdp size {dp}; pad the batch or shrink the mesh")
        if self._opt_state is None:
            self._materialize(batch_nds)
        if self._step_fn is None:
            if self._pipeline is not None:
                from .pipelined import build_pipelined_step
                self._step_fn = build_pipelined_step(
                    self, len(batch_nds))
            else:
                self._step_fn = self._build_step(len(batch_nds))

        train_set = set(self._train_idx)
        train_vals = tuple(self._params[i]._data._data
                           for i in self._train_idx)
        frozen_vals = tuple(p._data._data for i, p in enumerate(self._params)
                            if i not in train_set)
        opt_leaves, opt_tree = self._flat_opt_state()
        key = _random.new_key()
        self._optimizer.num_update = self.step_count  # drive lr schedules
        t = _host_np.float32(self.step_count + 1)
        lr = _host_np.float32(float(self._optimizer.learning_rate))
        scale = _host_np.float32(
            1.0 if self.loss_scaler is None
            else self.loss_scaler.loss_scale)
        batch_vals = self._global_batch_vals([b._data for b in batch_nds])
        if jax.process_count() > 1:
            key = _host_np.asarray(key)
        args = (train_vals, frozen_vals, tuple(opt_leaves), opt_tree,
                t, lr, scale, key) + tuple(batch_vals)
        if self._pipe_example_args is None:
            # abstract snapshot for on-demand .lower() (structure checks,
            # compiled_step_text)
            self._pipe_example_args = self._abstract_args(args, static={3})
        return args

    def _flat_opt_state(self):
        return jtu.tree_flatten(
            jtu.tree_map(lambda s: s._data if isinstance(s, NDArray) else s,
                         tuple(self._opt_state),
                         is_leaf=lambda s: isinstance(s, NDArray)))

    def _bind_outputs(self, new_train, aux, new_state_leaves, opt_tree):
        """Point parameters and optimizer state at a dispatch's outputs."""
        train_set = set(self._train_idx)
        it_t = iter(new_train)
        it_a = iter(aux)
        for i, p in enumerate(self._params):
            p._data._data = next(it_t) if i in train_set else next(it_a)
        new_states = jtu.tree_unflatten(opt_tree, list(new_state_leaves))
        self._opt_state = [
            jtu.tree_map(NDArray, st) for st in new_states]

    # ------------------------------------------------------------------ #
    # elastic checkpointing (checkpoint/ subsystem): each process
    # gathers only its addressable shards, so fsdp-sharded params and
    # optimizer state checkpoint without ever materializing the full
    # tree on one host. Restore hands host arrays to the jitted step,
    # which re-places them via its in_shardings — resume on the SAME
    # mesh is bit-exact (asserted in tests); a different mesh shape
    # loads and trains correctly but reduction order may differ in the
    # last ulp.
    # ------------------------------------------------------------------ #
    def save_checkpoint(self, manager, step=None, iterator=None,
                        block=False):
        """Async full-capsule snapshot (params, optimizer state, step
        count, scheduler num_update, RNG, iterator position)."""
        from .. import checkpoint as _ckpt
        tree, meta = _ckpt.spmd_capsule(self, iterator=iterator)
        if step is None:
            step = meta["step"]
        else:
            # caller's loop position wins (see Trainer.save_checkpoint:
            # step_count does not advance on guard-skipped steps, and a
            # resume must not re-run already-applied batches)
            meta["step"] = int(step)
        manager.save(int(step), tree, meta=meta, block=block)
        return int(step)

    def restore_checkpoint(self, manager, step=None, iterator=None):
        """Bit-exact resume from ``manager`` (default: latest committed
        step). The block must be initialized with known shapes; the
        jitted step is rebuilt lazily and re-places restored host
        arrays via its in_shardings. Returns the restored step."""
        from .. import checkpoint as _ckpt
        arrays, meta = manager.restore(step)
        _ckpt.restore_spmd(self, arrays, meta, iterator=iterator)
        return int(meta.get("step", 0))

    def install_preemption(self, manager, iterator=None, exit_after=True):
        """Arm SIGTERM: drain any in-flight snapshot, write one final
        synchronous capsule, then let the process die."""
        from .. import checkpoint as _ckpt

        def _state():
            tree, meta = _ckpt.spmd_capsule(self, iterator=iterator)
            return meta["step"], tree, meta

        return manager.install_preemption_hook(_state,
                                               exit_after=exit_after)

"""Fused whole-tree optimizer step (multi-tensor apply).

The reference kills per-parameter launch overhead with engine op bulking
plus hand-fused multi-tensor kernels (`src/operator/contrib/
preloaded_multi_sgd-inl.h`, the multi_* family in optimizer_op.cc —
file-level citations, SURVEY.md caveat). The TPU-native translation is to
put the WHOLE update into one XLA program: group trainable parameters by
(dtype, storage type, hyperparameter signature) and apply each group's
update as ONE jitted, donated call over the stacked pytree of
(weights, grads, optimizer states).

Two consumers share the same functional core (``apply_updates``):

  - ``gluon.Trainer`` jits it per group via ``FusedApplier`` — the eager
    per-parameter Python loop (one un-jitted dispatch per param per step)
    collapses to one compiled call per group per step.
  - ``parallel.SPMDTrainer`` calls it INSIDE its single jitted train step,
    so fwd+bwd+reduce+update stay one XLA program.

The imperative ``Optimizer`` subclasses are reused unchanged: inside the
trace each parameter's update runs through ``update_multi_precision`` on
NDArray views of the traced arrays, and XLA fuses the resulting
elementwise chains across parameters. Step count, learning rate, and
gradient rescale ride as traced scalars (``_traced_t`` / ``_traced_lr`` /
a temporarily swapped ``rescale_grad``) so schedules and Adam/LAMB bias
correction advance without recompiling.

What does NOT fuse (falls back to the eager per-param path):

  - optimizers with per-step host-side state (``fusable = False``:
    Nadam's ``m_schedule``, SGLD's fresh host RNG key per update) —
    baking those into a trace would freeze them at their step-1 values;
  - ``row_sparse``-gradient parameters — their active-row index sets
    change shape every step, which would retrace per step.

Round 13 adds the IN-STEP NON-FINITE GUARD (docs/RESILIENCE.md): one
jitted all-finite reduction over every fused gradient produces a device
scalar ``ok`` that rides into each group's update program as PURE
TRACED DATA, where a ``where``-select returns the OLD weights and
optimizer state when the step must be skipped. The skip is therefore
decided on device with zero extra host syncs on the dispatch path (the
flag is read AFTER the updates are enqueued, only to keep host step
counters and the loss scaler honest), and the group programs still
compile exactly once — overflow/clean transitions and loss-scale
growth/decay never retrace (``guard_trace_count`` /
``trace_count`` asserted in tests and tools/train_chaos_bench.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
import numpy as np

from ..base import getenv_bool
from ..ndarray import NDArray

__all__ = ["apply_updates", "FusedApplier", "hyperparam_signature",
           "all_finite", "norm_based"]


def _is_nd(x):
    return isinstance(x, NDArray)


def norm_based(optimizer) -> bool:
    """True for optimizers whose update rule reads a GLOBAL weight/grad
    norm (LAMB/LARS trust ratios). Those updates are only correct over
    full parameter values: under fsdp the pipelined step applies updates
    on shard-local slices, where a per-shard norm would silently change
    the trust ratio — parallel/pipelined.py rejects the combination via
    this one shared predicate so the two trainers cannot drift."""
    name = type(optimizer).__name__.lower()
    return any(t in name for t in ("lamb", "lars"))


def all_finite(grad_vals):
    """Traceable all-finite reduction over a sequence of jax arrays →
    an f32 scalar (1.0 = every float entry finite). THE guard
    reduction — shared by the fused group programs, the external
    multi-group guard, and the SPMD step (parallel/spmd.py), so the
    guard semantics cannot drift between trainers."""
    ok = jnp.asarray(True)
    for g in grad_vals:
        if jnp.issubdtype(g.dtype, jnp.floating):
            ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(g)))
    return ok.astype(jnp.float32)


def apply_updates(optimizer, indices, weight_vals, grad_vals, states,
                  t, lr, rescale_grad=None):
    """Functional whole-tree optimizer application (call under a trace).

    Parameters
    ----------
    optimizer : Optimizer — imperative optimizer, reused as the update rule.
    indices : sequence of parameter indices (the optimizer's state keys).
    weight_vals / grad_vals : sequences of jax arrays, aligned to indices.
    states : sequence of optimizer-state pytrees with jax-array leaves.
    t : traced step count — scalar, or a (len(indices),) vector for
        per-parameter counts (Adam/LAMB bias correction).
    lr : traced base learning rate (per-param multipliers apply inside).
    rescale_grad : optional traced gradient rescale; when given it
        temporarily replaces ``optimizer.rescale_grad`` so batch-size
        changes do not force a retrace.

    Returns ``(new_weights, new_states)`` — tuples aligned to indices,
    with jax-array leaves. The optimizer's host-side counters are touched
    at trace time only; callers own their true values.
    """
    new_weights: List = []
    new_states: List = []
    saved_rescale = optimizer.rescale_grad
    optimizer._traced_lr = lr
    if rescale_grad is not None:
        optimizer.rescale_grad = rescale_grad
    t_is_vec = getattr(t, "ndim", 0) >= 1
    try:
        for slot, (pi, w, g) in enumerate(
                zip(indices, weight_vals, grad_vals)):
            w_nd = NDArray(w)
            g_nd = NDArray(g)
            st = jtu.tree_map(NDArray, states[slot])
            optimizer._traced_t = t[slot] if t_is_vec else t
            optimizer.update_multi_precision(pi, w_nd, g_nd, st)
            # pin output dtypes to the input dtypes: the traced t/lr
            # scalars are f32 arrays, and jnp promotion would otherwise
            # widen low-precision weights/state (breaking donation buffer
            # reuse and the group's dtype key). Low-precision groups thus
            # compute scalar-touched arithmetic in f32 and round back to
            # the storage dtype — documented in docs/PERF_NOTES.md.
            new_w = w_nd._data
            if new_w.dtype != w.dtype:
                new_w = new_w.astype(w.dtype)
            new_weights.append(new_w)
            new_states.append(jtu.tree_map(
                lambda old, new: (
                    new._data.astype(old.dtype)
                    if _is_nd(new) and new._data.dtype != old.dtype
                    else (new._data if _is_nd(new) else new)),
                states[slot], st))
    finally:
        optimizer._traced_t = optimizer._traced_lr = None
        optimizer.rescale_grad = saved_rescale
    return tuple(new_weights), tuple(new_states)


def hyperparam_signature(optimizer) -> Tuple:
    """Hashable signature of every host scalar an update trace bakes in.

    A fused trace captures the optimizer's scalar attributes (momentum,
    betas, wd, clip_gradient, ...) as constants; if any of them changes the
    jitted group function must be rebuilt. Step count, learning rate and
    rescale_grad are excluded — they ride as traced inputs.
    """
    skip = {"num_update", "lr", "rescale_grad", "_traced_t", "_traced_lr"}
    items = []
    for k, v in sorted(vars(optimizer).items()):
        if k in skip:
            continue
        if isinstance(v, (int, float, bool, str)) or v is None:
            items.append((k, v))
    return (type(optimizer).__name__, tuple(items))


class FusedApplier:
    """Whole-tree fused apply for ``Trainer``'s eager step.

    Groups (index, param, grad) triples by (dtype, grad storage type),
    and runs each group through ONE jitted call of ``apply_updates`` with
    the weights and optimizer-state leaves donated. The jit cache is keyed
    by (group key, member indices, hyperparameter signature, per-param
    lr/wd multipliers, state treedef) — any change retraces exactly once,
    steady state re-dispatches the cached executable.
    """

    def __init__(self, optimizer, donate: bool = True,
                 guard: Optional[bool] = None):
        self.optimizer = optimizer
        # donated on every backend (the CPU backend implements donation
        # too), so the CPU tests run the arm the chip runs: a guard
        # veto, checkpoint or warm start that re-read a donated input
        # fails here first, not on first contact with a TPU
        self.donate = donate
        if guard is None:
            guard = getenv_bool("MXTPU_STEP_GUARD", True)
        self.guard = bool(guard)
        self._jits: Dict = {}
        self._guard_jits: Dict = {}
        self._accum_jits: Dict = {}
        self.trace_count = 0      # executions of a traced body (compiles)
        self.call_count = 0       # fused group dispatches
        self.guard_trace_count = 0  # all-finite reduction compiles
        self.accum_trace_count = 0  # f32 accumulate-program compiles
        self.skipped_steps = 0    # guard-vetoed apply() calls

    # ------------------------------------------------------------------ #
    def supported(self) -> bool:
        return getattr(self.optimizer, "fusable", True)

    def grad_all_finite(self, grad_vals):
        """One jitted all-finite reduction over every fused gradient →
        an f32 device scalar (1.0 = apply, 0.0 = skip). Compiled once
        per (shape, dtype) signature; non-float grads are vacuously
        finite and excluded."""
        vals = tuple(g for g in grad_vals
                     if jnp.issubdtype(g.dtype, jnp.floating))
        if not vals:
            return None
        sig = tuple((v.shape, str(v.dtype)) for v in vals)
        fn = self._guard_jits.get(sig)
        if fn is None:
            applier = self

            def allfinite(grads):
                applier.guard_trace_count += 1   # trace-time only
                return all_finite(grads)

            fn = jax.jit(allfinite)
            self._guard_jits[sig] = fn
        return fn(vals)

    def accumulate(self, acc_vals, grad_vals):
        """One jitted f32 microbatch-gradient accumulation:
        ``acc + grad.astype(f32)`` over the whole fused set (round 16,
        docs/TRAINING_PERF.md). f32 accumulators keep low-precision
        microbatch gradients from losing mass to rounding, and
        non-finite values PROPAGATE through the sum — so the apply-time
        all-finite verdict over the accumulators is the COMBINED
        verdict for the accumulated step (a NaN in any microbatch skips
        the whole apply). Compiled once per (shape, dtype) signature,
        accumulators donated; the program's shape never depends on the
        accumulation count, so changing counts never retraces
        (``accum_trace_count`` asserted in tests and
        tools/step_bench.py --mfu --smoke)."""
        sig = tuple((v.shape, str(v.dtype)) for v in grad_vals)
        fn = self._accum_jits.get(sig)
        if fn is None:
            applier = self

            def accum(accs, grads):
                applier.accum_trace_count += 1   # trace-time only
                return tuple(a + g.astype(jnp.float32)
                             for a, g in zip(accs, grads))

            fn = jax.jit(accum,
                         donate_argnums=(0,) if self.donate else ())
            self._accum_jits[sig] = fn
        return fn(tuple(acc_vals), tuple(grad_vals))

    def apply(self, items: Sequence, updater,
              extra_grads: Sequence = ()) -> bool:
        """Apply one fused update to ``items`` = [(index, param, grad)].

        ``updater`` is the Trainer's ``Updater`` — optimizer state is
        created into / read from ``updater.states`` so eager and fused
        paths share one serializable state store (save_states parity).

        With the guard on, the skip decision is computed on device and
        ``where``-selected inside each group's program; the flag is
        read back only AFTER every group is dispatched, and a vetoed
        step rolls the host update counters back so schedules and
        Adam/LAMB bias correction do not advance on skipped steps.
        ``extra_grads`` are gradients applied OUTSIDE the fused call
        (the Trainer's row_sparse path) that must still join the
        all-or-nothing verdict — any non-finite entry there vetoes the
        fused groups too. Returns True when the update was applied,
        False when the guard skipped it (params/state bit-identical to
        before the call).
        """
        opt = self.optimizer
        groups: Dict = {}
        for i, p, g in items:
            if i not in updater.states:
                updater.states[i] = opt.create_state_multi_precision(
                    i, p.data())
            gkey = (str(p.data().dtype),
                    getattr(p, "_grad_stype", "default"))
            groups.setdefault(gkey, []).append((i, p, g))
        # guard plumbing: with ONE group (the common case) the
        # all-finite reduction folds INTO the group's own program and
        # the flag comes back as an extra output — zero added
        # dispatches (the separate-program design measured ~12% on the
        # CPU dispatch floor; inline is <2%, PERF_NOTES round 13).
        # Multi-group sets — and steps carrying extra (row_sparse)
        # grads — need the COMBINED flag before any group selects, so
        # they pay one small external reduction program.
        extra_vals = tuple(getattr(g, "_data", g) for g in extra_grads)
        inline_guard = self.guard and len(groups) == 1 and not extra_vals
        ok = None
        if self.guard and not inline_guard:
            ok = self.grad_all_finite(
                tuple(g._data for _, _, g in items) + extra_vals)
        # commit the step's counters BEFORE dispatching: the eager path
        # bumps _update_count before reading the lr, so the scheduler must
        # see the post-bump num_update here too (scheduler(t), not t-1).
        # Trace-time bumps inside update() land on already-bumped counts
        # and are overwritten below, keeping the host counters exact.
        counts = opt._index_update_count
        prev_counts = dict(counts)
        prev_num_update = opt.num_update
        new_counts = {i: counts.get(i, 0) + 1 for i, _, _ in items}
        counts.update(new_counts)
        opt.num_update = max(counts.values(), default=opt.num_update)
        # read the schedule ONCE, before any group dispatch: a group's
        # trace-time _update_count() calls inside apply_updates bump
        # num_update mid-loop, so a per-group read would hand LATER
        # groups scheduler(t+1) instead of scheduler(t) whenever an
        # earlier group (re)traces (multi-dtype/stype sets only)
        lr = np.float32(float(opt.learning_rate))
        rescale = np.float32(float(opt.rescale_grad))
        for gkey, group in groups.items():
            group_ok = self._apply_group(gkey, group, updater, lr,
                                         rescale, ok,
                                         inline_guard=inline_guard)
            if inline_guard:
                ok = group_ok
        counts.update(new_counts)
        opt.num_update = max(counts.values(), default=opt.num_update)
        # mxlint: allow-host-sync(flag read AFTER every group dispatched; off the dispatch critical path by design)
        if ok is None or bool(np.asarray(ok) > 0):
            return True
        # guard veto: the programs already selected the old params and
        # state; un-advance the host counters so the next applied step
        # reuses this step's t (a skipped step never happened, contract
        # of the reference's multi_all_finite skip)
        counts.clear()
        counts.update(prev_counts)
        opt.num_update = prev_num_update
        self.skipped_steps += 1
        return False

    # ------------------------------------------------------------------ #
    def _apply_group(self, gkey, group, updater, lr, rescale,
                     ok=None, inline_guard=False):
        opt = self.optimizer
        indices = tuple(i for i, _, _ in group)
        states = [updater.states[i] for i in indices]
        state_leaves, state_tree = jtu.tree_flatten(
            jtu.tree_map(lambda s: s._data if _is_nd(s) else s,
                         tuple(states), is_leaf=_is_nd))
        mults = tuple((float(getattr(p, "lr_mult", 1.0)),
                       float(getattr(p, "wd_mult", 1.0)))
                      for _, p, _ in group)
        mode = ("inline" if inline_guard
                else "external" if ok is not None else "off")
        sig = (gkey, indices, state_tree,
               hyperparam_signature(opt), mults, mode)
        fn = self._jits.get(sig)
        if fn is None:
            fn = self._build(indices, state_tree, mode)
            self._jits[sig] = fn

        weight_vals = tuple(p.data()._data for _, p, _ in group)
        grad_vals = tuple(g._data for _, _, g in group)
        # apply() already committed this step's counts: use them directly
        t_vec = np.asarray(
            [opt._index_update_count.get(i, 1) for i in indices],
            np.float32)

        group_ok = None
        if mode == "external":
            new_ws, new_state_leaves = fn(
                weight_vals, grad_vals, tuple(state_leaves), t_vec, lr,
                rescale, ok)
        elif mode == "inline":
            new_ws, new_state_leaves, group_ok = fn(
                weight_vals, grad_vals, tuple(state_leaves), t_vec, lr,
                rescale)
        else:
            new_ws, new_state_leaves = fn(
                weight_vals, grad_vals, tuple(state_leaves), t_vec, lr,
                rescale)
        self.call_count += 1

        for (_, p, _), new_w in zip(group, new_ws):
            p.data()._data = new_w
        new_states = jtu.tree_unflatten(state_tree, list(new_state_leaves))
        jtu.tree_map(
            lambda old, new: setattr(old, "_data", new) if _is_nd(old)
            else None,
            tuple(states), new_states, is_leaf=_is_nd)
        return group_ok

    def _build(self, indices, state_tree, mode="off"):
        opt = self.optimizer
        applier = self

        def core(weight_vals, grad_vals, state_leaves, t_vec, lr, rescale,
                 ok):
            applier.trace_count += 1  # python body runs at trace time only
            states = jtu.tree_unflatten(state_tree, list(state_leaves))
            new_ws, new_states = apply_updates(
                opt, indices, weight_vals, grad_vals, states, t_vec, lr,
                rescale_grad=rescale)
            new_leaves = tuple(jtu.tree_leaves(new_states))
            if ok is not None:
                # skip-step as pure data: the guard flag selects the OLD
                # params/state, so a vetoed step is bit-identical to not
                # stepping — and the program is the same either way (no
                # retrace across overflow/clean transitions)
                apply_p = ok > 0
                new_ws = tuple(jnp.where(apply_p, nw, w)
                               for nw, w in zip(new_ws, weight_vals))
                new_leaves = tuple(
                    jnp.where(apply_p, nl, ol)
                    for nl, ol in zip(new_leaves, state_leaves))
            return new_ws, new_leaves

        donate = (0, 2) if self.donate else ()
        if mode == "external":
            def fused_ext(weight_vals, grad_vals, state_leaves, t_vec, lr,
                          rescale, ok):
                return core(weight_vals, grad_vals, state_leaves, t_vec,
                            lr, rescale, ok)
            return jax.jit(fused_ext, donate_argnums=donate)
        if mode == "inline":
            # single-group fast path: the all-finite reduction runs
            # inside the SAME program and the flag rides out as a third
            # output — no extra dispatch, no extra host sync point
            def fused_inline(weight_vals, grad_vals, state_leaves, t_vec,
                             lr, rescale):
                applier.guard_trace_count += 1   # trace-time only
                ok = all_finite(grad_vals)
                new_ws, new_leaves = core(
                    weight_vals, grad_vals, state_leaves, t_vec, lr,
                    rescale, ok)
                return new_ws, new_leaves, ok
            return jax.jit(fused_inline, donate_argnums=donate)

        def fused_off(weight_vals, grad_vals, state_leaves, t_vec, lr,
                      rescale):
            return core(weight_vals, grad_vals, state_leaves, t_vec, lr,
                        rescale, None)
        return jax.jit(fused_off, donate_argnums=donate)

"""In-program overlapped gradient collectives (ROADMAP item 5).

PR 13 measured the failure this module removes: inside the one-program
GSPMD step XLA schedules every gradient all-reduce AFTER the whole
backward (`overlap_ratio: 0.0` in BENCH_MFU.json) — the collectives are
a serial tail, not an overlapped stream. The reference framework solved
the same problem host-side with P3 priority scheduling of kvstore
push/pull during backward (SURVEY.md §2.3); the TPU-native analogue is
to make the overlap a property of the *compiled program*: the step runs
under `shard_map`, backward is decomposed per layer block with chained
`jax.vjp` pullbacks, and each gradient bucket's collective is issued as
an explicit in-program `lax.psum` (or a ppermute ring) *between* block
pullbacks, so the collective for block i+1's gradients is in flight on
ICI while block i's backward computes.

Correctness contract (asserted in tests/test_pipelined_step.py):

- **Bitwise parity.** The pipelined step reproduces the GSPMD step's
  loss/param/optimizer-state trajectories bit-for-bit on clean streams
  over the 2-device dp and fsdp meshes. The parity recipe mirrors what
  GSPMD's partitioner emits: the loss is computed as LOCAL partial sums
  (`PipelineSpec.head` returns un-normalized per-shard sums and counts),
  the partials tree is psummed over the batch axes, and a pure
  `finalize` reproduces the baseline's scalar loss expression on the
  globals — division by a power-of-two shard count is exact, and a
  2-device all-reduce is a single commutative add, so every op matches
  the partitioned baseline's local computation exactly.
- **Deterministic issue order.** Buckets come from
  `collectives.plan_grad_buckets` (the audited packing) and are issued
  strictly in plan order through `collectives.BucketSchedule` at trace
  time — a collective is a cross-replica rendezvous, and a reordered
  issue is the silent deadlock PR 13 fenced host-side. The per-trace
  ledger (`SPMDTrainer.pipelined_issue_ledger`) records what was issued;
  `structure_report` re-derives the order from the lowered StableHLO so
  the *compiled* order, not just the traced one, is asserted.
- **Guard/scaler/accum compose unchanged.** The PR-8 all-finite guard
  reads the post-collective (for int8: dequantized) gradients, combines
  the per-shard verdicts with a `pmin`, and the skip-step stays a
  where-select; loss scaling rides the backward seed; accumulation
  folds into the same donated f32 carry as the GSPMD accum step.

Sharding support: dp and fsdp batch axes (tp/sp/pp/ep must be size 1 on
this path — tensor-parallel models keep the GSPMD step). fsdp params are
all-gathered to full values at the top of the body (ZeRO), gradients are
psummed at full size and sliced back to the local shard — at 2 devices
this is bitwise the partitioner's gather/reduce-scatter pair.

Known limits (documented in docs/TRAINING_PERF.md): parameter-mutating
forwards (BatchNorm running stats) raise loudly; dropout>0 runs but
draws per-shard masks (no bitwise parity with the GSPMD step's global
mask); norm-based optimizers (LAMB/LARS) are rejected under fsdp because
the update would see shard-local norms.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import jax.tree_util as jtu
from jax import lax
from jax.sharding import PartitionSpec

from .. import autograd, profiler, random as _random
from ..base import MXNetError, getenv_int, shard_map
from ..ndarray import NDArray
from .collectives import (BucketSchedule, int8_bucket_allreduce,
                          plan_grad_buckets, ring_allreduce_flat)

__all__ = ["PipelineSpec", "build_pipelined_step",
           "build_pipelined_accum_step", "structure_report",
           "ring_allreduce_flat"]


def _bucket_limit_bytes():
    return getenv_int("MXTPU_GRAD_BUCKET_BYTES", 0) or \
        getenv_int("MXTPU_GRAD_BUCKET_MB", 32) * (1 << 20)


# --------------------------------------------------------------------- #
# pipeline structure declaration
# --------------------------------------------------------------------- #
class PipelineSpec:
    """Declares a model's layer stack as stem → blocks → head for the
    pipelined backward.

    Parameters
    ----------
    blocks : sequence — the pipeline blocks, in forward order. Each
        entry is either a HybridBlock (called as ``blk(x, *ctx)``) or a
        ``(modules, fn)`` pair where ``modules`` is the list of blocks
        owning the entry's parameters and ``fn(x_nd, *ctx_nds)`` runs
        it. Blocks must have pairwise-disjoint parameters.
    head : callable ``head(x_nd, *batch_nds) -> tuple of scalar
        NDArrays`` — the LOCAL PARTIAL SUMS of the loss (un-normalized
        per-shard sums/counts). Runs with head (and tied) params bound.
    finalize : callable over the PSUMMED partials (jnp scalars) →
        scalar loss. Must be parameter-free pure arithmetic and must
        reproduce the baseline loss expression exactly (bitwise parity
        hinges on it): e.g. for a mean, return ``n / d`` where ``head``
        emitted ``(sum(x), float(x.size))``.
    stem_modules / head_modules : blocks owning the stem/head params.
    stem : callable ``stem(*batch_nds) -> x0 NDArray`` (default: the
        first batch element as-is, e.g. when embeddings sit in block 0).
    context : optional ``context(*batch_nds) -> tuple of NDArrays`` —
        parameter-independent constants handed to every block (e.g. the
        BERT attention mask). No gradient flows through the context.
    name : diagnostic label.

    Parameters appearing in both ``stem_modules`` and ``head_modules``
    (tied embeddings) are owned by the stem; the head receives them as
    an explicit differentiation argument and the two cotangent
    contributions are summed — same 2-term sum autodiff produces for
    the GSPMD step, so parity holds.
    """

    def __init__(self, blocks, head, finalize, stem_modules=(),
                 head_modules=(), stem=None, context=None, name=""):
        self.block_entries = []
        for b in blocks:
            if isinstance(b, tuple):
                mods, fn = b
                self.block_entries.append((list(mods), fn))
            else:
                self.block_entries.append(
                    ([b], (lambda x, *ctx, _b=b: _b(x, *ctx))))
        self.head = head
        self.finalize = finalize
        self.stem_modules = list(stem_modules)
        self.head_modules = list(head_modules)
        self.stem = stem
        self.context = context
        self.name = name or "pipeline"

    # -- parameter-to-segment mapping ---------------------------------- #
    def segment_params(self, params, train_idx):
        """Partition the trainable parameter indices over the segments.

        Returns ``(stem_own, block_own, head_own, tied)`` — lists of
        indices into ``params``; ``tied`` are head-visible params owned
        by the stem. Raises on overlap between blocks or uncovered
        trainables."""
        train_set = set(train_idx)
        # identity on the Parameter object, not its data NDArray
        by_id = {id(params[i]): i for i in range(len(params))}

        def collect(modules):
            seen, out = set(), []
            for m in modules:
                # bare Parameters (e.g. a tied-decoder bias hung directly
                # off the model) are accepted alongside blocks
                ps = m.collect_params().values() \
                    if hasattr(m, "collect_params") else [m]
                for p in ps:
                    i = by_id.get(id(p))
                    if i is None or i not in train_set or i in seen:
                        continue
                    seen.add(i)
                    out.append(i)
            return sorted(out)

        stem_own = collect(self.stem_modules)
        head_raw = collect(self.head_modules)
        block_own, claimed = [], set(stem_own)
        for bi, (mods, _) in enumerate(self.block_entries):
            own = [i for i in collect(mods) if i not in claimed]
            dup = [i for i in collect(mods)
                   if i in claimed and i not in stem_own]
            if dup:
                raise MXNetError(
                    f"pipeline block {bi} shares trainable params "
                    f"{[params[i].name for i in dup]} with an earlier "
                    f"block — pipelined blocks must be disjoint")
            shared_stem = [i for i in collect(mods) if i in stem_own]
            if shared_stem:
                raise MXNetError(
                    f"pipeline block {bi} shares params "
                    f"{[params[i].name for i in shared_stem]} with the "
                    f"stem — tie params only between stem and head")
            block_own.append(own)
            claimed.update(own)
        tied = [i for i in head_raw if i in claimed]
        bad_tie = [i for i in tied if i not in stem_own]
        if bad_tie:
            raise MXNetError(
                f"head params {[params[i].name for i in bad_tie]} are "
                f"owned by a pipeline block — ties are only supported "
                f"between stem and head (the embedding/decoder pattern)")
        head_own = [i for i in head_raw if i not in claimed]
        claimed.update(head_own)
        missing = [params[i].name for i in train_idx if i not in claimed]
        if missing:
            raise MXNetError(
                f"pipeline spec does not cover trainable params "
                f"{missing}; add their blocks to stem_modules / blocks "
                f"/ head_modules")
        return stem_own, block_own, head_own, tied


# --------------------------------------------------------------------- #
# fsdp gather / slice against a param's PartitionSpec
# --------------------------------------------------------------------- #
def _spec_entries(spec, ndim):
    entries = list(spec) if spec is not None else []
    entries += [None] * (ndim - len(entries))
    return [tuple(e) if isinstance(e, (tuple, list)) else
            ((e,) if e is not None else ()) for e in entries]


def _gather_full(val, spec, mesh_shape):
    """All-gather a sharded param to its full value (ZeRO gather).
    Gathers minor (last-listed) axes first so the tile order matches
    the NamedSharding layout."""
    for d, axes in enumerate(_spec_entries(spec, val.ndim)):
        for ax in reversed(axes):
            if mesh_shape.get(ax, 1) > 1:
                val = lax.all_gather(val, ax, axis=d, tiled=True)
    return val


def _slice_local(val, spec, mesh_shape):
    """Slice a full (reduced) gradient back to the local shard."""
    for d, axes in enumerate(_spec_entries(spec, val.ndim)):
        live = [ax for ax in axes if mesh_shape.get(ax, 1) > 1]
        if not live:
            continue
        size = 1
        for ax in live:
            size *= mesh_shape[ax]
        idx = jnp.int32(0)
        for ax in live:  # major-first fold, matching the tile order
            idx = idx * mesh_shape[ax] + lax.axis_index(ax)
        local = val.shape[d] // size
        val = lax.dynamic_slice_in_dim(val, idx * local, local, axis=d)
    return val


def _is_sharded(spec, mesh_shape):
    return any(mesh_shape.get(ax, 1) > 1
               for axes in _spec_entries(spec, 64) for ax in axes)


# --------------------------------------------------------------------- #
# bucket collectives
# --------------------------------------------------------------------- #
def _reduce_bucket(vals, raxes, mode, int8, mesh_shape):
    """One bucket's in-program collective (the traced primitives live in
    collectives.py). Returns the reduced member list plus the ledger
    entry describing what was emitted."""
    if not raxes:
        return list(vals), {"op": "none"}
    if int8 and all(jnp.issubdtype(v.dtype, jnp.floating) for v in vals):
        out = int8_bucket_allreduce(vals, raxes)
        return out, {"op": "int8_psum",
                     "shapes": [tuple(v.shape) for v in vals]}
    if mode == "ring":
        if len(raxes) != 1:
            raise MXNetError(
                "grad_collective='ring' needs exactly one batch axis "
                f"with size > 1, got {raxes}")
        ax = raxes[0]
        flat = jnp.concatenate(
            [v.astype(jnp.float32).reshape(-1) for v in vals]) \
            if len(vals) > 1 else vals[0].astype(jnp.float32).reshape(-1)
        red = ring_allreduce_flat(flat, ax, mesh_shape[ax])
        out, off = [], 0
        for v in vals:
            out.append(red[off:off + v.size].reshape(v.shape)
                       .astype(v.dtype))
            off += v.size
        return out, {"op": "ring",
                     "shapes": [tuple(v.shape) for v in vals]}
    summed = lax.psum(tuple(vals), raxes)
    return list(summed), {"op": "psum",
                          "shapes": [tuple(v.shape) for v in vals]}


# --------------------------------------------------------------------- #
# the pipelined forward/backward core (runs inside shard_map)
# --------------------------------------------------------------------- #
def _pipelined_grads(trainer, spec, train_full, frozen_vals, key, batch,
                     scale, raxes, train_specs_by_idx, remat_plan):
    """Per-shard forward + per-segment backward with bucket collectives
    issued between pullbacks. ``train_full`` maps param index → FULL
    (gathered) value. Returns (loss_val, local grads by train_idx order,
    ledger)."""
    params = trainer._params
    train_idx = trainer._train_idx
    train_set = set(train_idx)
    mesh_shape = dict(trainer.mesh.shape)
    from ..gluon.block import _hybrid_trace_scope
    from ..models._remat import resolve_policy

    stem_own, block_own, head_own, tied = spec.segment_params(
        params, train_idx)

    member_info = [(i, int(params[i]._data._data.size),
                    int(params[i]._data._data.dtype.itemsize),
                    str(params[i]._data._data.dtype)) for i in train_idx]
    plan = plan_grad_buckets(member_info, _bucket_limit_bytes())
    sched = BucketSchedule(plan)

    int8 = bool(trainer._int8_allreduce)
    mode = trainer._grad_collective
    full_grads, local_grads, ledger = {}, {}, []

    tied_head_grads = {}

    def _issue(buckets):
        for b in buckets:
            vals = [full_grads[i] for i in b.indices]
            # tied params carry a second (head/decoder) cotangent: it
            # rides the same bucket collective as an extra operand and
            # is summed AFTER the reduction — GSPMD reduces the two
            # transpose partials independently before adding them, so
            # reducing their pre-added sum would break bitwise parity
            extra_idx = [i for i in b.indices if i in tied_head_grads]
            extras = [tied_head_grads[i] for i in extra_idx]
            red, entry = _reduce_bucket(vals + extras, raxes, mode, int8,
                                        mesh_shape)
            entry["key"] = b.key
            entry["indices"] = list(b.indices) + extra_idx
            ledger.append(entry)
            by_tied = dict(zip(extra_idx, red[len(vals):]))
            for i, g in zip(b.indices, red[:len(vals)]):
                if i in by_tied:
                    g = g + by_tied[i]
                sp = train_specs_by_idx[i]
                local_grads[i] = _slice_local(g, sp, mesh_shape) \
                    if _is_sharded(sp, mesh_shape) else g

    def _bind(idx_list, vals):
        for i, v in zip(idx_list, vals):
            params[i]._data = NDArray(v)

    saved = [p._data for p in params]
    frozen_idx = [i for i in range(len(params)) if i not in train_set]
    try:
        _bind(frozen_idx, frozen_vals)
        _bind(train_idx, [train_full[i] for i in train_idx])
        with _hybrid_trace_scope(), _random.key_provider(key), \
                autograd._ModeScope(recording=False, training=True):
            batch_nds = [NDArray(b) for b in batch]
            ctx = tuple(spec.context(*batch_nds)) if spec.context \
                else ()
            ctx_vals = tuple(c._data for c in ctx)

            def stem_fn(vals):
                _bind(stem_own, vals)
                x0 = spec.stem(*batch_nds) if spec.stem else batch_nds[0]
                return x0._data

            x, pull_stem = jax.vjp(
                stem_fn, tuple(train_full[i] for i in stem_own))

            pulls = []
            for bi, (mods, fn) in enumerate(spec.block_entries):
                own = block_own[bi]

                entry = remat_plan[bi] if remat_plan else False
                if entry:
                    # remat'd blocks take their RNG base key as an
                    # explicit input (the remat_call contract): provider
                    # state mutated inside the checkpoint trace would
                    # leak inner tracers, and an input key replays
                    # identically in the recompute pass
                    def block_fn_k(vals, xv, bkey, _own=own, _fn=fn):
                        _bind(_own, vals)
                        with _random.key_provider(bkey):
                            return _fn(NDArray(xv),
                                       *[NDArray(c) for c in ctx_vals]
                                       )._data

                    ck = jax.checkpoint(block_fn_k,
                                        policy=resolve_policy(entry))
                    x, pull3 = jax.vjp(
                        ck, tuple(train_full[i] for i in own), x,
                        _random.new_key())
                    pull = (lambda g, _p=pull3: _p(g)[:2])
                else:
                    def block_fn(vals, xv, _own=own, _fn=fn):
                        _bind(_own, vals)
                        return _fn(NDArray(xv),
                                   *[NDArray(c) for c in ctx_vals])._data

                    x, pull = jax.vjp(
                        block_fn, tuple(train_full[i] for i in own), x)
                pulls.append(pull)

            def head_fn(vals, tvals, xv):
                _bind(head_own, vals)
                _bind(tied, tvals)
                parts = spec.head(NDArray(xv), *batch_nds)
                return tuple(p._data if isinstance(p, NDArray) else p
                             for p in parts)

            partials, pull_head = jax.vjp(
                head_fn, tuple(train_full[i] for i in head_own),
                tuple(train_full[i] for i in tied), x)
            for p in partials:
                if getattr(p, "ndim", 0) != 0:
                    raise MXNetError(
                        f"PipelineSpec.head must return scalar local "
                        f"partial sums; got shape {p.shape}")
            # frozen params must come back untouched: the pipelined
            # body returns them as-is, so a mutating forward (BN
            # running stats) would silently drop its update — fail loud
            for i in frozen_idx:
                if params[i]._data._data is not (
                        frozen_vals[frozen_idx.index(i)]):
                    raise MXNetError(
                        f"pipelined step does not support parameter-"
                        f"mutating forwards (param {params[i].name} was "
                        f"reassigned, e.g. BatchNorm running stats); "
                        f"use the GSPMD step for this model")
    finally:
        for p, s in zip(params, saved):
            p._data = s

    # --- loss: psum the local partials, finalize on the globals ------- #
    g_partials = lax.psum(partials, raxes) if raxes else partials

    def fin(*gs):
        L = spec.finalize(*gs)
        L = L._data if isinstance(L, NDArray) else L
        return L * scale  # loss scaling rides the backward seed

    with profiler.scope("mx.loss"):
        loss_scaled, pull_fin = jax.vjp(fin, *g_partials)
        seeds = pull_fin(jnp.float32(1.0))
        loss_val = loss_scaled / scale

    # --- backward, deepest segment first, collectives interleaved ----- #
    g_head, g_tied_head, g_x = pull_head(seeds)
    for j, i in enumerate(tied):
        tied_head_grads[i] = g_tied_head[j]
    for i, g in zip(head_own, g_head):
        full_grads[i] = g
        _issue(sched.mark_ready(i))
    for bi in range(len(spec.block_entries) - 1, -1, -1):
        g_bvals, g_x = pulls[bi](g_x)
        for i, g in zip(block_own[bi], g_bvals):
            full_grads[i] = g
            _issue(sched.mark_ready(i))
    (g_stem,) = pull_stem(g_x)
    for i, g in zip(stem_own, g_stem):
        full_grads[i] = g
        _issue(sched.mark_ready(i))
    _issue(sched.drain())
    if len(sched.issued) != len(plan):  # pragma: no cover - invariant
        raise MXNetError("pipelined bucket schedule did not drain")

    grads = tuple(local_grads[i] for i in train_idx)
    return loss_val, grads, ledger


# --------------------------------------------------------------------- #
# step builders (mirror spmd._build_step / _build_accum_step)
# --------------------------------------------------------------------- #
def _pipeline_prereqs(trainer):
    mesh = trainer.mesh
    for ax in ("tp", "sp", "pp", "ep"):
        if mesh.shape.get(ax, 1) > 1:
            raise MXNetError(
                f"pipelined step supports dp/fsdp meshes only; axis "
                f"{ax!r} has size {mesh.shape[ax]} — use the GSPMD "
                f"step for tensor/sequence/pipeline-parallel models")
    from ..optimizer.fused import norm_based
    if trainer.sharding_mode == "fsdp" and norm_based(trainer._optimizer):
        raise MXNetError(
            f"pipelined fsdp step cannot run norm-based optimizer "
            f"{type(trainer._optimizer).__name__}: the fused update "
            f"would see shard-local norms")
    raxes = tuple(a for a in ("fsdp", "dp") if mesh.shape[a] > 1)
    return raxes


def _specs(trainer, n_batch):
    repl, batch_sh, train_sh, frozen_sh, state_sh = \
        trainer._step_shardings()
    return {
        "repl": repl, "batch_sh": batch_sh,
        "train": tuple(s.spec for s in train_sh),
        "frozen": tuple(s.spec for s in frozen_sh),
        "state": tuple(s.spec for s in state_sh),
        "batch": PartitionSpec(("fsdp", "dp")),
        "train_sh": train_sh, "frozen_sh": frozen_sh,
        "state_sh": tuple(state_sh),
        "n_batch": n_batch,
    }


def build_pipelined_step(trainer, n_batch):
    """The pipelined analogue of ``SPMDTrainer._build_step`` — same call
    signature, same outputs, same donation — so the host-side ``step``
    path runs unchanged."""
    raxes = _pipeline_prereqs(trainer)
    spec = trainer._pipeline
    params = trainer._params
    train_idx = trainer._train_idx
    optimizer = trainer._optimizer
    guard = trainer.guard
    mesh = trainer.mesh
    base_rescale = float(optimizer.rescale_grad)
    sp = _specs(trainer, n_batch)
    mesh_shape = dict(mesh.shape)
    train_specs_by_idx = {i: s for i, s in zip(train_idx, sp["train"])}
    remat_plan = trainer._remat_plan

    def pstep(train_vals, frozen_vals, opt_leaves, opt_tree, t, lr,
              scale, key, *batch):
        if not trainer._pipe_lowering:  # python body = trace time only
            trainer.step_trace_count += 1
            trainer.pipelined_step_trace_count += 1

        def body(train_vals, frozen_vals, opt_leaves, t, lr, scale,
                 key, *batch):
            full = {}
            for i, v in zip(train_idx, train_vals):
                s = train_specs_by_idx[i]
                full[i] = _gather_full(v, s, mesh_shape) \
                    if _is_sharded(s, mesh_shape) else v
            loss_val, grads, ledger = _pipelined_grads(
                trainer, spec, full, frozen_vals, key, batch, scale,
                raxes, train_specs_by_idx, remat_plan)
            if not trainer._pipe_lowering:
                trainer.pipelined_issue_ledger = ledger
                trainer.pipelined_bucket_order = [e["key"]
                                                 for e in ledger]
            opt_state = jtu.tree_unflatten(opt_tree, opt_leaves)
            from ..optimizer.fused import all_finite, apply_updates
            with profiler.scope("mx.optimizer"):
                new_train, new_states = apply_updates(
                    optimizer, train_idx, train_vals, grads, opt_state,
                    t, lr,
                    rescale_grad=jnp.float32(base_rescale) / scale)
            new_train = tuple(new_train)
            new_leaves = tuple(jtu.tree_leaves(tuple(new_states)))
            if guard:
                # guard verdict on the POST-collective grads (for int8:
                # the dequantized values), per-shard then pmin-combined
                # so fsdp shards agree — the PR-8 where-select skip
                with profiler.scope("mx.guard"):
                    ok_flag = all_finite(grads)
                    if raxes:
                        ok_flag = lax.pmin(ok_flag, raxes)
                    apply_p = ok_flag > 0
                    new_train = tuple(
                        jnp.where(apply_p, nw, w)
                        for nw, w in zip(new_train, train_vals))
                    new_leaves = tuple(
                        jnp.where(apply_p, nl, ol)
                        for nl, ol in zip(new_leaves, opt_leaves))
            else:
                ok_flag = jnp.float32(1.0)
            return (new_train, tuple(frozen_vals), new_leaves,
                    loss_val, ok_flag)

        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(sp["train"], sp["frozen"], sp["state"],
                      PartitionSpec(), PartitionSpec(), PartitionSpec(),
                      PartitionSpec()) + (sp["batch"],) * n_batch,
            out_specs=(sp["train"], sp["frozen"], sp["state"],
                       PartitionSpec(), PartitionSpec()),
            check_vma=False)
        return mapped(train_vals, frozen_vals, opt_leaves, t, lr,
                      scale, key, *batch)

    donate = (0, 2) if trainer.donate else ()
    repl = sp["repl"]
    return jax.jit(
        pstep, static_argnums=(3,),
        in_shardings=(sp["train_sh"], sp["frozen_sh"], sp["state_sh"],
                      repl, repl, repl, repl)
        + (sp["batch_sh"],) * n_batch,
        out_shardings=(sp["train_sh"], sp["frozen_sh"], sp["state_sh"],
                       repl, repl),
        donate_argnums=donate)


def build_pipelined_accum_step(trainer, n_batch):
    """Pipelined analogue of ``_build_accum_step`` — the same donated
    f32 accumulator carry, combined verdict and is_last-gated apply, so
    ``step_microbatches`` host code runs unchanged and k stays pure
    host data (one trace for k ∈ {1,4,8,...})."""
    raxes = _pipeline_prereqs(trainer)
    spec = trainer._pipeline
    train_idx = trainer._train_idx
    optimizer = trainer._optimizer
    guard = trainer.guard
    mesh = trainer.mesh
    base_rescale = float(optimizer.rescale_grad)
    sp = _specs(trainer, n_batch)
    mesh_shape = dict(mesh.shape)
    train_specs_by_idx = {i: s for i, s in zip(train_idx, sp["train"])}
    remat_plan = trainer._remat_plan

    def pastep(train_vals, frozen_vals, opt_leaves, opt_tree, acc_vals,
               acc_ok, acc_loss, t, lr, scale, inv_k, is_last, key,
               *batch):
        if not trainer._pipe_lowering:
            trainer.accum_step_trace_count += 1
            trainer.pipelined_accum_step_trace_count += 1

        def body(train_vals, frozen_vals, opt_leaves, acc_vals, acc_ok,
                 acc_loss, t, lr, scale, inv_k, is_last, key, *batch):
            full = {}
            for i, v in zip(train_idx, train_vals):
                s = train_specs_by_idx[i]
                full[i] = _gather_full(v, s, mesh_shape) \
                    if _is_sharded(s, mesh_shape) else v
            loss_val, grads, ledger = _pipelined_grads(
                trainer, spec, full, frozen_vals, key, batch, scale,
                raxes, train_specs_by_idx, remat_plan)
            if not trainer._pipe_lowering:
                trainer.pipelined_issue_ledger = ledger
                trainer.pipelined_bucket_order = [e["key"]
                                                 for e in ledger]
            with profiler.scope("mx.optimizer"):
                new_acc = tuple(a + g.astype(jnp.float32)
                                for a, g in zip(acc_vals, grads))
            from ..optimizer.fused import all_finite, apply_updates
            if guard:
                with profiler.scope("mx.guard"):
                    ok_here = all_finite(grads)
                    if raxes:
                        ok_here = lax.pmin(ok_here, raxes)
                    ok_round = acc_ok * ok_here
            else:
                ok_round = jnp.float32(1.0)
            loss_round = acc_loss + loss_val
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
            with profiler.scope("mx.optimizer"):
                acc_out = tuple(jnp.where(last_p, jnp.zeros_like(na), na)
                                for na in new_acc)
            acc_ok_out = jnp.where(last_p, jnp.float32(1.0), ok_round)
            acc_loss_out = jnp.where(last_p, jnp.float32(0.0),
                                     loss_round)
            return (new_train, tuple(frozen_vals), new_leaves, acc_out,
                    acc_ok_out, acc_loss_out, loss_round * inv_k,
                    ok_round)

        P = PartitionSpec
        mapped = shard_map(
            body, mesh=mesh,
            in_specs=(sp["train"], sp["frozen"], sp["state"],
                      sp["train"], P(), P(), P(), P(), P(), P(), P(),
                      P()) + (sp["batch"],) * n_batch,
            out_specs=(sp["train"], sp["frozen"], sp["state"],
                       sp["train"], P(), P(), P(), P()),
            check_vma=False)
        return mapped(train_vals, frozen_vals, opt_leaves, acc_vals,
                      acc_ok, acc_loss, t, lr, scale, inv_k, is_last,
                      key, *batch)

    donate = (0, 2, 4) if trainer.donate else ()
    repl = sp["repl"]
    return jax.jit(
        pastep, static_argnums=(3,),
        in_shardings=(sp["train_sh"], sp["frozen_sh"], sp["state_sh"],
                      sp["train_sh"], repl, repl, repl, repl, repl,
                      repl, repl, repl) + (sp["batch_sh"],) * n_batch,
        out_shardings=(sp["train_sh"], sp["frozen_sh"], sp["state_sh"],
                       sp["train_sh"], repl, repl, repl, repl),
        donate_argnums=donate)


# --------------------------------------------------------------------- #
# structural overlap assertion (CPU-runnable, lowered-text based)
# --------------------------------------------------------------------- #
def _collect_ops(text, op_names):
    """Walk StableHLO text and return ``[(line_no, op, result_shapes)]``
    in program order. Region-holding ops (all_reduce) print their type
    signature on the closing line; scan forward to the first ``->``."""
    import re
    shape_re = re.compile(r"tensor<([^>]*)>")
    lines = text.splitlines()
    out = []
    for n, line in enumerate(lines):
        hit = next((op for op in op_names
                    if "stablehlo." + op in line), None)
        if hit is None:
            continue
        if "->" not in line and (") ->" not in line):
            sig = ""
            for m in range(n, min(n + 200, len(lines))):
                if "->" in lines[m]:
                    sig = lines[m].split("->", 1)[1]
                    break
        else:
            sig = line.split("->", 1)[1] if "->" in line else line
        shapes = []
        for s in shape_re.findall(sig):
            dims = [d for d in s.split("x")[:-1]]
            try:
                shapes.append(tuple(int(d) for d in dims))
            except ValueError:
                shapes.append(tuple(dims))
        out.append((n, hit, shapes))
    return out


def structure_report(text, ledger):
    """Assertable structure facts from a pipelined step's lowered
    StableHLO against the trace-time issue ledger.

    Returns a dict with:
      - ``n_grad_collective_groups`` vs ``n_buckets`` — every bucket's
        collective made it into the program, as a distinct group;
      - ``order_matches_plan`` — the program-order shapes of the grad
        collectives equal the ledger's bucket-member shapes in plan
        order (the deterministic-rendezvous contract, now asserted on
        the *compiled* program);
      - ``interleaved`` — at least one backward ``dot_general`` sits
        strictly between the first and last grad collective, i.e. the
        collectives are interleaved with backward, not clustered after
        it (the PR-13 `overlap_ratio: 0.0` failure shape).
    Scalar all-reduces (loss partials, guard pmin, int8 amax pmax) are
    excluded by the rank filter."""
    ring = any(e.get("op") == "ring" for e in ledger)
    coll_op = "collective_permute" if ring else "all_reduce"
    ops = _collect_ops(text, [coll_op, "dot_general"])
    colls = [(n, shapes) for n, op, shapes in ops
             if op == coll_op and any(len(s) > 0 for s in shapes)]
    dots = [n for n, op, _ in ops if op == "dot_general"]

    # group consecutive collective ops (one bucket's members emit one
    # variadic op or several adjacent ops, no dot_general in between)
    groups = []
    for n, shapes in colls:
        if groups and not any(groups[-1][-1][0] < d < n for d in dots):
            groups[-1].append((n, shapes))
        else:
            groups.append([(n, shapes)])

    expected = [[tuple(s) for s in e.get("shapes", [])]
                for e in ledger if e.get("op") != "none"]
    # adjacent buckets issued from the same pullback print as one
    # textual group, so the order contract is on the FLAT program-order
    # shape sequence (bucket boundaries are the plan's, not the text's)
    exp_flat = [s for b in expected for s in b]
    got_flat = [s for g in groups for _, shapes in g for s in shapes]
    if ring:
        order_ok = len(got_flat) >= len(expected) > 0
    else:
        order_ok = got_flat == exp_flat
    interleaved = False
    if groups:
        first_end = groups[0][-1][0]
        last_start = groups[-1][0][0]
        interleaved = any(first_end < d < last_start for d in dots)
    return {
        "collective_op": coll_op,
        "n_buckets": len(expected),
        "n_grad_collective_groups": len(groups),
        "order_matches_plan": bool(order_ok),
        "interleaved": bool(interleaved),
        "n_backward_dots_between": sum(
            1 for d in dots
            if groups and groups[0][-1][0] < d < groups[-1][0][0]),
    }

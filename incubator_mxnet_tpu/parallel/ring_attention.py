"""Ring attention: exact attention over sequences sharded across devices.

The reference has NO long-context parallelism (SURVEY.md §5.7 — BERT-era
≤512 windows); this module is the TPU-native capability that subsumes it.
Sequence length is sharded over the mesh ``sp`` axis; each device holds a
Q/K/V block and K/V blocks rotate around the ring via ``lax.ppermute`` on
ICI while a numerically-stable streaming softmax (the flash-attention
recurrence) accumulates partial outputs. Compute on the current block
overlaps with the transfer of the next (XLA schedules the ppermute
asynchronously), so attention of length ``sp × T_blk`` runs with per-device
memory of one block — the Ring Attention construction (see PAPERS.md).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..base import MXNetError, pcast_varying, shard_map

__all__ = ["ring_self_attention", "ring_attention_block",
           "ring_flash_attention", "ring_flash_attention_block",
           "active_ring_mesh"]


def active_ring_mesh(seq_len: int):
    """The model-side gate for sequence-parallel attention dispatch:
    returns the ACTIVE SPMD mesh when it has an ``sp`` axis that divides
    ``seq_len`` and we are NOT recording on the eager tape (the ring call
    bypasses it), else None. Shared by every seq_parallel model."""
    from .. import autograd as _ag
    from .spmd import _ACTIVE_MESH
    mesh = _ACTIVE_MESH.get()
    if mesh is None or mesh.shape.get("sp", 1) <= 1 \
            or seq_len % mesh.shape["sp"] or _ag.is_recording():
        return None
    return mesh

_NEG_INF = -1e30


def _stream_block(q, k, v, acc, row_max, row_sum, mask, scale=1.0):
    """One flash-attention accumulation step.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); acc: (B, Tq, H, D);
    row_max/row_sum: (B, Tq, H); mask: additive, either (Tq, Tk) shared
    or (B, Tq, Tk) per-batch (the valid_length form), or None.
    """
    # dot operands keep their input dtype (bf16 rides the MXU at full
    # rate); scores/statistics accumulate in f32 with the scale applied
    # to the f32 scores (scaling a bf16 q would round it)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if mask is not None:
        # (Tq, Tk) shared mask or (B, Tq, Tk) per-batch (valid_length)
        scores = scores + (mask[None, None] if mask.ndim == 2
                           else mask[:, None])
    blk_max = scores.max(axis=-1)                       # (B,H,Tq)
    blk_max = jnp.moveaxis(blk_max, 1, -1)              # (B,Tq,H)
    new_max = jnp.maximum(row_max, blk_max)
    corr = jnp.exp(row_max - new_max)                   # (B,Tq,H)
    p = jnp.exp(scores - jnp.moveaxis(new_max, -1, 1)[..., None])  # (B,H,Tq,Tk)
    blk_out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
    blk_sum = jnp.moveaxis(p.sum(axis=-1), 1, -1)       # (B,Tq,H)
    acc = acc * corr[..., None] + blk_out
    row_sum = row_sum * corr + blk_sum
    return acc, new_max, row_sum


def ring_attention_block(q, k, v, valid_length=None,
                         axis_name: str = "sp",
                         causal: bool = False, scale: Optional[float] = None,
                         *, vary_axes: tuple = ()):
    """Per-shard ring attention body (call inside ``shard_map``).

    q, k, v: local blocks (B, T_blk, H, D); the global sequence is the
    concatenation over the ``axis_name`` mesh axis. ``valid_length``
    (B,) GLOBAL key lengths (the encoder key-padding form) masks keys at
    global positions >= the length. Returns the local output block.
    """
    B, Tq, H, D = q.shape
    n = lax.axis_index(axis_name)
    size = lax.psum(1, axis_name)
    if scale is None:
        scale = D ** -0.5

    acc = jnp.zeros(q.shape, jnp.float32)
    row_max = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    row_sum = jnp.zeros((B, Tq, H), jnp.float32)
    # constants enter the loop unvarying over the mesh axes while the loop
    # body produces device-varying values; align the carry's varying type
    # over EVERY axis the shard_map shards q over (sp plus the batch axis
    # when present — a dp x sp mesh otherwise trips the fori_loop carry
    # type check)
    cast_axes = (axis_name,) + tuple(a for a in vary_axes
                                     if a and a != axis_name)
    acc, row_max, row_sum = jax.tree_util.tree_map(
        lambda x: pcast_varying(x, cast_axes),
        (acc, row_max, row_sum))
    qf = q  # input dtype into the block einsums (f32 accumulation inside)

    pos_q = n * Tq + jnp.arange(Tq)

    def body(step, carry):
        acc, row_max, row_sum, k_cur, v_cur = carry
        # after `step` rotations device n holds the block of device n-step
        src = (n - step) % size
        pos_k = src * Tq + jnp.arange(k_cur.shape[1])
        mask = None
        if causal:
            mask = jnp.where(pos_k[None, :] <= pos_q[:, None], 0.0,
                             _NEG_INF)
        if valid_length is not None:
            vl_mask = jnp.where(
                pos_k[None, :] < valid_length.astype(jnp.int32)[:, None],
                0.0, _NEG_INF)                        # (B, Tk)
            vl_mask = jnp.broadcast_to(vl_mask[:, None],
                                       (vl_mask.shape[0], Tq,
                                        vl_mask.shape[1]))
            mask = vl_mask if mask is None else mask[None] + vl_mask
        acc, row_max, row_sum = _stream_block(
            qf, k_cur, v_cur, acc, row_max, row_sum, mask, scale=scale)
        # rotate k/v one hop around the ring (device i -> i+1)
        perm = [(i, (i + 1) % size) for i in range(size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, row_max, row_sum, k_nxt, v_nxt

    carry = (acc, row_max, row_sum, k, v)
    carry = lax.fori_loop(0, size, body, carry)
    acc, row_max, row_sum = carry[:3]
    out = acc / jnp.maximum(row_sum[..., None], 1e-30)
    # fully-masked rows (vl==0): row_max never rose from the additive
    # -inf floor and p degenerated to uniform — zero them (the same
    # masked-row contract as ops.pallas_attention / _sdpa_blockwise)
    out = jnp.where((row_max > _NEG_INF / 2)[..., None], out, 0.0)
    return out.astype(q.dtype)


def _ring_shard_map(make_block_fn, q, k, v, mesh, axis_name, batch_axis,
                    valid_length=None):
    """Shared wrapper: validate the mesh/sequence contract and shard_map
    the per-block ring function over (batch_axis, axis_name).

    ``make_block_fn(batch_axis_or_None) -> block_fn`` — a builder, so
    every engine resolves the mesh's actual batch axis (the dense block
    needs it for its fori_loop carry varying-type alignment).
    ``valid_length`` (B,) global key lengths ride along batch-sharded."""
    from . import mesh as _mesh_mod

    if mesh is None:
        mesh = _mesh_mod.default_mesh()
    if axis_name not in mesh.shape:
        raise MXNetError(f"mesh has no axis {axis_name!r}")
    sp = mesh.shape[axis_name]
    if q.shape[1] % sp != 0:
        raise MXNetError(
            f"sequence length {q.shape[1]} not divisible by {axis_name} "
            f"axis size {sp}")
    if batch_axis is None:
        b_axes = ()
    elif isinstance(batch_axis, str):
        b_axes = (batch_axis,)
    else:
        b_axes = tuple(batch_axis)
    b_axes = tuple(a for a in b_axes
                   if a in mesh.shape and mesh.shape[a] > 1)
    b_entry = b_axes if len(b_axes) > 1 else (
        b_axes[0] if b_axes else None)
    block_fn = make_block_fn(b_axes)  # resolve the per-mesh batch axes
    spec = PartitionSpec(b_entry, axis_name, None, None)
    in_specs = [spec, spec, spec]
    args = [q, k, v]
    if valid_length is not None:
        in_specs.append(PartitionSpec(b_entry))
        args.append(valid_length)
    mapped = shard_map(block_fn, mesh=mesh,
                       in_specs=tuple(in_specs), out_specs=spec)
    return mapped(*args)


def ring_self_attention(q, k, v, mesh: Optional[Mesh] = None,
                        axis_name: str = "sp", causal: bool = False,
                        scale: Optional[float] = None,
                        batch_axis: Optional[str] = "dp",
                        valid_length=None):
    """Exact self-attention with the sequence sharded over ``axis_name``.

    q, k, v: global (B, T, H, D) arrays; T must divide by the ``sp`` axis
    size. Returns (B, T, H, D). Differentiable (jax traces through the
    ppermute ring), jit-safe, and composable with data parallelism via
    ``batch_axis``.
    """
    def fn_builder(b_axes):
        return partial(ring_attention_block, axis_name=axis_name,
                       causal=causal, scale=scale, vary_axes=b_axes)
    return _ring_shard_map(fn_builder, q, k, v, mesh, axis_name,
                           batch_axis, valid_length=valid_length)


# --------------------------------------------------------------------- #
# ring attention with the Pallas flash kernel as the per-block engine
# --------------------------------------------------------------------- #

def _merge_partials(o1, lse1, o2, lse2):
    """Associatively combine two attention partial results carrying
    logsumexp (the flash merge rule): both (B,H,T,D)/(B,H,T)."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    den = jnp.maximum(w1 + w2, 1e-38)
    out = (o1.astype(jnp.float32) * w1[..., None]
           + o2.astype(jnp.float32) * w2[..., None]) / den[..., None]
    return out, m + jnp.log(den)


def _block_vl(step, n, size, B, Tq, causal):
    """Key-validity gating for ring step ``step``: the held block is
    (n - step) mod size — under causality fully visible iff it precedes
    ours, else fully masked (vl=0 ⇒ kernel masks everything ⇒ merge
    weight ~0 in forward, zero gradient in backward)."""
    if not causal:
        return jnp.full((B,), Tq, jnp.int32)
    allowed = (n - step) % size < n
    return jnp.where(allowed, Tq, 0) * jnp.ones((B,), jnp.int32)


def _block_bwd_any(q, k, v, vl, out, lse, g, causal, scale, interpret):
    """Per-block backward against the GLOBAL logsumexp — the ring/flash
    backward identity: p_ij = exp(s_ij - LSE_i) is exact for every block
    once LSE is the full-row normalizer. Pallas kernels on TPU (or
    interpret mode), the shared residual-based dense math otherwise."""
    from ..ops.pallas_attention import (_dense_block_bwd, _dense_hpp,
                                        _flash_backward, _use_dense,
                                        pallas_path)

    if pallas_path(interpret):
        dense = _use_dense(q.shape[2], k.shape[2])
        return _flash_backward(q, k, v, vl, out, lse, g, causal=causal,
                               scale=scale, interpret=interpret,
                               dense=dense,
                               hpp=_dense_hpp(q.shape[1], bwd=True)
                               if dense else None)
    return _dense_block_bwd(q, k, v, vl, out, lse, g, causal, scale)


def _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale, interpret):
    from ..ops.pallas_attention import block_attn_lse

    B, Tq, H, D = q.shape
    n = lax.axis_index(axis_name)
    size = lax.psum(1, axis_name)

    qt = q.transpose(0, 2, 1, 3)                       # (B, H, T, D)
    full_vl = jnp.full((B,), Tq, jnp.int32)

    out0, lse0 = block_attn_lse(qt, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), full_vl,
                                causal, scale, interpret)
    out0 = out0.astype(jnp.float32)

    def body(step, carry):
        out, lse, k_cur, v_cur = carry
        perm = [(i, (i + 1) % size) for i in range(size)]
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        vl = _block_vl(step, n, size, B, Tq, causal)
        o_b, lse_b = block_attn_lse(qt, k_cur.transpose(0, 2, 1, 3),
                                    v_cur.transpose(0, 2, 1, 3), vl,
                                    False, scale, interpret)
        out, lse = _merge_partials(out, lse, o_b.astype(jnp.float32),
                                   lse_b)
        return out, lse, k_cur, v_cur

    out, lse, _, _ = lax.fori_loop(1, size, body, (out0, lse0, k, v))
    return out.transpose(0, 2, 1, 3).astype(q.dtype), lse


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def ring_flash_attention_block(q, k, v, axis_name: str = "sp",
                               causal: bool = False,
                               scale: Optional[float] = None,
                               interpret: bool = False):
    """Ring attention with the Pallas flash kernel per block (call inside
    shard_map; q/k/v local blocks (B, T_blk, H, D)).

    Forward: each ring step computes its block's (out, logsumexp) with
    ``block_attn_lse`` and merges partials with the flash merge rule.
    Backward: a SECOND ring where each step runs the per-block flash
    backward against the global logsumexp (the p = exp(s - LSE)
    identity), accumulating dq locally while the dk/dv accumulators
    ride the ring home with their blocks — the Ring Attention backward
    schedule (PAPERS.md)."""
    out, _ = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                  interpret)
    return out


def _ring_flash_fwd(q, k, v, axis_name, causal, scale, interpret):
    out, lse = _ring_flash_fwd_impl(q, k, v, axis_name, causal, scale,
                                    interpret)
    return out, (q, k, v, out, lse)


def _ring_flash_bwd(axis_name, causal, scale, interpret, res, g):
    q, k, v, out, lse = res
    B, Tq, H, D = q.shape
    n = lax.axis_index(axis_name)
    size = lax.psum(1, axis_name)

    qt = q.transpose(0, 2, 1, 3)
    gt = g.transpose(0, 2, 1, 3).astype(jnp.float32)
    ot = out.transpose(0, 2, 1, 3)
    full_vl = jnp.full((B,), Tq, jnp.int32)

    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    dq0, dk0, dv0 = _block_bwd_any(qt, kt, vt, full_vl, ot, lse, gt,
                                   causal, scale, interpret)

    def body(step, carry):
        dq, dk_cur, dv_cur, k_cur, v_cur = carry
        perm = [(i, (i + 1) % size) for i in range(size)]
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
        vl = _block_vl(step, n, size, B, Tq, causal)
        dq_b, dk_b, dv_b = _block_bwd_any(qt, k_cur, v_cur, vl, ot, lse,
                                          gt, False, scale, interpret)
        return (dq + dq_b.astype(jnp.float32),
                dk_cur + dk_b.astype(jnp.float32),
                dv_cur + dv_b.astype(jnp.float32), k_cur, v_cur)

    dq, dk_cur, dv_cur, _, _ = lax.fori_loop(
        1, size, body, (dq0.astype(jnp.float32), dk0.astype(jnp.float32),
                        dv0.astype(jnp.float32), kt, vt))
    # one final hop brings each block's accumulated dk/dv home
    perm = [(i, (i + 1) % size) for i in range(size)]
    dk_home = lax.ppermute(dk_cur, axis_name, perm)
    dv_home = lax.ppermute(dv_cur, axis_name, perm)
    return (dq.transpose(0, 2, 1, 3).astype(q.dtype),
            dk_home.transpose(0, 2, 1, 3).astype(k.dtype),
            dv_home.transpose(0, 2, 1, 3).astype(v.dtype))


ring_flash_attention_block.defvjp(_ring_flash_fwd, _ring_flash_bwd)


def ring_flash_attention(q, k, v, mesh: Optional[Mesh] = None,
                         axis_name: str = "sp", causal: bool = False,
                         scale: Optional[float] = None,
                         batch_axis: Optional[str] = "dp",
                         interpret: bool = False):
    """ring_self_attention with the Pallas flash kernel as the per-block
    engine (TPU hot path; ``interpret=True`` runs the same kernels on
    CPU). Same contract: global (B, T, H, D), T divisible by the sp
    size, differentiable end to end."""
    return _ring_shard_map(
        lambda b_axes: partial(ring_flash_attention_block,
                               axis_name=axis_name, causal=causal,
                               scale=scale, interpret=interpret),
        q, k, v, mesh, axis_name, batch_axis)

"""The plain reference's three training steps, and the numbers compared.

The reference is given the generated weights and the same three batches as
the program, and computes in float32 at ``HIGHEST`` precision, in blocks of
rows (gradients of each block's part of the batch loss are added up), so that
it fits beside nothing but itself: it runs once the program's state is freed.

Numbers compared (all gaps, smaller is closer):
  loss1..loss3  |program's loss - reference's| / |reference's|, each step
  grad_norm     worst leaf of |‖g_p‖ - ‖g_r‖| / max(‖g_r‖, median leaf ‖g_r‖),
                the first step's gradient as the optimizer got it
  change_norm   the same of the parameters' change after the three steps,
                over the leaves whose reference gradient is at least a
                thousandth of the median leaf's (the others move by round-off
                alone under Adam or LAMB)
  change_med    the median leaf's gap of the change, which is steadier
  copy_gap      share of the low-precision compute weights' elements that
                differ from their float32 master rounded to that type: the
                plain rule of mixed precision, which the next forward rests
                on (0 for the reference, which keeps no copy)
"""

import statistics

import jax
import jax.numpy as jnp

from . import plain_optim, refops, weights as W


def _blocks(batch, rows_per_block):
    n = next(iter(batch.values())).shape[0]
    for s in range(0, n, rows_per_block):
        yield {k: v[s:s + rows_per_block] for k, v in batch.items()}


def reference_steps(ref, config, job, weights, batches, mode="f32",
                    rows=None):
    """Run the reference over ``batches`` (a list of {field: numpy array}).
    ``rows``: a slice of each batch's rows to train on instead of all (how a
    planted fault is read: half of the batch, or one chip's share).
    Returns {"loss": [..], "grad": {leaf: norm}, "change": {leaf: norm}}."""
    spec = ref.param_spec(config)
    stacked = {name: W.is_stacked(kind) for name, _, _, kind in spec}
    parts = {name: W.parts(kind) for name, _, _, kind in spec}
    ops = refops.Ops(mode)
    rpb = int(job["check"]["rows_per_block"])

    def contrib(p, block, den):
        return ref.loss_contrib(p, block, den, config, ops, refops)

    grad_fn = jax.jit(jax.value_and_grad(contrib))
    add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                  donate_argnums=(0,))
    update = plain_optim.make_update(
        job["optimizer"],
        {k: v for k, v in job["hyper"].items()}, stacked)
    to_f32 = jax.jit(lambda t: jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32), t))
    p = to_f32(weights)
    m = jax.tree_util.tree_map(jnp.zeros_like, p)
    v = jax.tree_util.tree_map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, start=1):
            if rows is not None:
                batch = {k: a[rows] for k, a in batch.items()}
            den = ref.denominators(batch)
            loss, g = 0.0, None
            for block in _blocks(batch, rpb):
                l_b, g_b = grad_fn(p, block, den)
                loss += float(l_b)
                g = g_b if g is None else add(g, g_b)
            losses.append(loss)
            if t == 1:
                grad_norms = jax.device_get(
                    plain_optim.leaf_norms(g, stacked, parts))
            p, m, v = update(p, g, m, v, jnp.float32(t))
        del m, v, g
        diff = jax.jit(lambda a, b: jax.tree_util.tree_map(
            lambda x, y: x - y.astype(jnp.float32), a, b),
            donate_argnums=(0,))(p, weights)
        change = jax.device_get(plain_optim.leaf_norms(diff, stacked, parts))
    return {"loss": losses,
            "grad": {k: float(x) for k, x in grad_norms.items()},
            "change": {k: float(x) for k, x in change.items()}}


def _leaf_gaps(got, want, floor):
    return {k: abs(got[k] - want[k]) / max(want[k], floor) for k in want}


def gaps(got, want):
    """The numbers compared, of ``got`` (the program, a control or a fault)
    against ``want`` (the reference). Also the leaves behind the worst."""
    out, detail = {}, {}
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), start=1):
        out[f"loss{i}"] = abs(a - b) / abs(b)
    g_med = statistics.median(want["grad"].values())
    g = _leaf_gaps(got["grad"], want["grad"], g_med)
    out["grad_norm"] = max(g.values())
    detail["grad_norm"] = max(g, key=g.get)
    moved = [k for k, n in want["grad"].items() if n >= 1e-3 * g_med]
    c_med = statistics.median(want["change"][k] for k in moved)
    c = _leaf_gaps({k: got["change"][k] for k in moved},
                   {k: want["change"][k] for k in moved}, c_med)
    out["change_norm"] = max(c.values())
    out["change_med"] = statistics.median(c.values())
    detail["change_norm"] = max(c, key=c.get)
    detail["leaves_left_out"] = sorted(set(want["grad"]) - set(moved))
    out["copy_gap"] = float(got.get("copy", 0.0))
    return out, detail

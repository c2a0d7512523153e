"""Plain float32 optimizers for the training reference, written from the
published rules and kept apart from the program's.

LAMB: You et al., "Large Batch Optimization for Deep Learning" (ICLR 2020),
algorithm 2 with bias correction; the trust ratio is per leaf (per layer slice
of a stacked leaf) and 1 where either norm is 0. AdamW: Loshchilov & Hutter
(ICLR 2019) in MXNet 1.x's form (``contrib.adamw_update``): the bias
correction is folded into the rate and the decay ``wd * w`` is not multiplied
by the rate.
"""

import jax
import jax.numpy as jnp


def _axes(x, stacked):
    return tuple(range(1, x.ndim)) if stacked else None


def _norm(x, stacked):
    return jnp.sqrt(jnp.sum(jnp.square(x), axis=_axes(x, stacked),
                            keepdims=stacked))


def lamb(p, g, m, v, t, stacked, lr, beta1=0.9, beta2=0.999, epsilon=1e-6,
         wd=0.0):
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    m_hat = m / (1.0 - beta1 ** t)
    v_hat = v / (1.0 - beta2 ** t)
    u = m_hat / (jnp.sqrt(v_hat) + epsilon) + wd * p
    r1, r2 = _norm(p, stacked), _norm(u, stacked)
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / jnp.where(r2 > 0, r2, 1.0),
                      1.0)
    return p - lr * ratio * u, m, v


def adamw(p, g, m, v, t, stacked, lr, beta1=0.9, beta2=0.999, epsilon=1e-8,
          wd=0.0):
    m = beta1 * m + (1.0 - beta1) * g
    v = beta2 * v + (1.0 - beta2) * jnp.square(g)
    lr_t = lr * jnp.sqrt(1.0 - beta2 ** t) / (1.0 - beta1 ** t)
    return p - (lr_t * m / (jnp.sqrt(v) + epsilon) + wd * p), m, v


RULES = {"lamb": lamb, "adamw": adamw}


def make_update(name, hyper, stacked_of):
    """Jitted whole-tree update: (params, grads, m, v, t) -> (params, m, v).
    ``stacked_of``: {leaf name: bool}."""
    rule = RULES[name]
    hyper = {k: float(v) for k, v in hyper.items()}

    def update(params, grads, m, v, t):
        out = {k: rule(params[k], grads[k], m[k], v[k], t, stacked_of[k],
                       **hyper) for k in params}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()})
    return jax.jit(update, donate_argnums=(0, 2, 3))


def leaf_norms(tree, stacked_of, parts_of):
    """{compared leaf name: norm}, each a scalar on the device. A stacked
    leaf gives a norm for each layer, a fused one for each part."""
    from .weights import part_name
    out = {}
    for k, x in tree.items():
        n_parts = parts_of[k]
        x = x.astype(jnp.float32)
        if stacked_of[k]:
            n = jnp.sqrt(jnp.sum(jnp.square(
                x.reshape(x.shape[0], n_parts, -1)), axis=-1))
            for l in range(x.shape[0]):
                for j in range(n_parts):
                    out[part_name(k, l, j, n_parts)] = n[l, j]
        else:
            n = jnp.sqrt(jnp.sum(jnp.square(x.reshape(n_parts, -1)), axis=-1))
            for j in range(n_parts):
                out[part_name(k, None, j, n_parts)] = n[j]
    return out

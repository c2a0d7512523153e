"""Weights from the seed: one jitted call on the device, in the type each leaf
is served or trained in. The reference and the system under test are both
given what this makes; neither is given anything the other made.

A configuration's reference declares its leaves with ``param_spec(config)``:
``[(name, shape, dtype, kind)]`` where a leaf with a leading layer axis has
``/L`` in its ``kind``; one that is several tensors fused along its first
own axis (q|k|v) ends in ``:3`` and is compared part by part, since a key's
bias has no gradient under softmax while the query's and value's have. Kinds: ``matrix`` N(0, 0.02); ``bias`` N(0, 0.02);
``gamma`` 1 + N(0, 0.02); ``beta`` N(0, 0.02).
"""

import jax
import jax.numpy as jnp


def seed_key(seed):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _draw(key, shape, dtype, kind):
    x = 0.02 * jax.random.normal(key, shape, jnp.float32)
    if kind.split("/")[0].split(":")[0] == "gamma":
        x = 1.0 + x
    return x.astype(dtype)


def make_weights(spec, seed, sharding=None):
    """{name: array} for a spec, made on the device in one program."""
    def gen(key):
        return {name: _draw(jax.random.fold_in(key, i), tuple(shape),
                            jnp.dtype(dtype), kind)
                for i, (name, shape, dtype, kind) in enumerate(spec)}
    fn = jax.jit(gen, out_shardings=sharding) if sharding is not None \
        else jax.jit(gen)
    return fn(seed_key(seed))


def is_stacked(kind):
    return "/L" in kind


def parts(kind):
    return int(kind.split(":")[1]) if ":" in kind else 1


def part_name(leaf, layer, part, n_parts):
    """Name of a leaf as it is compared: ``name``, ``name[l]`` for a layer
    of a stacked leaf, ``name[l].j`` for part j of a fused one."""
    name = leaf if layer is None else f"{leaf}[{layer}]"
    return name if n_parts == 1 else f"{name}.{part}"

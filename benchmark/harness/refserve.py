"""The served tokens against the plain reference.

For each sampled request the reference runs once over the prompt with its
served tokens (float32, ``HIGHEST``, no cache, no kernel) and reads, at each
served position, how far the served token's logit lies below the reference's
best. That is valid for greedy tokens, which is all this traffic sends.
``logit_gap`` is the widest such gap over the sample. The control is the same
reference in the nearest precision below the configuration's, put in the
program's place: at each of the same served positions, the gap of the token
that the lower precision puts first.
"""

import jax
import jax.numpy as jnp
import numpy as np

from . import refops


def sample(done, k, seed):
    """``k`` of the finished requests, drawn from the seed, the longest
    (prompt and served tokens together) always among them."""
    if not done:
        return []
    size = [r["n_prompt"] + len(r["req"].token_ids) for r in done]
    longest = int(np.argmax(size))
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 0xC0FFEE])
    rest = [i for i in range(len(done)) if i != longest]
    pick = list(rng.permutation(rest)[:max(0, k - 1)])
    return [done[i] for i in [longest] + sorted(pick)]


def score(ref, config, weights, requests, control=None):
    """({"logit_gap": widest gap}, {"program": {...}, "control": {...}}).
    Both gaps are read at the served positions of the same prompts and
    tokens."""
    max_len = int(config["n_positions"])
    n_pos = max((len(r["req"].token_ids) for r in requests), default=1)

    @jax.jit
    def served_gap(p, ids, pos, toks, n_t):
        logits = ref.logits_at(p, ids, pos, config, refops.Ops("f32"), refops)
        best = logits.max(axis=-1)
        got = jnp.take_along_axis(logits, toks[:, None], axis=-1)[:, 0]
        live = jnp.arange(pos.shape[0]) < n_t
        return (jnp.max(jnp.where(live, best - got, 0.0)),
                jnp.std(logits, where=live[:, None]))

    @jax.jit
    def control_gap(p, ids, pos, n_t):
        logits = ref.logits_at(p, ids, pos, config, refops.Ops("f32"), refops)
        low = ref.logits_at(p, ids, pos, config, refops.Ops(control), refops)
        first = jnp.argmax(low, axis=-1)
        got = jnp.take_along_axis(logits, first[:, None], axis=-1)[:, 0]
        live = jnp.arange(pos.shape[0]) < n_t
        return jnp.max(jnp.where(live, logits.max(-1) - got, 0.0))

    gap, ctl_gap, spread = 0.0, 0.0, []
    with jax.default_matmul_precision("highest"):
        for r in requests:
            toks = np.asarray(r["req"].token_ids, np.int32)
            n_p, n_t = r["n_prompt"], len(toks)
            ids = np.zeros(max_len, np.int32)
            ids[:n_p] = r["req"].prompt_ids
            ids[n_p:n_p + n_t - 1] = toks[:-1]
            pos = np.zeros(n_pos, np.int32)
            pos[:n_t] = np.arange(n_p - 1, n_p - 1 + n_t)
            tk = np.zeros(n_pos, np.int32)
            tk[:n_t] = toks
            g, sd = served_gap(weights, ids, pos, tk, n_t)
            gap = max(gap, float(g))
            spread.append(float(sd))
            if control:
                ctl_gap = max(ctl_gap, float(
                    control_gap(weights, ids, pos, n_t)))
    numbers = {"logit_gap": gap}
    readings = {"program": {"logit_gap": gap,
                            "logit_std": float(np.mean(spread or [0.0]))}}
    if control:
        readings["control"] = {"logit_gap": ctl_gap}
    return numbers, readings

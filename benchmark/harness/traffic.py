"""The one general traffic generator: it reads a mix's file of parameters.

Serving mixes (``"kind": "serve_open_loop"``): arrival and length draws are
copied from ``tools/serve_bench.py`` (``_make_requests`` Poisson arrivals,
``_persona_requests`` shared prefixes) and changed in two ways. Requests are
timed from the instant they are due, and every seed gets the same schedule:
sizes, personas and gaps are drawn from the file's ``sizes_seed``, the run's
seed draws the token ids (and the weights). A first version gave each seed
the same set in another order; with some 70 requests in a window of three
residence times the order alone moved the tokens emitted in the window by
+-7% (PERF.md), so the order is part of the mix.

Training jobs (``"kind": "train"``): a fresh batch of rows for every step,
each field filled by its kind.
"""

import numpy as np


def _rng(*ints):
    return np.random.default_rng([int(i) & 0xFFFFFFFFFFFFFFFF for i in ints])


# ------------------------------------------------------------------ train

def train_batches(fields, finish, rows, vocab, seq_len, seed):
    """Endless stream of batches: {field: int32/float32 array (rows, ...)}."""
    rng = _rng(seed, 0x7A11)
    while True:
        raw = {}
        for name, shape, kind in fields:
            full = (rows,) + tuple(shape)
            if kind == "token":
                a = rng.integers(0, vocab, full, dtype=np.int32)
            elif kind in ("segment", "binary"):
                a = rng.integers(0, 2, full, dtype=np.int32)
            elif kind == "full_length":
                a = np.full(full, seq_len, np.int32)
            elif kind == "position":
                a = rng.integers(0, seq_len, full, dtype=np.int32)
            elif kind == "ones":
                a = np.ones(full, np.float32)
            else:
                raise ValueError(f"unknown batch field kind {kind!r}")
            raw[name] = a
        yield finish(raw)


# ------------------------------------------------------------------ serve

def _lengths(rng, spec, n):
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    elif spec["dist"] == "uniform":
        x = rng.uniform(spec["min"], spec["max"] + 1, n)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, mix, n, span):
    """``n`` inter-arrival gaps that add up to ``span`` seconds."""
    if mix["arrivals"] == "poisson":
        g = rng.exponential(1.0, n)
    elif mix["arrivals"] == "gamma":
        g = rng.gamma(mix["gamma_shape"], 1.0, n)
    elif mix["arrivals"] == "uniform":
        g = np.ones(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
    return g * (span / g.sum())


def _segment(mix, rate, span, tag, seed, vocab, t0):
    """Requests of one stretch of the run, ``span`` seconds from ``t0``."""
    n = int(round(rate * span))
    if n <= 0:
        return []
    fixed = _rng(mix["sizes_seed"], tag, n)
    gaps = _gaps(fixed, mix, n, span)
    out_len = _lengths(fixed, mix["output"], n)
    shared = mix.get("shared_prefix")
    if shared:
        ranks = np.arange(1, shared["personas"] + 1, dtype=np.float64)
        p = ranks ** -float(shared["zipf"])
        persona = fixed.choice(shared["personas"], size=n, p=p / p.sum())
        own_len = _lengths(fixed, shared["suffix"], n)
    else:
        persona = np.full(n, -1)
        own_len = _lengths(fixed, mix["prompt"], n)
    due = t0 + np.cumsum(gaps) - gaps[0] * 0.5
    ids = _rng(seed, tag, 2)
    reqs = []
    for i in range(n):
        plen = int(own_len[i])
        pre = shared["prefix_len"] if shared else 0
        olen = int(min(out_len[i], mix["max_total"] - plen - pre))
        reqs.append({
            "due": float(due[i]), "persona": int(persona[i]),
            "own_ids": ids.integers(0, vocab, plen, dtype=np.int32),
            "max_new_tokens": max(1, olen), "segment": tag})
    return reqs


def persona_prefixes(mix, vocab):
    """The fixed system prompts of a shared-prefix mix (none otherwise): the
    same for every seed, as a deployment's are."""
    shared = mix.get("shared_prefix")
    if not shared:
        return []
    rng = _rng(mix["sizes_seed"], 0x5EED)
    return [rng.integers(0, vocab, shared["prefix_len"], dtype=np.int32)
            for _ in range(shared["personas"])]


def serve_requests(mix, rate, seconds, seed, vocab, postroll_s):
    """Requests of a run in order of their due time (seconds from the
    stream's start): pre-roll ``[0, preroll_s)`` to reach a steady state,
    the window ``[preroll_s, preroll_s + seconds)``, and post-roll traffic
    that keeps the load up while the window's last requests finish."""
    pre = float(mix["preroll_s"])
    prefixes = persona_prefixes(mix, vocab)
    reqs = (_segment(mix, rate, pre, 1, seed, vocab, 0.0)
            + _segment(mix, rate, seconds, 2, seed, vocab, pre)
            + _segment(mix, rate, postroll_s, 3, seed, vocab, pre + seconds))
    for r in reqs:
        own = r.pop("own_ids")
        r["prompt_ids"] = (np.concatenate([prefixes[r["persona"]], own])
                           if r["persona"] >= 0 else own)
        r["in_window"] = r["segment"] == 2
    return reqs, prefixes

"""What the adapters share: placing generated weights into the program's
parameters and reading the trainer's state back by the reference's leaf names.
This is the only part of the benchmark that knows the program's insides; the
seams it leans on are listed in PERF.md (Open questions)."""

import jax
import jax.numpy as jnp


def install(pairs, weights):
    """``pairs``: [(leaf, layer index or None, Parameter)]. Gives each
    parameter its generated array (a layer's slice of a stacked leaf)."""
    from incubator_mxnet_tpu.ndarray import NDArray
    for leaf, layer, param in pairs:
        arr = weights[leaf] if layer is None else weights[leaf][layer]
        param.set_data(NDArray(arr))


def _part_norms(xs, ns):
    """Norms of each array's ``n`` equal parts along its first axis."""
    return jnp.concatenate([jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32).reshape(n, -1)), axis=-1))
        for x, n in zip(xs, ns)])


class TrainSut:
    """A compiled training step with its state: ``SPMDTrainer.step`` is the
    entry the window drives."""

    def __init__(self, trainer, pairs, order, beta1):
        self.trainer = trainer
        self.pairs = pairs
        self.order = order
        self.beta1 = float(beta1)
        self.parts = {}             # {leaf: parts it is compared in}
        self._norms = jax.jit(_part_norms, static_argnums=(1,))
        self._diff_norms = jax.jit(
            lambda xs, ys, ns: _part_norms(
                [x.astype(jnp.float32) - y.astype(jnp.float32)
                 for x, y in zip(xs, ys)], ns), static_argnums=(2,))
        from harness.refops import round_to
        self._differ = jax.jit(lambda xs, ms: sum(
            jnp.sum(x.astype(jnp.float32) != round_to(m, x.dtype))
            for x, m in zip(xs, ms)))

    def step(self, batch):
        """One step through the program's own entry; returns the loss (an
        array on the device; the step has ended when this returns, since the
        trainer reads its applied flag back)."""
        return self.trainer.step(*[batch[k] for k in self.order])._data

    def _state(self):
        """[(master weights, first moment)] by pair, from the optimizer's
        state: ``(master, (mean, var))`` for a low-precision parameter under
        multi_precision, ``(mean, var)`` for a float32 one."""
        tr = self.trainer
        slot_of = {id(tr._params[i]): s for s, i in enumerate(tr._train_idx)}
        out = []
        for leaf, layer, param in self.pairs:
            st = tr._opt_state[slot_of[id(param)]]
            if isinstance(st[1], (tuple, list)):
                master, mean = st[0]._data, st[1][0]._data
            else:
                master, mean = param.data()._data, st[0]._data
            out.append((master, mean))
        return out

    def _names(self):
        from harness.weights import part_name
        ns = tuple(self.parts.get(leaf, 1) for leaf, _, _ in self.pairs)
        names = [part_name(leaf, layer, j, n)
                 for (leaf, layer, _), n in zip(self.pairs, ns)
                 for j in range(n)]
        return names, ns

    def gradient_norms(self):
        """After the first step: the norm of each leaf's gradient as the
        optimizer got it, from its first moment ``(1 - beta1) * g``."""
        names, ns = self._names()
        norms = jax.device_get(self._norms(
            [mean for _, mean in self._state()], ns))
        return {n: float(v) / (1.0 - self.beta1) for n, v in zip(names, norms)}

    def change_norms(self, weights):
        """The norm of each leaf's change from the generated weights."""
        names, ns = self._names()
        now = [master for master, _ in self._state()]
        then = [weights[leaf] if layer is None else weights[leaf][layer]
                for leaf, layer, _ in self.pairs]
        norms = jax.device_get(self._diff_norms(now, then, ns))
        return {n: float(v) for n, v in zip(names, norms)}

    def copy_gap(self, stale=None):
        """Share of the low-precision parameters' elements that differ from
        their float32 master rounded to the parameter's type: what the next
        forward reads against what the update wrote. With ``stale`` (the
        generated weights) in the parameters' place: what a copy that was
        never refreshed would read."""
        now, want = [], []
        for (leaf, layer, param), (master, _) in zip(self.pairs,
                                                     self._state()):
            have = param.data()._data
            if have.dtype == master.dtype:
                continue
            if stale is not None:
                have = stale[leaf] if layer is None else stale[leaf][layer]
            now.append(have)
            want.append(master)
        if not now:
            return 0.0
        return float(self._differ(now, want)) / sum(x.size for x in now)

    def counters(self):
        snap = self.trainer.health_snapshot()
        health = snap.get("health", {})
        return {"step_trace_count": int(self.trainer.step_trace_count),
                "steps_applied": int(self.trainer.step_count),
                "health": {k: v for k, v in health.items()
                           if isinstance(v, (int, float))}}

    def close(self):
        self.trainer = None
        self.pairs = None


def build_trainer(model, pairs, loss_fn, job, order, n_chips):
    import jax
    from incubator_mxnet_tpu import parallel
    from incubator_mxnet_tpu.parallel import mesh as pmesh
    mesh = pmesh.build_mesh(devices=jax.devices()[:n_chips],
                            axis_sizes={"dp": n_chips})
    hyper = dict(job["hyper"])
    params = {"learning_rate": hyper.pop("lr"), "multi_precision": True}
    params.update(hyper)
    trainer = parallel.SPMDTrainer(
        model, forward_loss=loss_fn, optimizer=job["optimizer"],
        optimizer_params=params, mesh=mesh, sharding="replicated")
    return TrainSut(trainer, pairs, order, job["hyper"].get("beta1", 0.9))

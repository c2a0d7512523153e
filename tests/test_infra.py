"""Tests for the infra shims: runtime features, engine, util, profiler, AMP,
mx.np / mx.npx (SURVEY.md §5.1/5.2/5.6 + §2.2 AMP/numpy rows)."""

import json
import os

import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, autograd


def test_runtime_features():
    feats = mx.runtime.Features()
    assert feats.is_enabled("XLA")
    assert feats.is_enabled("CPU")
    assert "DIST_KVSTORE" in feats
    names = {f.name for f in mx.runtime.feature_list()}
    assert {"TPU", "PALLAS", "PROFILER", "AMP"} <= names


def test_engine_sync_mode_and_waitall():
    prev = mx.engine.set_sync(True)
    try:
        x = nd.ones((4, 4))
        y = (x * 2).sum()
        assert float(y.asnumpy()) == 32.0
    finally:
        mx.engine.set_sync(prev)
    mx.engine.wait_all()
    with mx.engine.bulk(16):
        assert nd.ones((2,)).shape == (2,)


def test_util_environment():
    assert os.environ.get("MXTPU_TEST_KNOB") is None
    with mx.util.environment("MXTPU_TEST_KNOB", "7"):
        assert os.environ["MXTPU_TEST_KNOB"] == "7"
        with mx.util.environment({"MXTPU_TEST_KNOB": None}):
            assert os.environ.get("MXTPU_TEST_KNOB") is None
    assert os.environ.get("MXTPU_TEST_KNOB") is None


def test_util_np_semantics():
    assert not mx.util.is_np_array()
    with mx.util.np_array(True):
        assert mx.util.is_np_array()
    mx.npx.set_np()
    assert mx.util.is_np_array() and mx.util.is_np_shape()
    mx.npx.reset_np()
    assert not mx.util.is_np_array()


def test_profiler_events_and_dump(tmp_path):
    prof = mx.profiler
    prof.set_config(filename=str(tmp_path / "trace.json"),
                    aggregate_stats=True)
    prof.start()
    with prof.scope("fwd"):
        nd.ones((8, 8)).sum().asnumpy()
    ev = prof.ProfileEvent("manual")
    ev.start()
    ev.stop()
    c = prof.Counter("batches")
    c.increment(3)
    prof.Marker("epoch_end").mark()
    prof.stop()
    path = prof.dump()
    with open(path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"fwd", "manual", "batches", "epoch_end"} <= names
    table = prof.dumps(reset=True)
    assert "fwd" in table and "Calls" in table


def test_profiler_scope_records_no_span_under_a_trace():
    """A region traced under ``jax.jit`` builds a program, not the region's
    work: it records no host span (the name reaches the device through the
    program's metadata); the same region run eagerly records one."""
    import jax
    import jax.numpy as jnp
    prof = mx.profiler
    prof.dumps(reset=True)
    prof.start()
    try:
        def body(x):
            with prof.scope("mx.traced_region"):
                return x * 2.0

        jax.jit(body)(jnp.ones((4,)))
        jax.make_jaxpr(body)(jnp.ones((4,)))
        with prof.scope("eager_region"):
            nd.ones((4, 4)).sum().asnumpy()
    finally:
        prof.stop()
    table = prof.dumps(reset=True)
    assert "eager_region" in table
    assert "mx.traced_region" not in table
    compiled = jax.jit(body).lower(jnp.ones((4,))).compile().as_text()
    assert ("mx.traced_region", "") in prof.scope_table(compiled).values()


def test_profiler_mfu():
    val = mx.profiler.mfu(1e12, 1.0, n_chips=1, peak_flops_per_chip=2e12)
    assert val == pytest.approx(0.5)


def test_amp_autocast_and_loss_scaler():
    from incubator_mxnet_tpu import amp

    amp.init("bfloat16")
    try:
        a = nd.ones((4, 8))
        b = nd.ones((8, 4))
        out = nd.dot(a, b)
        # autocast computes in bf16 but returns the widest input dtype
        assert out.dtype == np.float32
        assert np.allclose(out.asnumpy(), 8.0)
        # fp32-pinned op keeps behaviour on low-precision input
        sm = nd.softmax(nd.ones((2, 3), dtype="bfloat16"))
        assert str(sm.dtype) == "bfloat16"

        from incubator_mxnet_tpu import gluon
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize()
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.1})
        amp.init_trainer(trainer)
        with autograd.record():
            loss = net(nd.ones((2, 8))).sum()
            with amp.scale_loss(loss, trainer) as scaled:
                scaled.backward()
        assert not amp.unscale(trainer)
        trainer.step(2)
    finally:
        amp._deinit_for_tests()


def test_loss_scaler_policy():
    from incubator_mxnet_tpu.amp import LossScaler

    s = LossScaler(init_scale=1024., scale_window=2)
    s.update_scale(overflow=True)
    assert s.loss_scale == 512.
    s.update_scale(False)
    s.update_scale(False)
    assert s.loss_scale == 1024.


def test_mx_np_forwarding_and_autograd():
    x = mx.np.array([[1., 2.], [3., 4.]])
    y = mx.np.exp(x)
    assert isinstance(y, nd.NDArray)
    assert np.allclose(y.asnumpy(), np.exp(x.asnumpy()))
    # tape integration: grad of sum(x**2) = 2x
    x.attach_grad()
    with autograd.record():
        z = mx.np.sum(mx.np.square(x))
    z.backward()
    assert np.allclose(x.grad.asnumpy(), 2 * x.asnumpy())
    # non-array leading args fall back cleanly
    r = mx.np.arange(5)
    assert np.allclose(r.asnumpy(), np.arange(5))
    assert mx.np.pi == np.pi


def test_mx_npx_forwarding():
    x = nd.array(np.array([[-1., 2.]]))
    out = mx.npx.relu(x)
    assert np.allclose(out.asnumpy(), [[0., 2.]])
    sm = mx.npx.softmax(x)
    assert sm.shape == (1, 2)
    mx.npx.waitall()


def test_mx_np_random_surface():
    """mx.np.random — numpy.random-style API over the seeded stream."""
    mx.random.seed(0)
    r = mx.np.random
    assert r.rand(3, 4).shape == (3, 4)
    u = r.uniform(2.0, 4.0, size=(4000,)).asnumpy()
    assert 2.9 < u.mean() < 3.1 and u.min() >= 2.0
    b = r.beta(2.0, 5.0, size=(4000,)).asnumpy()
    assert 0.24 < b.mean() < 0.33 and 0.0 <= b.min() <= b.max() <= 1.0
    p = r.permutation(6).asnumpy()
    assert sorted(p.tolist()) == [0, 1, 2, 3, 4, 5]
    arr = mx.np.array([10.0, 20.0, 30.0, 40.0])
    cs = r.choice(arr, size=(3,), replace=False).asnumpy()
    assert len(set(cs.tolist())) == 3
    # weighted sampling without replacement: distinct draws (Gumbel top-k)
    cw = r.choice(5, size=(4,), replace=False,
                  p=[0.92, 0.02, 0.02, 0.02, 0.02]).asnumpy()
    assert len(set(cw.tolist())) == 4
    # numpy contracts: shuffle is in place and returns None; p must
    # match a; replace=False caps at the population size
    x = mx.np.array([0.0, 1.0, 2.0, 3.0, 4.0, 5.0])
    assert r.shuffle(x) is None
    assert sorted(x.asnumpy().tolist()) == [0, 1, 2, 3, 4, 5]
    import pytest as _pytest
    from incubator_mxnet_tpu.base import MXNetError as _E
    with _pytest.raises(_E):
        r.choice(10, size=(3,), p=[0.5, 0.5])
    with _pytest.raises(_E):
        r.choice(3, size=(5,), replace=False)
    r.seed(7)
    a1 = r.rand(4).asnumpy()
    r.seed(7)
    a2 = r.rand(4).asnumpy()
    assert (a1 == a2).all()


@pytest.mark.slow   # 8s (round-11 tier-1 budget repair); optimizer
                    # tier-1 coverage stays via test_fused_step;
                    # ci stage_unit runs it
def test_round5_optimizer_and_initializer_fills():
    """Adamax/Nadam/DCASGD/SGLD converge (SGLD stays finite — it's a
    sampler); Mixed/InitDesc/Load initializers behave per reference."""
    import numpy as np
    from incubator_mxnet_tpu import autograd, gluon, nd
    from incubator_mxnet_tpu.base import MXNetError

    mx.random.seed(0)
    for name in ("adamax", "nadam", "dcasgd", "sgld"):
        net = gluon.nn.Dense(4)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), name,
                           {"learning_rate": 0.05})
        X = nd.array(np.random.RandomState(0).randn(16, 6)
                     .astype(np.float32))
        Y = nd.array(np.random.RandomState(1).randn(16, 4)
                     .astype(np.float32))
        l2 = gluon.loss.L2Loss()
        first = last = None
        for _ in range(30):
            with autograd.record():
                l = l2(net(X), Y).mean()
            l.backward()
            tr.step(1)
            last = float(l.asnumpy())
            if first is None:
                first = last
        assert np.isfinite(last), name
        if name != "sgld":
            assert last < first, (name, first, last)

    ini = mx.initializer
    m = ini.Mixed([".*bias", ".*"], [ini.Zero(), ini.Constant(2.0)])
    a, b = nd.zeros((3,)), nd.zeros((2, 2))
    m("fc_bias", a)
    m("fc_weight", b)
    assert (a.asnumpy() == 0).all() and (b.asnumpy() == 2.0).all()
    saved = {"arg:w": nd.array(np.arange(4, dtype=np.float32)
                               .reshape(2, 2))}
    ld = ini.Load(saved, default_init=ini.Zero())
    w = nd.zeros((2, 2))
    ld("w", w)
    assert (w.asnumpy() == np.arange(4).reshape(2, 2)).all()
    import pytest as _pytest
    with _pytest.raises(MXNetError):
        ld("w", nd.zeros((3, 3)))


def test_check_consistency_reference_form():
    """check_consistency accepts the reference calling form (symbol +
    ctx-dict list, the fp16-vs-fp32 test_operator idiom) comparing
    forward outputs AND gradients at dtype-scaled tolerance."""
    import numpy as np
    from incubator_mxnet_tpu import test_utils as tu

    sym = mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc",
                                num_hidden=4)
    tu.check_consistency(sym, [
        {"ctx": mx.cpu(), "data": (2, 3),
         "type_dict": {"data": np.float32}},
        {"ctx": mx.cpu(), "data": (2, 3),
         "type_dict": {"data": np.float16}},
    ])
    with pytest.raises(mx.MXNetError, match="must agree on shapes"):
        tu.check_consistency(sym, [
            {"ctx": mx.cpu(), "data": (2, 3)},
            {"ctx": mx.cpu(), "data": (2, 4)},
        ])


def test_check_consistency_multi_output_and_int_inputs():
    """Reference-form check_consistency handles multi-output symbols
    (synthesized unit head grads) and integer-typed inputs (valid
    indices synthesized; float0 tangents excluded from comparison)."""
    import numpy as np
    from incubator_mxnet_tpu import test_utils as tu

    ms = mx.sym.split(mx.sym.Variable("data"), num_outputs=2, axis=1)
    tu.check_consistency(ms, [
        {"ctx": mx.cpu(), "data": (2, 4)},
        {"ctx": mx.cpu(), "data": (2, 4),
         "type_dict": {"data": np.float16}},
    ])
    es = mx.sym.Embedding(mx.sym.Variable("data"), name="emb",
                          input_dim=4, output_dim=3)
    tu.check_consistency(es, [
        {"ctx": mx.cpu(), "data": (5,), "type_dict": {"data": np.int32}},
        {"ctx": mx.cpu(), "data": (5,), "type_dict": {"data": np.int32}},
    ])

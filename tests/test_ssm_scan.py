"""The Mamba-2 state update (ops/ssm_scan.py): the chunked scan and the
one-token kernel against the recurrence written as a recurrence."""

import numpy as np
import pytest

import jax.numpy as jnp

from incubator_mxnet_tpu.ops import ssm_scan as ss
from incubator_mxnet_tpu import profiler


def _case(rng, B, T, H, P, N, slow):
    x = rng.standard_normal((B, T, H, P)).astype(np.float32)
    # slow: dt about 0.01, a state hundreds of positions deep; else 0.3-1
    dt = rng.uniform(0.005, 0.02, (B, T, H)) if slow \
        else rng.uniform(0.3, 1.0, (B, T, H))
    A = -rng.uniform(0.5, 2.0, (H,)).astype(np.float32)
    Bm = rng.standard_normal((B, T, N)).astype(np.float32)
    Cm = rng.standard_normal((B, T, N)).astype(np.float32)
    state = rng.standard_normal((B, H, P, N)).astype(np.float32)
    return tuple(map(jnp.asarray, (state, x, dt.astype(np.float32), A, Bm,
                                   Cm)))


@pytest.mark.parametrize("shape", [(4, 32, 16), (64, 64, 128), (3, 48, 8),
                                   (2, 128, 16)])
def test_pack_unpack_round_trip(shape):
    H, P, N = shape
    rng = np.random.default_rng(0)
    s = jnp.asarray(rng.standard_normal((2, H, P, N)).astype(np.float32))
    packed = ss.pack_state(s)
    assert packed.shape == (2,) + ss.state_row_shape(H, P, N)
    # full lanes wherever the heads divide: the kernel's premise
    if H % max(1, 128 // P) == 0:
        assert packed.shape[-1] == max(P, 128)
    np.testing.assert_array_equal(ss.unpack_state(packed, P), s)


@pytest.mark.parametrize("slow", [True, False], ids=["slow-decay", "fast"])
@pytest.mark.parametrize("block", [256, 16, 8])
def test_chunked_scan_matches_the_recurrence(block, slow):
    """A non-zero incoming state, 37 positions, the second row real for 20
    only. float32 on the CPU: 1e-4 covers sums of up to 37 terms in another
    order (read: 4e-6)."""
    rng = np.random.default_rng(1)
    st, x, dt, A, Bm, Cm = _case(rng, 2, 37, 4, 32, 16, slow)
    real = jnp.arange(37)[None, :] < jnp.asarray([37, 20])[:, None]
    y0, n0 = ss.ssm_scan_reference(st, x, dt, A, Bm, Cm, real)
    y1, n1 = ss.ssm_chunk_scan(st, x, dt, A, Bm, Cm, real, block=block)
    m = np.asarray(real)[:, :, None, None]
    np.testing.assert_allclose(np.asarray(y1) * m, np.asarray(y0) * m,
                               atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(n1, n0, atol=1e-4, rtol=1e-5)
    # the state must matter here: without the incoming one, y is far off
    y2, _ = ss.ssm_chunk_scan(jnp.zeros_like(st), x, dt, A, Bm, Cm, real,
                              block=block)
    assert np.abs((np.asarray(y2) - np.asarray(y0)) * m).max() > 0.1


def test_chunk_scan_padding_advances_no_state():
    rng = np.random.default_rng(2)
    st, x, dt, A, Bm, Cm = _case(rng, 2, 24, 4, 32, 16, True)
    real = jnp.arange(24)[None, :] < jnp.asarray([0, 9])[:, None]
    _, new = ss.ssm_chunk_scan(st, x, dt, A, Bm, Cm, real, block=8)
    np.testing.assert_array_equal(new[0], st[0])    # no real position
    _, upto9 = ss.ssm_chunk_scan(st[1:], x[1:, :9], dt[1:, :9], A,
                                 Bm[1:, :9], Cm[1:, :9])
    np.testing.assert_allclose(new[1], upto9[0], atol=1e-5)


LIVE = [[1, 0, 1, 1, 0], [0, 0, 0, 0, 0], [1, 1, 1, 1, 1], [0, 0, 0, 0, 1]]


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("dims", [(4, 32, 16), (16, 64, 128)])
def test_ssm_decode_kernel_matches_its_twin(dims, live):
    """The interpreted kernel against the jnp twin and one turn of the
    recurrence; a dead slot's rows come back bit for bit."""
    H, P, N = dims
    rng = np.random.default_rng(3)
    st, x, dt, A, Bm, Cm = _case(rng, 5, 1, H, P, N, False)
    live = jnp.asarray(live, bool)
    packed = ss.pack_state(st)
    y0, n0 = ss.ssm_decode_reference(packed, x[:, 0], dt[:, 0], A,
                                     Bm[:, 0], Cm[:, 0], live)
    y1, n1 = ss.ssm_decode(packed, x[:, 0], dt[:, 0], A, Bm[:, 0],
                           Cm[:, 0], live, interpret=True)
    np.testing.assert_allclose(y1, y0, atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(n1, n0, atol=1e-5, rtol=1e-6)
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(n1)[dead],
                                  np.asarray(packed)[dead])
    np.testing.assert_array_equal(np.asarray(y1)[dead], 0.0)
    y2, n2 = ss.ssm_scan_reference(st, x, dt, A, Bm, Cm, live[:, None])
    np.testing.assert_allclose(
        y0, np.asarray(y2[:, 0]) * np.asarray(live)[:, None, None],
        atol=1e-4, rtol=1e-5)
    np.testing.assert_allclose(n0, ss.pack_state(n2), atol=1e-5)


def test_ssm_decode_with_the_state_kept_in_bfloat16():
    """The precision below the one the benchmark's configuration states
    (its bf16-state control): the kernel and its twin read the rows as
    float32, update in float32 and round once on the way back, so they
    agree bit for bit, ``y`` is that of the unrounded update, and a dead
    slot's rows are untouched."""
    rng = np.random.default_rng(4)
    st, x, dt, A, Bm, Cm = _case(rng, 5, 1, 4, 32, 16, True)
    live = jnp.asarray(LIVE[0], bool)
    packed = ss.pack_state(st).astype(jnp.bfloat16)
    args = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], live)
    y0, n0 = ss.ssm_decode_reference(packed, *args)
    y1, n1 = ss.ssm_decode(packed, *args, interpret=True)
    assert n0.dtype == n1.dtype == jnp.bfloat16 and y1.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(n1, np.float32),
                                  np.asarray(n0, np.float32))
    np.testing.assert_allclose(y1, y0, atol=1e-4, rtol=1e-5)
    yf, nf = ss.ssm_decode_reference(packed.astype(jnp.float32), *args)
    np.testing.assert_allclose(y0, yf, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(n0, np.float32),
                                  np.asarray(nf.astype(jnp.bfloat16),
                                             np.float32))
    dead = ~np.asarray(live)
    np.testing.assert_array_equal(np.asarray(n1, np.float32)[dead],
                                  np.asarray(packed, np.float32)[dead])


def test_causal_conv_keeps_the_last_real_inputs():
    rng = np.random.default_rng(4)
    tail = jnp.asarray(rng.standard_normal((3, 3, 6)).astype(np.float32))
    x = jnp.asarray(rng.standard_normal((3, 5, 6)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((6, 4)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((6,)).astype(np.float32))
    real = jnp.arange(5)[None, :] < jnp.asarray([5, 2, 0])[:, None]
    out, new = ss.causal_conv(tail, x, w, b, real)
    full = np.concatenate([tail, x], axis=1)
    want = np.asarray(b) + sum(full[:, k:k + 5] * np.asarray(w)[:, k]
                               for k in range(4))
    np.testing.assert_allclose(out, want, atol=1e-5)
    np.testing.assert_array_equal(new[0], x[0, 2:5])
    np.testing.assert_array_equal(new[1], full[1, 2:5])   # 1 old, 2 new
    np.testing.assert_array_equal(new[2], tail[2])        # untouched
    # two chunks give what one does
    o1, t1 = ss.causal_conv(tail[:1], x[:1, :3], w, b)
    o2, t2 = ss.causal_conv(t1, x[:1, 3:], w, b)
    np.testing.assert_allclose(np.concatenate([o1, o2], 1), out[:1],
                               atol=1e-5)
    np.testing.assert_array_equal(t2, new[:1])


def test_dispatch_tally_names_what_each_site_got():
    profiler.ssm_dispatch(reset=True)
    rng = np.random.default_rng(5)
    st, x, dt, A, Bm, Cm = _case(rng, 2, 1, 4, 32, 16, False)
    live = jnp.ones((2,), bool)
    packed = ss.pack_state(st)
    ss.ssm_decode(packed, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], live,
                  interpret=False)
    ss.ssm_decode(packed, x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0], live,
                  interpret=True)
    ss.ssm_chunk_scan(st, x, dt, A, Bm, Cm)
    assert profiler.ssm_dispatch(reset=True) == {
        "ssm_decode_jnp": 1, "ssm_decode_pallas": 1, "ssm_chunk_jnp": 1}
    assert profiler.ssm_dispatch() == {}

"""Pallas flash-attention kernel tests.

Reference test idiom §4.2 (cross-backend consistency): the kernel runs in
INTERPRET mode on CPU and must match the dense softmax oracle; gradients
flow through the custom-vjp rematerializing backward.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from incubator_mxnet_tpu.ops.attention import _sdpa_dense
from incubator_mxnet_tpu.ops import pallas_attention as pa
from incubator_mxnet_tpu.ops.pallas_attention import (
    _flash_forward, flash_attention_bhtd, use_flash_attention)


@pytest.fixture(params=["streaming", "dense"])
def kernel_path(request, monkeypatch):
    """Run kernel parity tests against BOTH Pallas paths: the streaming
    FlashAttention-2 kernels (dense dispatch disabled via threshold 0)
    and the dense single-tile kernels (threshold above every test
    shape). The threshold is re-read per call in the non-jitted wrappers
    and passed as a static jit arg, so flipping the env between tests
    retraces instead of reusing the cached path."""
    monkeypatch.setenv("MXTPU_FLASH_DENSE_T",
                       "0" if request.param == "streaming" else "4096")
    return request.param


def _dense_ref(q, k, v, valid, causal):
    """(B,H,T,D) dense oracle."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    mask = np.arange(Tk)[None, :] < valid[:, None]          # (B, Tk)
    m = jnp.asarray(mask)[:, None, None, :]
    if causal:
        cm = np.tril(np.ones((Tq, Tk), bool))
        m = jnp.logical_and(m, jnp.asarray(cm)[None, None])
    out = _sdpa_dense(jnp.asarray(q.transpose(0, 2, 1, 3)),
                      jnp.asarray(k.transpose(0, 2, 1, 3)),
                      jnp.asarray(v.transpose(0, 2, 1, 3)),
                      m, D ** -0.5)
    return np.asarray(out).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("Tq,Tk,vl", [(16, 16, (16, 9)),
                                      (32, 16, (16, 16)),
                                      (8, 24, (24, 5))])
def test_kernel_interpret_matches_dense(causal, Tq, Tk, vl, kernel_path):
    if causal and Tq != Tk:
        pytest.skip("causal assumes square")
    rng = np.random.RandomState(0)
    B, H, D = 2, 3, 8
    q = rng.randn(B, H, Tq, D).astype(np.float32)
    k = rng.randn(B, H, Tk, D).astype(np.float32)
    v = rng.randn(B, H, Tk, D).astype(np.float32)
    valid = np.asarray(vl, np.int32)
    got = np.asarray(_flash_forward(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(valid), causal=causal, block_q=8, block_k=8,
        interpret=True))
    ref = _dense_ref(q, k, v, valid, causal)
    # rows past valid length have all-masked scores in BOTH impls only
    # when causal+query masking applies; compare valid region per batch
    for b in range(B):
        np.testing.assert_allclose(got[b], ref[b], rtol=2e-4, atol=2e-4)


def test_kernel_blocking_invariance(monkeypatch):
    """Different block sizes must give identical results (streaming path
    only — the dense kernel has no blocks, so it is pinned off here to
    keep the comparison meaningful)."""
    monkeypatch.setenv("MXTPU_FLASH_DENSE_T", "0")
    rng = np.random.RandomState(1)
    q = jnp.asarray(rng.randn(1, 2, 32, 8).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, 32, 8).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, 32, 8).astype(np.float32))
    vl = jnp.asarray([32], jnp.int32)
    a = _flash_forward(q, k, v, vl, block_q=8, block_k=8, interpret=True)
    b = _flash_forward(q, k, v, vl, block_q=32, block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


def test_gradients_match_dense(kernel_path):
    rng = np.random.RandomState(2)
    B, H, T, D = 1, 2, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    vl = jnp.asarray([T], jnp.int32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention_bhtd(q, k, v, vl, False, None,
                                            True) ** 2)

    def loss_dense(q, k, v):
        out = _dense_ref(np.asarray(q), np.asarray(k), np.asarray(v),
                         np.asarray(vl), False)
        return (out ** 2).sum()

    gq, gk, gv = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)

    # numeric check on a few coordinates of dq
    eps = 1e-3
    base = float(loss_dense(q, k, v))
    for idx in [(0, 0, 0, 0), (0, 1, 7, 3), (0, 0, 15, 7)]:
        qp = np.asarray(q).copy()
        qp[idx] += eps
        num = (float(loss_dense(jnp.asarray(qp), k, v)) - base) / eps
        assert abs(num - float(gq[idx])) < 0.05 * max(1.0, abs(num)), idx


def test_dispatch_fallback_on_cpu():
    """On the CPU test backend the dispatcher must take the jnp path and
    agree with the dense oracle (B,T,H,D layout)."""
    rng = np.random.RandomState(3)
    B, T, H, D = 2, 12, 2, 4
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    out = use_flash_attention(q, k, v, causal=True)
    ref = _dense_ref(np.asarray(q).transpose(0, 2, 1, 3),
                     np.asarray(k).transpose(0, 2, 1, 3),
                     np.asarray(v).transpose(0, 2, 1, 3),
                     np.full((B,), T, np.int32), True)
    np.testing.assert_allclose(np.asarray(out),
                               ref.transpose(0, 2, 1, 3), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_backward_matches_dense_grads(causal, kernel_path):
    """The Pallas dq/dk/dv kernels (interpret mode) must match analytic
    gradients through the dense softmax oracle, including key-padding
    and causal masks."""
    from incubator_mxnet_tpu.ops.attention import _sdpa_dense
    rng = np.random.RandomState(4)
    B, H, T, D = 2, 2, 24, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    vl = jnp.asarray([T, 13], jnp.int32)
    g = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))

    def flash_loss(q, k, v):
        out = flash_attention_bhtd(q, k, v, vl, causal, None, True)
        return jnp.sum(out * g)

    def dense_loss(q, k, v):
        mask = jnp.arange(T)[None, :] < vl[:, None]
        m = mask[:, None, None, :]
        if causal:
            m = jnp.logical_and(
                m, jnp.tril(jnp.ones((T, T), bool))[None, None])
        out = _sdpa_dense(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), m, D ** -0.5)
        return jnp.sum(out.transpose(0, 2, 1, 3) * g)

    gq, gk, gv = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    rq, rk, rv = jax.grad(dense_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(gq), np.asarray(rq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(rk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(gv), np.asarray(rv),
                               rtol=2e-4, atol=2e-4)


def test_pallas_backward_block_invariance(monkeypatch):
    monkeypatch.setenv("MXTPU_FLASH_DENSE_T", "0")
    from incubator_mxnet_tpu.ops.pallas_attention import (
        _flash_backward, _flash_fwd_lse)
    rng = np.random.RandomState(5)
    B, H, T, D = 1, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    vl = jnp.asarray([T], jnp.int32)
    g = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    out, lse = _flash_fwd_lse(q, k, v, vl, interpret=True)
    a = _flash_backward(q, k, v, vl, out, lse, g, block_q=8, block_k=8,
                        interpret=True)
    b = _flash_backward(q, k, v, vl, out, lse, g, block_q=32, block_k=16,
                        interpret=True)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-5)


def test_block_attn_lse_interpret_matches_dense(kernel_path):
    """(out, lse) primitive through the Pallas kernels in interpret mode
    (the ring-attention building block)."""
    from incubator_mxnet_tpu.ops.pallas_attention import (
        block_attn_lse, _dense_attn_lse)
    rng = np.random.RandomState(11)
    B, H, T, D = 2, 2, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, H, T, D).astype(np.float32))
    vl = jnp.asarray([T, 9], jnp.int32)
    for causal in (False, True):
        o_p, lse_p = block_attn_lse(q, k, v, vl, causal, None, True)
        o_d, lse_d = _dense_attn_lse(q, k, v, vl, causal, None)
        np.testing.assert_allclose(np.asarray(o_p), np.asarray(o_d),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_d),
                                   rtol=2e-4, atol=2e-4)
    # gradient through the custom vjp (Pallas backward kernels)
    g = jax.grad(lambda q: block_attn_lse(
        q, k, v, vl, True, None, True)[0].sum())(q)
    g_ref = jax.grad(lambda q: _dense_attn_lse(
        q, k, v, vl, True, None)[0].sum())(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=3e-4, atol=3e-4)


def test_kernel_bf16_operands_match_f32_reference(kernel_path):
    """bf16 inputs keep bf16 DOT OPERANDS (full-rate MXU) with f32
    accumulation — outputs must track the f32 dense reference within
    bf16 tolerance, fwd and bwd."""
    rng = np.random.RandomState(7)
    B, H, T, D = 2, 2, 32, 8
    q = rng.randn(B, H, T, D).astype(np.float32)
    k = rng.randn(B, H, T, D).astype(np.float32)
    v = rng.randn(B, H, T, D).astype(np.float32)
    valid = np.array([T, T - 5], np.int32)

    got = np.asarray(_flash_forward(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), jnp.asarray(valid),
        causal=False, block_q=8, block_k=8,
        interpret=True)).astype(np.float32)
    ref = _dense_ref(q, k, v, valid, False)
    np.testing.assert_allclose(got, ref, rtol=0.05, atol=0.05)

    # backward: bf16 flash grads track the f32 dense grads
    from incubator_mxnet_tpu.ops.pallas_attention import flash_attention_bhtd

    def loss_flash(q_, k_, v_):
        o = flash_attention_bhtd(q_, k_, v_, jnp.asarray(valid),
                                 False, None, interpret=True)
        return (o.astype(jnp.float32) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16))
    key_mask = jnp.asarray(np.arange(T)[None, None, None, :] <
                           valid[:, None, None, None])

    def dense_f32(q_, k_, v_):
        s = jnp.einsum("bhqd,bhkd->bhqk", q_, k_) * D ** -0.5
        s = jnp.where(key_mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bhkd->bhqd", p, v_)
        return (o ** 2).sum()

    g_f32 = jax.grad(dense_f32, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    # ALL THREE grads (dq via the dq kernel, dk/dv via the dkv kernel —
    # both kernels' dtype handling changed) against the f32 reference
    for gf, gr in zip(g_flash, g_f32):
        np.testing.assert_allclose(np.asarray(gf, np.float32),
                                   np.asarray(gr), rtol=0.1, atol=0.1)


def test_sdpa_valid_length_equals_boolean_mask():
    """sdpa(flash=True, valid_length=vl) must equal the (B,Tk) boolean
    mask form — valid_length is the form that engages the TPU Pallas
    kernel (a boolean mask alone falls back to the jnp path), so the
    two spellings must be interchangeable."""
    from incubator_mxnet_tpu import nd

    rng = np.random.RandomState(0)
    B, T, H, D = 2, 24, 2, 8
    q = nd.array(rng.randn(B, T, H, D).astype(np.float32))
    k = nd.array(rng.randn(B, T, H, D).astype(np.float32))
    v = nd.array(rng.randn(B, T, H, D).astype(np.float32))
    vl = np.array([T, 13], np.int32)
    mask = nd.array((np.arange(T)[None, :] < vl[:, None])
                    .astype(np.float32))
    out_mask = nd.scaled_dot_product_attention(q, k, v, mask=mask,
                                               flash=True)
    out_vl = nd.scaled_dot_product_attention(
        q, k, v, flash=True, valid_length=nd.array(vl, dtype="int32"))
    # rows beyond a batch's valid length attend nothing in the vl form;
    # compare the valid region
    a, b = out_mask.asnumpy(), out_vl.asnumpy()
    np.testing.assert_allclose(a[0], b[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(a[1, :13], b[1, :13], rtol=1e-5, atol=1e-5)


def test_sdpa_dense_path_honors_valid_length():
    """The non-flash dense path must mask padding keys when only
    valid_length (no boolean mask) is given."""
    from incubator_mxnet_tpu import nd

    rng = np.random.RandomState(1)
    B, T, H, D = 2, 10, 1, 4
    q = rng.randn(B, T, H, D).astype(np.float32)
    k = rng.randn(B, T, H, D).astype(np.float32)
    v = rng.randn(B, T, H, D).astype(np.float32)
    vl = np.array([T, 6], np.int32)
    mask = nd.array((np.arange(T)[None, :] < vl[:, None])
                    .astype(np.float32))
    out_vl = nd.scaled_dot_product_attention(
        nd.array(q), nd.array(k), nd.array(v),
        valid_length=nd.array(vl, dtype="int32"))           # flash=False
    out_mask = nd.scaled_dot_product_attention(
        nd.array(q), nd.array(k), nd.array(v), mask=mask)
    np.testing.assert_allclose(out_vl.asnumpy(), out_mask.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_fully_masked_rows_zero_output_and_safe_grads(kernel_path):
    """ADVICE r4: a fully-masked query row (vl==0, or q rows past the
    valid prefix) must produce ZERO output — not the uniform mean of V —
    with lse pinned to a finite -inf surrogate, and zero (not NaN)
    gradients. Checked on both kernel families and the jnp fallback."""
    rng = np.random.RandomState(0)
    B, H, T, D = 2, 2, 16, 8
    q = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, H, T, D), jnp.float32)
    vl = jnp.asarray([0, 5], jnp.int32)        # row 0 fully masked

    def loss(q, k, v):
        return flash_attention_bhtd(q, k, v, vl, False, None, True).sum()

    out = flash_attention_bhtd(q, k, v, vl, False, None, True)
    out_np = np.asarray(out)
    # batch 0: every row fully masked -> all zeros
    np.testing.assert_array_equal(out_np[0], 0.0)
    # batch 1: rows attend the 5-key prefix regardless of q position
    # (prefix mask, non-causal) -> finite and nonzero
    assert np.isfinite(out_np[1]).all() and np.abs(out_np[1]).sum() > 0

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert np.isfinite(np.asarray(g)).all()
    np.testing.assert_array_equal(np.asarray(dq)[0], 0.0)
    # masked-out keys (beyond the prefix) contribute nothing
    np.testing.assert_array_equal(np.asarray(dk)[1, :, 5:], 0.0)

    # jnp fallback path agrees (dispatcher with a boolean mask routes
    # to _sdpa_blockwise)
    from incubator_mxnet_tpu.ops.attention import _sdpa_blockwise
    km = np.arange(T)[None, :] < np.asarray([0, 5])[:, None]
    fb = _sdpa_blockwise(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), jnp.asarray(km), False,
                         D ** -0.5)
    np.testing.assert_array_equal(np.asarray(fb)[0], 0.0)
    np.testing.assert_allclose(np.asarray(fb).transpose(0, 2, 1, 3),
                               out_np, rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# PR 29: the dense pair in the projection's own layout, (B, T, 3*H*D)
# --------------------------------------------------------------------- #

_PACKED_CASES = {
    # BERT-large's heads, a full row and a short one
    "H16-D64-full": dict(B=2, H=16, D=64, T=256, causal=False,
                         vl=(256, 101)),
    # GPT-2-small's heads, causal
    "H12-D64-causal": dict(B=2, H=12, D=64, T=256, causal=True,
                           vl=(256, 256)),
    # one head a lane group
    "H2-D128-causal": dict(B=2, H=2, D=128, T=128, causal=True,
                           vl=(128, 77)),
}


def _packed_reference(qkv, vl, H, causal):
    """jnp oracle over the packed (B, T, 3*H*D) projection."""
    B, T, W = qkv.shape
    D = W // (3 * H)
    q, k, v = (x.reshape(B, T, H, D) for x in jnp.split(qkv, 3, axis=-1))
    m = (jnp.arange(T)[None, :] < vl[:, None])[:, None, None, :]
    if causal:
        m = m & jnp.tril(jnp.ones((T, T), bool))[None, None]
    return _sdpa_dense(q, k, v, m, D ** -0.5).reshape(B, T, H * D)


@pytest.fixture(scope="module")
def packed_case_results():
    """Forward and gradient of every packed case, computed once: the
    kernels (interpreted), the operator over them, and the jnp oracle."""
    cache = {}

    def get(name):
        if name in cache:
            return cache[name]
        c = _PACKED_CASES[name]
        B, H, D, T, causal = c["B"], c["H"], c["D"], c["T"], c["causal"]
        assert pa.packed_dense_shapes(T, H, D)
        rng = np.random.RandomState(5)
        qkv = jnp.asarray(rng.randn(B, T, 3 * H * D), jnp.float32)
        g = jnp.asarray(rng.randn(B, T, H * D), jnp.float32)
        vl = jnp.asarray(c["vl"], jnp.int32)

        def run(fn):
            out, vjp = jax.vjp(fn, qkv)
            return out, vjp(g)[0]

        kernel = run(lambda x: pa.flash_dense_packed(x, vl, H, causal,
                                                     None, True))
        oracle = run(lambda x: _packed_reference(x, vl, H, causal))
        cache[name] = (kernel, oracle, (qkv, vl, g, H, causal))
        return cache[name]
    return get


@pytest.mark.parametrize("what", ["out", "dq", "dk", "dv", "entry"])
@pytest.mark.parametrize("case", sorted(_PACKED_CASES))
def test_packed_dense_pair_matches_reference(case, what, packed_case_results,
                                             monkeypatch):
    """The packed dense pair against the jnp oracle at the cells' head
    sizes: the output, the three parts of its ONE (B, T, 3*H*D)
    gradient, and the operator's entry (``flash_attention_packed``, as
    the transformer cells call it) equal to the kernels called directly,
    output and single gradient, bit for bit."""
    kernel, oracle, (qkv, vl, g, H, causal) = packed_case_results(case)
    assert kernel[1].shape == qkv.shape
    if what == "entry":
        from incubator_mxnet_tpu.ops.attention import flash_attention_packed
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
        out, vjp = jax.vjp(lambda x: flash_attention_packed(
            x, valid_length=vl, heads=H, causal=causal), qkv)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(kernel[0]))
        np.testing.assert_array_equal(np.asarray(vjp(g)[0]),
                                      np.asarray(kernel[1]))
        return
    if what == "out":
        got, want = kernel[0], oracle[0]
    else:
        i = ("dq", "dk", "dv").index(what)
        got, want = (jnp.split(x[1], 3, axis=-1)[i]
                     for x in (kernel, oracle))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_packed_dense_pair_zeroes_fully_masked_rows():
    """valid_len 0: zero output (not the mean of V) and zero, finite
    gradients, as every other kernel family gives."""
    B, H, D, T = 2, 2, 64, 128
    rng = np.random.RandomState(3)
    qkv = jnp.asarray(rng.randn(B, T, 3 * H * D), jnp.float32)
    vl = jnp.asarray([0, T], jnp.int32)
    out, vjp = jax.vjp(lambda x: pa.flash_dense_packed(
        x, vl, H, False, None, True), qkv)
    grad = np.asarray(vjp(jnp.ones_like(out))[0])
    np.testing.assert_array_equal(np.asarray(out)[0], 0.0)
    np.testing.assert_array_equal(grad[0], 0.0)
    assert np.isfinite(grad).all() and np.abs(grad[1]).max() > 0


def test_packed_dense_pair_in_column_blocks(monkeypatch):
    """Rows too wide for ``_PACKED_BLOCK_BUDGET`` go in column blocks of
    fewer heads a program (grid (B, H/hpp)); the gradient's whole-row
    block then stays resident across the head axis. Same numbers as
    whole rows, bit for bit."""
    B, H, D, T = 2, 4, 64, 128
    rng = np.random.RandomState(9)
    qkv, g = (jnp.asarray(rng.randn(B, T, n * H * D), jnp.float32)
              for n in (3, 1))
    vl = jnp.asarray([T, 60], jnp.int32)

    def run():
        jax.clear_caches()
        out, vjp = jax.vjp(lambda x: pa.flash_dense_packed(
            x, vl, H, True, None, True), qkv)
        return out, vjp(g)[0]

    assert pa._packed_hpp(H, D, T, 4, bwd=True) == H
    whole = run()
    monkeypatch.setattr(pa, "_PACKED_BLOCK_BUDGET", 1 << 19)
    assert pa._packed_hpp(H, D, T, 4, bwd=True) == 2
    for a, b in zip(run(), whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    jax.clear_caches()


def _mesh_of(n):
    from incubator_mxnet_tpu.parallel import mesh as pmesh
    if n is None:
        return None
    if isinstance(n, dict):
        size = int(np.prod(list(n.values())))
        return pmesh.build_mesh(devices=jax.devices()[:size], axis_sizes=n)
    return pmesh.build_mesh(devices=jax.devices()[:n], axis_sizes={"dp": n})


@pytest.mark.parametrize("T,H,D,mesh,kernel,want", [
    (512, 16, 64, None, True, True),       # bertl-train outside a trainer
    (512, 16, 64, 1, True, True),          # bertl-train: a mesh of one
    (512, 12, 64, 1, True, True),          # GPT-2-small on one chip
    (128, 2, 128, None, True, True),       # one head a lane group
    (256, 4, 256, None, True, True),
    (512, 12, 64, 4, True, False),         # gpt2s-train-dp4: four devices
    (512, 16, 64, {"fsdp": 2}, True, False),
    (512, 16, 64, {"dp": 2, "tp": 2}, True, False),
    (512, 16, 64, {"sp": 2}, True, False),
    (512, 16, 64, 1, False, False),        # no Pallas kernel runs here
    (1024, 12, 64, None, True, False),     # over the dense limit
    (64, 4, 64, None, True, False),        # T not a multiple of 128
    (512, 3, 64, None, True, False),       # odd number of D=64 heads
    (128, 8, 16, None, True, False),       # the tiny test models' D
    (128, 4, 32, None, True, False),
    (128, 2, 192, None, True, False),      # D neither 64 nor n*128
])
def test_packed_dense_selection_rule(T, H, D, mesh, kernel, want,
                                     monkeypatch):
    """``packed_dense_eligible`` from shapes, the kernel's presence and
    the active trainer mesh: only an absent or one-device mesh gets the
    packed pair."""
    from incubator_mxnet_tpu.parallel.spmd import activation_sharding_scope
    if kernel:
        monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    with activation_sharding_scope(_mesh_of(mesh)):
        assert pa.packed_dense_eligible(T, H, D) is want
    if want:      # whole rows a program at every size a cell runs
        assert pa._packed_hpp(H, D, T, 2) == H
        assert pa._packed_hpp(H, D, T, 2, bwd=True) == H


def test_packed_entry_refuses_call_sites_outside_the_rule(monkeypatch):
    """The operator does not fall back: outside the rule it raises."""
    from incubator_mxnet_tpu.base import MXNetError
    from incubator_mxnet_tpu.parallel.spmd import activation_sharding_scope
    z = jnp.zeros((4, 128, 3 * 2 * 64), jnp.float32)
    with pytest.raises(MXNetError, match="packed_dense_eligible"):
        pa.flash_packed_self_attention(z, 2)          # no kernel on CPU
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    jax.eval_shape(lambda x: pa.flash_packed_self_attention(x, 2), z)
    with activation_sharding_scope(_mesh_of(4)):
        with pytest.raises(MXNetError, match="packed_dense_eligible"):
            pa.flash_packed_self_attention(z, 2)
    pa.dispatch_tally(reset=True)


def test_dispatch_tally_names_each_call_site(monkeypatch):
    """``dispatch_tally`` (``profiler.attention_dispatch``) counts, at
    trace time, which implementation each attention call site got."""
    from incubator_mxnet_tpu import profiler
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    profiler.attention_dispatch(reset=True)
    z = lambda *s: jnp.zeros(s, jnp.float32)
    jax.eval_shape(lambda q: use_flash_attention(q, q, q), z(1, 128, 2, 64))
    jax.eval_shape(lambda q: use_flash_attention(q, q, q, layout="bhtd"),
                   z(1, 2, 128, 64))
    jax.eval_shape(lambda q: use_flash_attention(q, q, q),
                   z(1, 640, 2, 8))                  # over the dense limit
    jax.eval_shape(lambda x: pa.flash_packed_self_attention(x, 2),
                   z(1, 128, 3 * 2 * 64))
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
    jax.eval_shape(lambda q: use_flash_attention(q, q, q), z(1, 128, 2, 64))
    assert profiler.attention_dispatch(reset=True) == {
        "dense_packed": 1, "dense_bhtd": 2, "stream_bhtd": 1,
        "blockwise_jnp": 1}
    assert profiler.attention_dispatch() == {}


# --------------------------------------------------------------------- #
# the packed pair compiled for the chip, without one (Mosaic + XLA:TPU
# against a described v5e: lowering faults and relayouts, no time)
# --------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """An AOT compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.mark.parametrize("B,H,D,T,causal", [
    (32, 16, 64, 512, False),      # bertl-train, a layer
    (16, 12, 64, 512, True),       # GPT-2-small, a layer on one chip
])
def test_packed_dense_pair_compiles_for_v5e_with_no_relayout(
        v5e_chip, no_compile_cache, B, H, D, T, causal):
    """At the cells' sizes the packed forward + backward compile for the
    v5e to the two custom calls over the projection itself: no ``copy``,
    ``transpose`` or fusion touches a bf16 array around them."""
    import re

    def fwd_bwd(qkv, vl, g):
        out, vjp = jax.vjp(lambda x: pa.flash_dense_packed(
            x, vl, H, causal, None, False), qkv)
        return out, vjp(g)[0]

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    text = jax.jit(fwd_bwd).lower(
        arg((B, T, 3 * H * D), jnp.bfloat16), arg((B,), jnp.int32),
        arg((B, T, H * D), jnp.bfloat16)).compile().as_text()
    calls = re.findall(r"%(mxtpu_flash_dense_\w+?)[.\d]* = (.*?) "
                       r"custom-call\((.*?)\), "
                       r"custom_call_target=\"tpu_custom_call\"", text)
    assert sorted(c[0] for c in calls) == ["mxtpu_flash_dense_bwd",
                                           "mxtpu_flash_dense_fwd"]
    fwd, bwd = sorted(calls, key=lambda c: c[0], reverse=True)
    assert f"bf16[{B},{T},{H * D}]" in fwd[1]
    assert f"f32[{B},{H // 2},2,{T}]" in fwd[1]      # lse, T on the lanes
    assert len(set(fwd[2].split(", ")[1:])) == 1     # the projection x3
    assert bwd[1].startswith(f"bf16[{B},{T},{3 * H * D}]")
    moved = re.findall(r"= bf16\[[^ ]* (copy|transpose|fusion)\(", text)
    assert not moved, moved


def test_serving_programs_compile_for_v5e_with_no_pool_relayout(
        v5e_chip, no_compile_cache, monkeypatch):
    """The engine's decode step and a chunk-prefill program of a
    two-layer model at GPT-2-XL's widths (25 heads of 64, 64 slots, 695
    pages of 16, bf16) compile for the v5e to one Mosaic call a layer
    and no ``copy`` or ``transpose`` of a pool-sized array: the pool
    (P, H, 16, 128) is stored in the layout its kernels read, and the
    scatter writes into it in place (a (.., 64)-wide pool cost two
    whole-pool copies a pool a program; PERF.md, PR 33)."""
    import math
    import re
    import numpy as np
    from incubator_mxnet_tpu.models.gpt import GPTModel
    from incubator_mxnet_tpu.serve import InferenceEngine, Request

    L, P = 2, 695
    model = GPTModel(vocab_size=256, units=1600, hidden_size=6400,
                     num_layers=L, num_heads=25, max_length=1024,
                     dropout=0.0, dtype="bfloat16", flash=False)
    model.initialize()
    eng = InferenceEngine(model, num_slots=64, page_size=16, max_len=1024,
                          num_pages=P, chunk_pages=32, token_budget=512)
    assert eng.health_snapshot()["kv_page_shape"] == (25, 16, 128)
    # two requests through a chunk each and a decode step on the CPU
    # record each program's abstract arguments. A 512-row chunk's
    # query, output and accumulator blocks (25 heads x 512 rows x 128
    # lanes) and the walk's two-block buffer need more than Mosaic's
    # default 16 MiB: the kernel's own ``vmem_limit_bytes`` is held here
    eng.run([Request(np.arange(1, 41, dtype=np.int32), max_new_tokens=2),
             Request(np.arange(1, 501, dtype=np.int32) % 255,
                     max_new_tokens=1)])
    bodies = {"decode": (eng._decode_step_fn, "mxtpu_ragged_decode"),
              ("chunk", 64): (eng._chunk_prefill_fn,
                              "mxtpu_ragged_prefill"),
              ("chunk", 512): (eng._chunk_prefill_fn,
                               "mxtpu_ragged_prefill")}
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    pool_elems = P * 25 * 16 * 128
    for name, (body, kernel) in bodies.items():
        args = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=v5e_chip),
            eng._programs[name][1])
        # a jit of its own: the engine's was traced on the CPU, where
        # the dispatchers took the jnp reference
        compiled = jax.jit(lambda *a: body(*a), donate_argnums=(1,)) \
            .lower(*args).compile()
        text = compiled.as_text()
        calls = re.findall(r"%(mxtpu_\w+?)[.\d]* = \S+ custom-call\(", text)
        assert calls == [kernel] * L, (name, calls)
        assert f"bf16[{P},25,16,128]{{3,2,1,0" in text     # row-major
        moved = [(shape, op) for shape, op in re.findall(
            r"= \w+\[([\d,]+)\]\S* (copy|transpose)\(", text)
            if math.prod(map(int, shape.split(","))) >= pool_elems // 2]
        assert not moved, (name, moved)
        # the sampling menu's sort sits in a branch no greedy step takes
        # (PERF.md, PR 37): the entry computation holds the branches,
        # not the sort
        entry = text[text.index("\nENTRY "):]
        assert " conditional(" in entry and " sort(" not in entry, name
        assert " sort(" in text, name
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes >= L * pool_elems * 2   # donated
        assert mem.temp_size_in_bytes < pool_elems * 2 // 4, \
            (name, mem.temp_size_in_bytes)

"""models/granite_hybrid.py against the plain reference (tests/
granite_hybrid_reference.py: float32, ``HIGHEST``, the recurrence written as
a recurrence), at a small size on the CPU."""

import os

import numpy as np
import pytest

import jax.numpy as jnp

import granite_hybrid_reference as ref
from granite_hybrid_util import Ops, build_model, draw_weights, tiny_config
from incubator_mxnet_tpu.ndarray import NDArray

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("dtype,slow,tol", [
    # float32 against float32: sums in another order (the chunked scan, the
    # fused projections). Read 7.5e-9 and 9.5e-9 on logits of spread 0.0024
    ("float32", True, 2e-7),
    ("float32", False, 2e-7),
    # bfloat16 matrices and activations against the float32 reference run
    # on the same (bfloat16-rounded) weights: 8 bits of mantissa through 7
    # layers, on logits of spread 0.0024. Read 1.75e-4 and 1.77e-4
    ("bfloat16", True, 6e-4),
    ("bfloat16", False, 6e-4),
], ids=["f32-slow", "f32-fast", "bf16-slow", "bf16-fast"])
def test_hybrid_forward_matches_the_reference(dtype, slow, tol):
    cfg = tiny_config(dtype)
    w = draw_weights(cfg, 5, slow_decay=slow)
    model = build_model(cfg, w)
    ids = np.random.default_rng(0).integers(0, 256, (2, 70)).astype(np.int32)
    got = np.asarray(model(NDArray(jnp.asarray(ids)))._data)
    assert got.dtype == np.float32 and got.shape == (2, 70, 256)
    for b in range(2):
        want = np.asarray(ref.logits_at(w, jnp.asarray(ids[b]),
                                        jnp.arange(70), cfg, Ops))
        assert np.abs(got[b] - want).max() < tol
        assert want.std() > 0.002           # the tolerance is of something


def test_the_state_reaches_the_logits():
    """With the slow-decay draw an error in state hundreds of positions old
    shows: the first token changed, the logits 60 positions on move by more
    than five hundred times the float32 tolerance above."""
    cfg = tiny_config()
    w = draw_weights(cfg, 5)
    model = build_model(cfg, w)
    ids = np.random.default_rng(1).integers(0, 256, (1, 64)).astype(np.int32)
    other = ids.copy()
    other[0, 0] = (other[0, 0] + 1) % 256
    a = np.asarray(model(NDArray(jnp.asarray(ids)))._data)[0, 60:]
    b = np.asarray(model(NDArray(jnp.asarray(other)))._data)[0, 60:]
    assert np.abs(a - b).max() > 1e-4


def test_cache_layout_says_what_each_layer_keeps():
    cfg = tiny_config()
    model = build_model(cfg, draw_weights(cfg, 1))
    layout = model.cache_layout()
    assert [lay["kind"] for lay in layout] == [
        "state", "state", "kv", "state", "state", "state", "kv"]
    assert layout[2] == {"kind": "kv", "kv_heads": 2, "head_dim": 32,
                         "scale": 1.0 / 64}
    # 4 heads of 32 side by side on 128 lanes; the conv's last 3 inputs
    assert layout[0]["rows"] == {"ssm": ((1, 16, 128), "float32"),
                                 "conv": ((3, 4 * 32 + 2 * 16), "float32")}
    rows = model.zero_rows(3)
    assert rows[2] is None and rows[0]["ssm"].shape == (3, 1, 16, 128)


def test_the_two_copies_of_the_reference_give_one_answer():
    """The benchmark's copy and this directory's are one text and give one
    answer on one seed."""
    import importlib.util
    path = os.path.join(REPO, "benchmark", "configs",
                        "granite_hybrid_reference.py")
    assert open(path).read() == open(ref.__file__).read()
    spec = importlib.util.spec_from_file_location("bm_granite_ref", path)
    theirs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(theirs)
    cfg = tiny_config()
    w = draw_weights(cfg, 9)
    ids = jnp.asarray(np.random.default_rng(2).integers(0, 256, 40),
                      jnp.int32)
    np.testing.assert_array_equal(
        theirs.logits_at(w, ids, jnp.arange(40), cfg, Ops),
        ref.logits_at(w, ids, jnp.arange(40), cfg, Ops))


def test_published_configuration_counts_what_the_issue_counted():
    """3.19e9 parameters, 75.5 MB of float32 state a sequence and 8,192 B of
    keys and values a position, from the shapes alone (nothing allocated)."""
    import json
    cfg = json.load(open(os.path.join(
        REPO, "benchmark", "configs", "granite-4.0-h-micro.json")))
    spec = {name: shape for name, shape, _, _ in ref.param_spec(cfg)}
    count = sum(int(np.prod(s)) for s in spec.values())
    assert count == 2_985_873_152 + 205_520_896 + 2048
    assert spec["ssm_in_w"] == (36, 8512, 2048)
    assert spec["qkv_w"] == (4, 3072, 2048)
    assert ref.state_bytes_per_slot(cfg) == 75_497_472
    assert ref.kv_bytes_per_token(cfg) == 8192
    # one token decoded at a context of 350: every matrix twice, attention
    # in 4 layers, the state update in 36, the head
    mats = 40 * 3 * 2048 * 8192 + 36 * (2048 * 8512 + 2048 * 4096) \
        + 4 * (2048 * 3072 + 2048 * 2048)
    assert ref.forward_flops(cfg, 1, 350, 1) == \
        2.0 * mats + 4.0 * 4 * 2048 * 350 + 6.0 * 36 * 64 * 64 * 128 \
        + 2.0 * 2048 * 100352

"""First-contact guards: chip_smoke.py refuses to run without a TPU, the
attention dispatchers never substitute the reference on platform tpu, an
unknown accelerator has no peak, and the compile cache is placed from
outside — plus the two things four chips refused (an un-partitionable
kernel call, an indivisible sharding hint). Host-only and cheap (one tiny
interpreted kernel is the only compile); the full CPU rehearsal of
chip_smoke.py is the one ``slow`` test."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu.ops import pallas_attention as pa
from incubator_mxnet_tpu.ops import ragged_attention as ra

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke(*args, timeout):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("MXTPU_FLASH_INTERPRET", None)
    return subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
        cwd=_REPO)


def test_chip_smoke_without_tpu_fails_before_compiling():
    r = _smoke(timeout=120)
    assert r.returncode != 0
    assert "leg=device" in r.stderr and "no TPU" in r.stderr
    # no leg ran, no result line, no success marker
    assert r.stdout.strip() == ""


@pytest.mark.slow
def test_chip_smoke_cpu_rehearsal_passes_without_the_marker():
    r = _smoke("--rehearsal", timeout=1500)
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = r.stdout.strip().splitlines()[-1]
    assert last.startswith("rehearsal platform=cpu")
    assert '"ok": true' not in r.stdout


# ------------------------------------------------------------------ #
# on platform tpu nothing stands in for the Mosaic kernel
# ------------------------------------------------------------------ #

def _dispatch_calls():
    q4 = jnp.zeros((1, 8, 2, 16), jnp.float32)        # (B, T, H, D)
    pool = jnp.zeros((3, 2, 8, 32), jnp.float32)      # (P, H, ps, 2D)
    table = jnp.zeros((1, 2), jnp.int32)
    one = jnp.ones((1,), jnp.int32)
    return {
        "flash": lambda **kw: pa.use_flash_attention(q4, q4, q4),
        "flash_packed": lambda **kw: pa.flash_packed_self_attention(
            jnp.zeros((1, 128, 3 * 2 * 64), jnp.float32), 2),
        "block": lambda **kw: pa.block_attn_lse(
            q4, q4, q4, jnp.full((1,), 2, jnp.int32), False, None,
            kw.get("interpret", False)),
        "ragged_decode": lambda **kw: ra.ragged_paged_attention(
            jnp.zeros((1, 2, 16)), pool, table, one, **kw),
        "ragged_prefill": lambda **kw: ra.ragged_prefill_attention(
            jnp.zeros((8, 2, 16)), pool, table[0], 0, **kw),
        "ragged_verify": lambda **kw: ra.ragged_verify_attention(
            jnp.zeros((1, 2, 2, 16)), pool, table, one, **kw),
    }


@pytest.mark.parametrize("site", sorted(_dispatch_calls()))
def test_tpu_dispatch_raises_when_pallas_is_unavailable(monkeypatch, site):
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    monkeypatch.setattr(pa, "_pallas_available", lambda: False)
    with pytest.raises(mx.MXNetError, match="pallas failed to import"):
        _dispatch_calls()[site]()


@pytest.mark.parametrize("site", sorted(_dispatch_calls()))
def test_tpu_dispatch_raises_on_interpret_mode(monkeypatch, site):
    monkeypatch.setattr(pa, "_on_tpu", lambda: True)
    if site == "block":                # takes interpret positionally only
        with pytest.raises(mx.MXNetError, match="interpret mode"):
            _dispatch_calls()[site](interpret=True)
        return
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    with pytest.raises(mx.MXNetError, match="interpret mode"):
        _dispatch_calls()[site]()
    if not site.startswith("flash"):   # the ragged ops' explicit argument
        monkeypatch.delenv("MXTPU_FLASH_INTERPRET")
        with pytest.raises(mx.MXNetError, match="interpret mode"):
            _dispatch_calls()[site](interpret=True)


def test_off_tpu_dispatch_keeps_the_reference(monkeypatch):
    monkeypatch.delenv("MXTPU_FLASH_INTERPRET", raising=False)
    assert pa.pallas_path(False) is False
    assert pa.pallas_path(True) is True


# ------------------------------------------------------------------ #
# one peak table, unknown accelerator raises
# ------------------------------------------------------------------ #

class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


def test_unknown_accelerator_has_no_peak(monkeypatch):
    from incubator_mxnet_tpu.utils import flops
    monkeypatch.delenv("MXTPU_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v99")])
    with pytest.raises(mx.MXNetError, match="TPU v99"):
        flops.peak_flops_per_device()
    with pytest.raises(mx.MXNetError, match="TPU v99"):
        mx.profiler.mfu(1e12, 1.0)
    monkeypatch.setattr(
        jax, "devices", lambda *a: [_FakeDevice("tpu", "TPU v5 lite")])
    peak = flops.peak_flops_per_device()
    assert peak == {"flops": 197e12, "source": "tpu-datasheet",
                    "device_kind": "TPU v5 lite"}


# ------------------------------------------------------------------ #
# compile cache placed from outside
# ------------------------------------------------------------------ #

def test_compile_cache_dir_comes_from_outside(monkeypatch, tmp_path):
    from incubator_mxnet_tpu.utils import compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
        assert compile_cache.enable() == "/some/dir"
        assert jax.config.jax_compilation_cache_dir == before

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        paths = []
        for cwd in (tmp_path, _REPO):
            monkeypatch.chdir(cwd)
            paths.append(compile_cache.enable())
            assert jax.config.jax_compilation_cache_dir == paths[-1]
        assert paths[0] == paths[1] == os.path.join(_REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ------------------------------------------------------------------ #
# what four chips refused: an un-partitionable kernel, an indivisible
# sharding hint
# ------------------------------------------------------------------ #

def test_flash_kernel_is_shard_mapped_under_a_multi_device_step(
        monkeypatch):
    """Inside SPMDTrainer's trace on a mesh of several devices the kernel
    call is wrapped in shard_map (Mosaic custom calls cannot be
    auto-partitioned); same values and gradients as the plain call."""
    from incubator_mxnet_tpu.parallel import mesh as pmesh
    from incubator_mxnet_tpu.parallel.spmd import activation_sharding_scope
    monkeypatch.setenv("MXTPU_FLASH_INTERPRET", "1")
    mesh = pmesh.build_mesh(axis_sizes={"dp": 2, "fsdp": 2, "tp": 2})
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (4, 8, 2, 16)) for kk in ks)

    def loss(q, k, v):
        return jnp.sum(pa.use_flash_attention(q, k, v, causal=True) ** 2)

    def on_mesh(q, k, v):
        with activation_sharding_scope(mesh):
            return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    jaxpr = str(jax.make_jaxpr(on_mesh)(q, k, v))
    assert "shard_map" in jaxpr
    assert "shard_map" not in str(jax.make_jaxpr(loss)(q, k, v))
    got = jax.jit(on_mesh)(q, k, v)
    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert jnp.allclose(a, b, rtol=1e-5, atol=1e-5)


def test_sharding_hint_is_fitted_to_the_shape():
    """GPT-2's 50257-row embedding cannot split over fsdp=4; the hint
    drops the axis and fsdp lands on the next divisible dim."""
    from jax.sharding import PartitionSpec as P
    from incubator_mxnet_tpu.parallel import mesh as pmesh
    from incubator_mxnet_tpu.parallel.spmd import _fit_spec, _fsdp_spec
    mesh = pmesh.build_mesh(axis_sizes={"fsdp": 4, "tp": 2})
    hint = P(("tp", "fsdp"), None)
    assert _fit_spec((512, 768), hint, mesh) == hint
    assert _fit_spec((50258, 768), hint, mesh) == P("tp", None)
    fitted = _fit_spec((50257, 768), hint, mesh)
    assert fitted == P(None, None)
    assert _fsdp_spec((50257, 768), mesh, base=fitted) == P(None, "fsdp")

"""Multi-host (2-process) execution test (VERDICT r2 next-round #6).

Launches tests/dist_worker.py through tools/launch.py --launcher local —
the TPU-native mirror of the reference's
tests/nightly/dist_sync_kvstore.py CI idiom: prove the distributed
kvstore and the fused SPMD step on one box with real separate processes
(jax.distributed over a 2x4-virtual-device CPU mesh)."""

import os
import subprocess
import sys


_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_two_process_dist_sync_and_spmd_step():
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the launcher must not inherit the single-process test mesh flags
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    for attempt in range(2):  # coordinator port/races under load: 1 retry
        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
             "-n", "2", "--launcher", "local", "--",
             sys.executable, os.path.join(_REPO, "tests",
                                          "dist_worker.py")],
            capture_output=True, text=True, timeout=540, env=env,
            cwd=_REPO)
        if r.returncode == 0:
            break
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    # both workers share the launcher's stdout pipe: concurrent writes can
    # interleave on one line, so count occurrences, not lines
    oks = r.stdout.count("DIST_WORKER_OK")
    assert oks == 2, f"expected 2 worker OK markers, got: {r.stdout}"


def test_four_process_tp_fsdp_mesh_crosses_process_boundaries():
    """P=4 x 2 virtual devices: dp2 x fsdp2 x tp2 mesh whose dp/fsdp
    axes span process boundaries (VERDICT r3 #7). Asserts all ranks
    agree on loss + params AND that the distributed trajectory equals
    the single-process 8-device run of the identical program."""
    env = dict(os.environ)
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    for attempt in range(2):
        r = subprocess.run(
            [sys.executable, os.path.join(_REPO, "tools", "launch.py"),
             "-n", "4", "--launcher", "local", "--",
             sys.executable, os.path.join(_REPO, "tests",
                                          "dist_worker_p4.py")],
            capture_output=True, text=True, timeout=540, env=env,
            cwd=_REPO)
        if r.returncode == 0:
            break
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    oks = r.stdout.count("DIST4_WORKER_OK")
    assert oks == 4, f"expected 4 worker OK markers, got: {r.stdout}"

    import re
    losses = [float(m) for m in re.findall(r"DIST4_LOSS ([0-9.]+)",
                                           r.stdout)]
    assert len(losses) == 4 and max(losses) - min(losses) < 1e-6, losses

    # single-process reference on this process's own 8 virtual devices
    # (conftest set xla_force_host_platform_device_count=8): identical
    # seed/mesh-shape/data must give the same loss. Initialize THIS
    # process's backend first — the worker module re-exports a 2-device
    # XLA_FLAGS at import, which must not win the lazy jax init race.
    import jax
    assert len(jax.devices()) == 8
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "dist_worker_p4_ref", os.path.join(_REPO, "tests",
                                           "dist_worker_p4.py"))
    mod = importlib.util.module_from_spec(spec)
    # the worker module sets 2-device env vars at import for its
    # subprocess role — restore this process's env so later tests that
    # spawn subprocesses inherit the 8-device test configuration
    saved = {k: os.environ.get(k) for k in ("JAX_PLATFORMS", "XLA_FLAGS")}
    try:
        spec.loader.exec_module(mod)
        _, _, ref_loss = mod.build_and_train()
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert abs(ref_loss - losses[0]) < 1e-5, (ref_loss, losses[0])

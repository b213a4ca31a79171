"""Multichip CPU dryrun setup — ONE helper instead of N hand-rolled
``--xla_force_host_platform_device_count`` blocks.

Every multichip bench/test used to copy the same dance (set
``JAX_PLATFORMS=cpu`` + a device count before any jax import).  The
copies drifted (some handled an already-initialized backend, some
didn't), so the logic now lives here and is consumed by
``tools/bench_serving.py --tp``, ``bench.py --sharded-update`` (via
``tools/bench_sharded_update.py``), ``tools/bench_checkpoint.py``,
``__graft_entry__``, and the multichip tests.

Importing this module is safe at any point: ``paddle_tpu`` never
initializes a jax backend at import time, and the helper tears down and
re-initializes live backends when the caller got here late.
"""
from __future__ import annotations

import os

__all__ = ["force_cpu_devices", "cpu_mesh_2d", "cpu_mesh_cp"]


def force_cpu_devices(n_devices: int = 8) -> None:
    """Force JAX onto ``n_devices`` virtual CPU devices, before OR
    after a backend has been initialized.  Must not touch any real TPU
    client."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.pop("JAX_PLATFORM_NAME", None)
    import jax
    import jax.extend.backend
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        # a live backend that already satisfies the request must be a
        # NO-OP (tests import this after conftest forced the mesh —
        # tearing it down would invalidate every live array)
        if jax.devices()[0].platform == "cpu" \
                and jax.device_count() >= n_devices:
            return
        # jax_num_cpu_devices must be set before backends initialize:
        # tear the clients down and let them re-initialize under the
        # new config on the next jax.devices() call
        jax.clear_caches()
        jax.extend.backend.clear_backends()
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    if not (jax.devices()[0].platform == "cpu"
            and jax.device_count() >= n_devices):
        raise RuntimeError(
            f"CPU forcing failed: {jax.device_count()} "
            f"{jax.devices()[0].platform} device(s), wanted "
            f"{n_devices} cpu")


def cpu_mesh_2d(fsdp: int, tp: int, replica: int = 1):
    """First-class 2D dryrun mesh (round 21): force enough virtual CPU
    devices for an ``fsdp x tp`` (optionally ``dp x fsdp x tp``) mesh
    and return the :func:`paddle_tpu.jit.spmd.mesh_2d` ProcessMesh over
    them.  The one-liner behind the 2D tests and ``tools/
    bench_spmd2d.py`` — replaces ad-hoc ``force_cpu_devices(N)`` +
    hand-built ``ProcessMesh`` pairs, and never shrinks an
    already-forced larger device count (safe under the conftest-forced
    8-device mesh)."""
    force_cpu_devices(max(replica * fsdp * tp, 1))
    from ..jit.spmd import mesh_2d
    return mesh_2d(fsdp, tp, replica=replica)


def cpu_mesh_cp(cp: int, tp: int = 1):
    """Context-parallel dryrun mesh (round 22): force enough virtual
    CPU devices for a ``cp`` (optionally ``cp x tp``) mesh and return
    the :func:`paddle_tpu.jit.spmd.cp_mesh` ProcessMesh over them —
    the one-liner behind the cp tests and ``tools/bench_serving.py
    --cp``."""
    force_cpu_devices(max(cp * tp, 1))
    from ..jit.spmd import cp_mesh
    return cp_mesh(cp, tp=tp)

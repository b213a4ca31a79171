"""Device / place management.

Capability parity with the reference's Place + DeviceManager
(reference: paddle/phi/common/place.h, paddle/phi/backends/device_manager.h:134,
context pool paddle/phi/backends/context_pool.h).  On TPU the device runtime is
PJRT, surfaced through JAX; a "place" is a thin handle to a jax.Device.

The reference's hardware-plugin C ABI (paddle/phi/backends/device_ext.h) maps
to the PJRT C API plugin mechanism — selecting a platform here selects a PJRT
client underneath.
"""
from __future__ import annotations

import contextlib
import os
from typing import Optional

import jax


class Place:
    """Base device handle (reference: paddle/phi/common/place.h)."""

    device_type: str = "unknown"

    def __init__(self, device_id: int = 0):
        self.device_id = int(device_id)

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (isinstance(other, Place)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    @property
    def jax_device(self) -> jax.Device:
        devs = [d for d in jax.devices() if d.platform == self.device_type]
        if not devs:
            # never hand back another platform's device: a TPUPlace that
            # silently computes on the CPU hides the chip's absence
            raise RuntimeError(
                f"{self!r}: no {self.device_type!r} device in this "
                f"process (jax.devices() = {jax.devices()})")
        return devs[self.device_id % len(devs)]


class CPUPlace(Place):
    device_type = "cpu"


class TPUPlace(Place):
    """The TPU place — the whole point of this framework.

    Replaces the reference's GPUPlace/CUDAPlace (paddle/phi/common/place.h)."""
    device_type = "tpu"


class CustomPlace(Place):
    """Third-party accelerator place (reference: custom device plugin,
    paddle/phi/backends/custom/custom_device.cc:1059). Under PJRT a custom
    platform is just another client name."""

    def __init__(self, device_type: str, device_id: int = 0):
        super().__init__(device_id)
        self.device_type = device_type


def on_tpu() -> bool:
    """THE answer to "is this process computing on a TPU" — every
    kernel dispatcher (``use_pallas=None``) and entry point asks here.
    A backend-initialisation error propagates: a chip that failed to
    come up must never read as "not on TPU" and quietly select the XLA
    reference path."""
    return jax.devices()[0].platform == "tpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point
    that compiles for the chip (``chip_smoke.py``, ``bench.py``,
    ``tools/engine_server.py``) and return the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it and
    nothing is configured here.  Otherwise the cache lives at
    ``<checkout>/.jax_cache`` — derived from this package's location,
    because the directory is part of the cache key: a path made from a
    tempdir, a pid or the time never hits.  The CPU test suite does
    not call this."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


_CURRENT_DEVICE: list[Optional[Place]] = [None]


def _default_place() -> Place:
    plat = jax.devices()[0].platform
    if plat == "tpu":
        return TPUPlace(0)
    if plat == "cpu":
        return CPUPlace(0)
    return CustomPlace(plat, 0)


def get_device() -> str:
    """Current device string, e.g. 'tpu:0' (parity:
    python/paddle/device/__init__.py get_device)."""
    p = _CURRENT_DEVICE[0] or _default_place()
    return f"{p.device_type}:{p.device_id}"


def get_place() -> Place:
    return _CURRENT_DEVICE[0] or _default_place()


def set_device(device: str) -> Place:
    """Select the device new tensors land on, e.g. set_device('tpu')
    (parity: python/paddle/device/__init__.py set_device)."""
    if ":" in device:
        dev_type, idx = device.split(":")
        idx = int(idx)
    else:
        dev_type, idx = device, 0
    dev_type = {"gpu": "tpu"}.get(dev_type, dev_type)  # be forgiving
    if dev_type == "cpu":
        place: Place = CPUPlace(idx)
    elif dev_type == "tpu":
        place = TPUPlace(idx)
    else:
        place = CustomPlace(dev_type, idx)
    _CURRENT_DEVICE[0] = place
    return place


@contextlib.contextmanager
def device_guard(device: str):
    old = _CURRENT_DEVICE[0]
    set_device(device)
    try:
        yield
    finally:
        _CURRENT_DEVICE[0] = old


def device_count(device_type: str = "tpu") -> int:
    return len([d for d in jax.devices() if d.platform == device_type])


def is_compiled_with_tpu() -> bool:
    return device_count("tpu") > 0


def synchronize():
    """Block until all outstanding device work is done (parity:
    paddle.device.synchronize)."""
    jax.effects_barrier()


def device_group_key(value):
    """Hashable identity of the device set an array is committed to, or
    None when unknown.  Used to group per-submesh work (pipeline stages
    place parameters on disjoint submeshes; one jitted computation cannot
    mix device sets)."""
    try:
        return frozenset(d.id for d in value.devices())
    except Exception:
        return None

"""paddle.device — device management + memory accounting.

Parity: python/paddle/device/ (reference — set_device/get_device,
device/cuda/* memory stats backed by paddle/fluid/memory/stats.h
DEVICE_MEMORY_STAT macros, streams/events).

TPU-native: allocation is PJRT's job, so stats come from the PJRT
``Device.memory_stats()`` counters (bytes_in_use / peak_bytes_in_use on
TPU).  Backends without allocator telemetry (XLA CPU) fall back to
summing live on-device arrays, with the peak tracked at query points.
Streams/events collapse to XLA's async dispatch: synchronize =
drain-and-block.
"""
from __future__ import annotations

from typing import Optional

import jax

from ..core.device import (CPUPlace, TPUPlace, CustomPlace, get_device,
                           set_device, is_compiled_with_tpu)


def is_compiled_with_cuda() -> bool:
    return any(d.platform == "gpu" for d in jax.devices())


def is_compiled_with_xpu() -> bool:
    return False

__all__ = ["set_device", "get_device", "get_all_device_type",
           "get_available_device", "get_available_custom_device",
           "device_count", "synchronize", "memory_allocated",
           "max_memory_allocated", "memory_reserved",
           "max_memory_reserved", "reset_peak_memory_stats",
           "memory_stats",
           "cuda", "CPUPlace", "TPUPlace", "CustomPlace",
           "Stream", "Event", "current_stream", "stream_guard"]


def _device(dev: Optional[int] = None):
    devs = jax.local_devices()
    return devs[dev or 0]


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def get_available_custom_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()
            if d.platform not in ("cpu", "gpu", "tpu")]


def device_count(device_type: Optional[str] = None) -> int:
    if device_type is None:
        return jax.device_count()
    return sum(1 for d in jax.devices() if d.platform == device_type)


def synchronize(device=None):
    """Block until all dispatched device work is done."""
    jax.effects_barrier()
    for arr in jax.live_arrays():
        try:
            arr.block_until_ready()
        except Exception:
            pass


# ---------------------------------------------------------------------------
# memory stats (reference: paddle/fluid/memory/stats.h — peak/current per
# device, surfaced as paddle.device.cuda.max_memory_allocated)
# ---------------------------------------------------------------------------
_PEAK_FALLBACK = {}     # device index -> peak bytes seen at query points
_PEAK_BASELINE = {}     # device index -> PJRT peak counter at last reset


def memory_stats(device=None) -> dict:
    """The raw PJRT allocator counters for one device
    (``bytes_in_use`` / ``peak_bytes_in_use`` / ``bytes_limit`` ... on
    TPU) — SURVEY §5.5 memory-stat parity.  Backends without allocator
    telemetry (XLA CPU) and failed/uninitialized backends return ``{}``
    instead of raising, so telemetry code can poll unconditionally."""
    try:
        return _dev_stats(_device(device))
    except Exception:                                 # noqa: BLE001
        return {}


def _dev_stats(d) -> dict:
    """Stats for an already-resolved device; {} when none/failed."""
    try:
        stats = d.memory_stats()
    except Exception:                                 # noqa: BLE001
        return {}
    return dict(stats) if stats else {}


def _live_bytes(dev) -> int:
    total = 0
    for arr in jax.live_arrays():
        try:
            for shard in arr.addressable_shards:
                if shard.device == dev:
                    total += shard.data.nbytes
        except Exception:
            pass
    return total


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (parity:
    paddle.device.cuda.memory_allocated).  Never raises: a backend
    without stats falls back to summing live arrays, and a missing/
    broken backend reports 0."""
    try:
        d = _device(device)
    except Exception:                                 # noqa: BLE001
        return 0
    stats = _dev_stats(d)
    if stats and "bytes_in_use" in stats:
        cur = int(stats["bytes_in_use"])
    else:
        cur = _live_bytes(d)
    key = d.id
    _PEAK_FALLBACK[key] = max(_PEAK_FALLBACK.get(key, 0), cur)
    return cur


def max_memory_allocated(device=None) -> int:
    """Peak allocated bytes (parity: paddle.device.cuda.max_memory_allocated).

    On backends without allocator counters the peak is tracked at query
    points — call memory_allocated() at the places you care about.  PJRT
    exposes no peak-reset, so after reset_peak_memory_stats() the device
    counter only counts if it rises above its value at reset; otherwise
    current usage sampled at query points is the post-reset peak.
    Never raises; 0 when no backend is available."""
    try:
        d = _device(device)
    except Exception:                                 # noqa: BLE001
        return 0
    stats = _dev_stats(d)
    if stats and "peak_bytes_in_use" in stats:
        peak = int(stats["peak_bytes_in_use"])
        base = _PEAK_BASELINE.get(d.id)
        if base is None:
            return peak
        sampled = max(_PEAK_FALLBACK.get(d.id, 0),
                      int(stats.get("bytes_in_use", 0)))
        _PEAK_FALLBACK[d.id] = sampled
        return peak if peak > base else sampled
    memory_allocated(device)
    return _PEAK_FALLBACK.get(d.id, 0)


def memory_reserved(device=None) -> int:
    stats = memory_stats(device)
    if stats:
        for k in ("bytes_reserved", "pool_bytes", "bytes_limit"):
            if k in stats:
                return int(stats[k])
    return memory_allocated(device)


def max_memory_reserved(device=None) -> int:
    return max(memory_reserved(device), max_memory_allocated(device))


def reset_peak_memory_stats(device=None):
    try:
        d = _device(device)
    except Exception:                                 # noqa: BLE001
        return
    _PEAK_FALLBACK[d.id] = 0
    stats = _dev_stats(d)
    if "peak_bytes_in_use" in stats:
        _PEAK_BASELINE[d.id] = int(stats["peak_bytes_in_use"])


def reset_max_memory_allocated(device=None):
    reset_peak_memory_stats(device)


def reset_max_memory_reserved(device=None):
    reset_peak_memory_stats(device)


# ---------------------------------------------------------------------------
# streams/events (XLA dispatch is already async; sync points map to
# block_until_ready)
# ---------------------------------------------------------------------------
class Stream:
    """Parity: paddle.device.Stream.  XLA runs one async dispatch stream
    per device; explicit streams are ordering no-ops kept for API parity."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize(self.device)

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev


class Event:
    """Parity: paddle.device.Event."""

    def __init__(self, device=None, enable_timing=False, blocking=False):
        self._recorded = False
        import time
        self._time = time.perf_counter

    def record(self, stream=None):
        self._recorded = True
        self._t0 = self._time()

    def query(self) -> bool:
        return True

    def synchronize(self):
        synchronize()

    def elapsed_time(self, end_event) -> float:
        return max(0.0, (getattr(end_event, "_t0", self._time())
                         - getattr(self, "_t0", 0.0)) * 1000.0)


_CURRENT_STREAM = Stream()


def current_stream(device=None) -> Stream:
    return _CURRENT_STREAM


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


# ---------------------------------------------------------------------------
# paddle.device.cuda namespace (reference API surface; maps to the
# current accelerator)
# ---------------------------------------------------------------------------
class _CudaNamespace:
    Stream = Stream
    Event = Event

    @staticmethod
    def device_count():
        n = device_count("gpu")
        return n if n else device_count("tpu")

    max_memory_allocated = staticmethod(max_memory_allocated)
    memory_allocated = staticmethod(memory_allocated)
    max_memory_reserved = staticmethod(max_memory_reserved)
    memory_reserved = staticmethod(memory_reserved)
    reset_max_memory_allocated = staticmethod(reset_max_memory_allocated)
    reset_max_memory_reserved = staticmethod(reset_max_memory_reserved)
    synchronize = staticmethod(synchronize)
    current_stream = staticmethod(current_stream)
    stream_guard = staticmethod(stream_guard)

    @staticmethod
    def get_device_properties(device=None):
        d = _device(device)
        class _Props:
            name = d.device_kind
            total_memory = (d.memory_stats() or {}).get("bytes_limit", 0)
            major, minor = 0, 0
            multi_processor_count = 1
        return _Props()

    @staticmethod
    def empty_cache():
        import gc
        gc.collect()

    @staticmethod
    def get_device_name(device=None):
        """Parity: device/cuda get_device_name — the accelerator kind
        string (TPU kind here, e.g. 'TPU v5 lite')."""
        return _device(device).device_kind

    @staticmethod
    def get_device_capability(device=None):
        """Parity: get_device_capability — (major, minor).  CUDA compute
        capability has no TPU analog; the TPU generation number is the
        meaningful major version."""
        kind = _device(device).device_kind
        import re as _re
        m = _re.search(r"v(\d+)", kind)
        return (int(m.group(1)) if m else 0, 0)


cuda = _CudaNamespace()


def get_cudnn_version():
    """Parity: paddle.device.get_cudnn_version — None when not built
    with cuDNN (always, on the TPU stack)."""
    return None


def is_compiled_with_ipu() -> bool:
    return False


def is_compiled_with_cinn() -> bool:
    """The graph compiler here is XLA, not CINN."""
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_distribute() -> bool:
    """Distributed (collectives over ICI/DCN) is always built in."""
    return True


def is_compiled_with_custom_device(device_type: str = None) -> bool:
    """PJRT plugins are the custom-device mechanism; 'tpu' counts."""
    import jax
    try:
        plats = {d.platform for d in jax.devices()}
    except RuntimeError:
        return False
    if device_type is None:
        return bool(plats - {"cpu", "gpu"})
    return device_type in plats


def get_all_custom_device_type():
    import jax
    try:
        return sorted({d.platform for d in jax.devices()}
                      - {"cpu", "gpu"})
    except RuntimeError:
        return []


class XPUPlace:
    """Parity name (device/__init__ XPUPlace): Kunlun XPU hardware is
    not present on a TPU stack; constructing one is an error, as on any
    paddle build without XPU support."""

    def __init__(self, dev_id=0):
        raise RuntimeError(
            "XPUPlace is unavailable: this framework targets TPU "
            "devices (use paddle.TPUPlace / CPUPlace)")


class IPUPlace:
    """Parity name (device/__init__ IPUPlace); same contract as
    XPUPlace on a non-IPU build."""

    def __init__(self):
        raise RuntimeError(
            "IPUPlace is unavailable: this framework targets TPU "
            "devices (use paddle.TPUPlace / CPUPlace)")


def set_stream(stream=None):
    """Parity: device.set_stream.  XLA orders work on a single device
    stream by data dependence; the call validates the handle and
    returns the previous (current) stream."""
    prev = current_stream()
    if stream is not None and not isinstance(stream, Stream):
        raise TypeError(f"set_stream expects a Stream, got {type(stream)}")
    return prev


__all__ += ["get_cudnn_version", "XPUPlace", "IPUPlace",
            "is_compiled_with_ipu", "is_compiled_with_cinn",
            "is_compiled_with_rocm", "is_compiled_with_distribute",
            "is_compiled_with_custom_device",
            "get_all_custom_device_type", "set_stream"]


"""paddle.device — device selection + memory observability.

Reference: python/paddle/device/ (set_device:189) and the memory-stat
surface paddle.device.cuda.max_memory_allocated backed by
paddle/fluid/memory/stats.cc. Here the allocator is PJRT's; the stats
come from ``Device.memory_stats()`` (bytes_in_use / peak_bytes_in_use),
with a compiled-executable fallback (``memory_analysis``) for runtimes
that don't export allocator stats.
"""
from __future__ import annotations

from paddle_tpu.core.place import (  # noqa: F401
    CPUPlace, CustomPlace, Place, TPUPlace, get_device, set_device,
    is_compiled_with_tpu,
)

__all__ = ["get_device", "set_device", "device_count",
           "memory_allocated", "max_memory_allocated",
           "memory_reserved", "max_memory_reserved",
           "reset_max_memory_allocated", "reset_peak_memory_stats",
           "memory_stats", "empty_cache", "get_memory_info"]


def _device(device=None):
    import jax

    devs = jax.local_devices()
    if device is None:
        return devs[0]
    if isinstance(device, int):
        return devs[device]
    return device


def device_count() -> int:
    import jax

    return jax.local_device_count()


def memory_stats(device=None) -> dict:
    """Raw PJRT allocator stats. The CPU backend exports none and gives
    {}; on an accelerator missing stats are an error, not an empty
    dict that reads as zero bytes."""
    d = _device(device)
    stats = d.memory_stats()
    if stats is None:
        if d.platform == "cpu":
            return {}
        raise RuntimeError(
            f"{d.device_kind} ({d.platform}) exports no allocator stats")
    return stats


def memory_allocated(device=None) -> int:
    """Bytes currently held by live buffers on the device (reference
    paddle.device.cuda.memory_allocated / stats.cc Allocated)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """High-water mark of allocated bytes (reference
    max_memory_allocated / stats.cc peak value)."""
    s = memory_stats(device)
    return int(s.get("peak_bytes_in_use", s.get("bytes_in_use", 0)))


def memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("bytes_reserved", s.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    s = memory_stats(device)
    return int(s.get("peak_bytes_reserved",
                     s.get("peak_bytes_in_use", 0)))


def reset_max_memory_allocated(device=None) -> None:
    """PJRT keeps its own peak counter; where the runtime can't reset
    it, this is a documented no-op (the reference resets an in-process
    counter, stats.cc)."""
    try:
        _device(device).clear_memory_stats()  # pragma: no cover
    except Exception:
        pass


reset_peak_memory_stats = reset_max_memory_allocated


def empty_cache() -> None:
    """Parity no-op: PJRT owns the buffer pool."""


def get_memory_info(device=None) -> dict:
    """Summary dict: allocated/peak/limit bytes where available."""
    s = memory_stats(device)
    return {
        "allocated": int(s.get("bytes_in_use", 0)),
        "peak_allocated": int(s.get("peak_bytes_in_use", 0)),
        "limit": int(s.get("bytes_limit", 0)),
    }


def compiled_memory_analysis(jitted_or_lowered) -> dict:
    """HBM footprint of ONE compiled executable (argument/output/temp/
    code bytes) — the fallback observability path when allocator stats
    are unavailable. Accepts a jax ``Compiled`` object or anything with
    ``memory_analysis()``."""
    ma = jitted_or_lowered.memory_analysis()
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


# ---------------------------------------------------------------------------
# custom-device plugin seam (reference: paddle/phi/capi/ C ABI +
# backends/custom/custom_device.cc:47 C_DeviceInterface; python
# discovery device/__init__.py:46-50 CUSTOM_DEVICE_ROOT)
# ---------------------------------------------------------------------------

_registered_backends = {}


def register_backend(name, pjrt_plugin_path=None, factory=None,
                     priority=400, experimental=True):
    """Plug an external accelerator backend without modifying the
    framework — the reference's custom-device mechanism re-based on
    PJRT: hardware vendors ship a PJRT C-API plugin (`.so`), the
    framework registers it with the runtime and every op/collective
    works through the same XLA path (the role of the C kernel/CCL ABI
    in paddle/phi/capi/).

    ``pjrt_plugin_path``: path to a PJRT plugin shared library, loaded
    via jax's plugin discovery. ``factory``: alternatively a callable
    returning an xla_client.Client (in-process backends, tests).
    """
    import jax

    if name in _registered_backends:
        raise ValueError(f"backend {name!r} already registered")
    if (pjrt_plugin_path is None) == (factory is None):
        raise ValueError(
            "register_backend needs exactly one of pjrt_plugin_path "
            "(vendor .so) or factory (in-process client constructor)")
    if pjrt_plugin_path is not None:
        from jax._src.xla_bridge import register_plugin

        register_plugin(name, library_path=pjrt_plugin_path,
                        priority=priority)
    else:
        from jax._src.xla_bridge import register_backend_factory

        register_backend_factory(name, factory, priority=priority,
                                 experimental=experimental)
    _registered_backends[name] = pjrt_plugin_path or factory
    return name


def registered_backends():
    """Names registered through register_backend (the reference lists
    discovered custom devices in get_all_custom_device_type)."""
    return sorted(_registered_backends)


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return registered_backends()

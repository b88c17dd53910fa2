"""HBM / host memory observability facade.

Reference analog: paddle/fluid/memory/stats.h (DEVICE_MEMORY_STAT_*,
HostMemoryStat*) and python/paddle/device/cuda — memory_allocated /
max_memory_allocated / memory_reserved.

On TPU the runtime (PJRT) owns the allocator, so this facade *observes*
rather than allocates: it reads ``Device.memory_stats()`` where the
plugin provides it and falls back to walking ``jax.live_arrays()`` —
the framework-visible HBM working set.  That is exactly the information
the reference's stats layer exposes for OOM debugging (which buffers are
live, how big, and the peak), which PJRT otherwise keeps opaque.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

__all__ = [
    "memory_stats",
    "memory_allocated",
    "max_memory_allocated",
    "live_tensor_bytes",
    "top_live_buffers",
    "memory_summary",
    "log_memory",
]

# peak tracker for the live-arrays fallback (device stats report their own
# peak when available)
_peak_seen = [0]


def _device(device=None):
    import jax

    if device is not None and not isinstance(device, (str, int)):
        return device
    devs = jax.devices()
    if isinstance(device, int):
        return devs[device]
    if isinstance(device, str) and ":" in device:
        kind, _, idx = device.partition(":")
        return [d for d in devs if d.platform == kind][int(idx)]
    return devs[0]


def memory_stats(device=None) -> Dict[str, int]:
    """Raw per-device allocator stats (empty dict when the backend
    doesn't report them — e.g. the CPU backend)."""
    try:
        stats = _device(device).memory_stats()
    except Exception:
        stats = None
    return dict(stats) if stats else {}


def live_tensor_bytes(device=None) -> int:
    """Bytes held by framework-visible live arrays on ``device``."""
    import jax

    try:
        dev = _device(device)
        total = 0
        for a in jax.live_arrays():
            try:
                if dev in a.devices():
                    total += a.nbytes // len(a.devices())
            except Exception:
                pass
        return total
    except Exception:
        return 0


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on ``device`` (reference:
    paddle.device.cuda.memory_allocated)."""
    stats = memory_stats(device)
    for key in ("bytes_in_use", "bytes_used"):
        if key in stats:
            return int(stats[key])
    n = live_tensor_bytes(device)
    _peak_seen[0] = max(_peak_seen[0], n)
    return n


def max_memory_allocated(device=None) -> int:
    """Peak allocated bytes (reference: max_memory_allocated).  Uses the
    allocator's own peak when reported, else the observed live-array peak."""
    stats = memory_stats(device)
    for key in ("peak_bytes_in_use", "max_bytes_in_use"):
        if key in stats:
            return int(stats[key])
    memory_allocated(device)  # refresh the fallback peak
    return _peak_seen[0]


def top_live_buffers(n: int = 10, device=None) -> List[Tuple[int, str, str]]:
    """The ``n`` biggest live arrays: (nbytes, shape, dtype) descending.
    This is the OOM post-mortem the reference prints from its allocator
    stats (memory/stats.h + allocator_facade retry logging)."""
    import jax

    entries = []
    try:
        dev = _device(device)
        for a in jax.live_arrays():
            try:
                if dev in a.devices():
                    entries.append(
                        (int(a.nbytes // len(a.devices())), str(a.shape), str(a.dtype))
                    )
            except Exception:
                pass
    except Exception:
        pass
    entries.sort(reverse=True)
    return entries[:n]


def memory_summary(device=None, top: int = 8) -> str:
    """Human-readable HBM report."""
    lines = []
    stats = memory_stats(device)
    alloc = memory_allocated(device)
    peak = max_memory_allocated(device)
    src = "allocator" if stats else "live-arrays"
    lines.append(
        f"memory[{src}]: in_use={alloc / 2**20:.1f}MiB peak={peak / 2**20:.1f}MiB"
    )
    if "bytes_limit" in stats:
        lines.append(f"  limit={stats['bytes_limit'] / 2**20:.1f}MiB")
    for nbytes, shape, dtype in top_live_buffers(top, device):
        lines.append(f"  {nbytes / 2**20:9.1f}MiB  {dtype:10s} {shape}")
    return "\n".join(lines)


def log_memory(tag: str = "", device=None, file=None) -> int:
    """Print a one-line HBM usage note; returns bytes in use."""
    import sys

    alloc = memory_allocated(device)
    peak = max_memory_allocated(device)
    print(
        f"[paddle_tpu.memory] {tag}: in_use={alloc / 2**20:.1f}MiB "
        f"peak={peak / 2**20:.1f}MiB",
        file=file or sys.stderr,
    )
    return alloc

"""Process start-up shared by every entry point (``run.py``,
``chip_smoke.py``, ``bench.py``, ``parity.py``, ``tools/multichip``,
``tools/fleet_chaos``, the fleet's plane processes): where compiled
programs are cached, and which device the process actually got.

One chip belongs to one process. A parent that has touched a JAX device
holds it; a child that needs the same chip then fails backend
initialization (or lands on the host CPU). Nothing here spawns anything —
the point is that every summary line names the device, so a run that
landed somewhere else says so.
"""

from __future__ import annotations

import os
from typing import Dict, Optional

__all__ = [
    "DEFAULT_CACHE_DIR",
    "device_memory_limit",
    "device_summary",
    "enable_compile_cache",
]

# <checkout>/.jax_cache — a FIXED path (the directory is part of the
# cache key's lookup: one that moves between runs never hits).
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and
    this sets no directory in code. Otherwise the cache lives at
    :data:`DEFAULT_CACHE_DIR` inside the checkout. A directory that
    cannot be created raises — a run that believes it is cached and is
    not pays minutes of compilation per process without saying so.
    """
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR


def device_summary() -> Dict[str, object]:
    """``backend`` / ``device_kind`` / ``device_count`` as JAX reports
    them — the block every entry point's summary line carries."""
    import jax

    devices = jax.devices()
    return {
        "backend": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
    }


def device_memory_limit() -> Optional[int]:
    """The first local device's ``bytes_limit``, or None on a backend
    that reports no memory statistics (the CPU test mesh:
    ``memory_stats()`` returns None there). On a TPU a missing limit is
    an error, never a guess — capacity decisions made against a made-up
    HBM size are how a fit gets routed to a tier that cannot hold it."""
    import jax

    device = jax.local_devices()[0]
    stats = device.memory_stats() or {}
    limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if limit:
        return int(limit)
    if device.platform == "tpu":
        raise RuntimeError(
            f"{device.device_kind}: memory_stats() reported no bytes_limit "
            f"({stats!r}); refusing to guess the device's memory"
        )
    return None

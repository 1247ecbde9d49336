"""Start-up helpers for entry points: the devices a chip run may use,
where compiled programs are cached, and virtual CPU devices for rehearsing
a multi-chip run.

None runs on ``import horovod_tpu``; a script that compiles for the chip
calls :func:`enable_compile_cache` itself before its first jit.
"""

from __future__ import annotations

import os
from pathlib import Path

# A cache directory that moves between runs never hits, so it is derived
# from this file's place in the checkout: never from tempfile, a pid or the
# clock.
_CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` decides the place when it is set (JAX
    reads it itself; no directory is set in code, and ``hvdrun`` children
    inherit it).  Unset, the cache lives in ``<checkout>/.jax_cache``.
    Every program is cached, however quickly it compiled: a fresh machine
    pays for hundreds of small programs as well as the few large ones.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def accelerator_devices(*, cpu_by_name: bool = False) -> list:
    """``jax.devices()`` for a run that measures or proves something on
    the chip: exits non-zero, naming what JAX found, unless they are TPU
    devices.  JAX itself falls back to the CPU when it finds no chip; a
    caller must not.  ``cpu_by_name=True`` also admits CPU devices when
    the caller asked for them by name (``JAX_PLATFORMS=cpu``), which is a
    rehearsal and must be reported as ``platform: cpu``."""
    import jax

    asked = os.environ.get("JAX_PLATFORMS", "")
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "tpu" or (cpu_by_name and platform == "cpu"
                             and asked.split(",")[0] == "cpu"):
        return devices
    raise SystemExit(
        f"no accelerator: JAX found platform={platform!r} "
        f"device_kind={devices[0].device_kind!r} count={len(devices)} "
        f"(JAX_PLATFORMS={asked!r}); this run needs platform='tpu'"
        + (" or JAX_PLATFORMS=cpu asked for by name" if cpu_by_name
           else ""))


def use_virtual_cpu_devices(n_devices: int) -> None:
    """Run this process on ``n_devices`` virtual CPU devices.

    For rehearsing mesh code on a box with no chip.  Call before JAX
    initialises a backend; a process started with ``JAX_PLATFORMS=cpu
    XLA_FLAGS=--xla_force_host_platform_device_count=N`` needs no call.
    """
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)

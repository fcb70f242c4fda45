"""The chip this run holds: refuse anything else, and describe it."""

from __future__ import annotations

import os
import pathlib

import jax


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def setup_compile_cache(root: pathlib.Path) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache`` (a fixed path: the path is
    part of the cache's key). Every program is kept, however quick its
    compile, so that a warm run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_tpu(chips: int) -> list:
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (device 0 is {devs[0].platform}); "
                     f"the benchmark never falls back to it")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def describe(devices: list) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}

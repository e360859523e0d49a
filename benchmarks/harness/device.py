"""The device a run is on: the look for the chip (a measurement path that
finds no chip fails; it never falls back to the CPU), what JAX reports of
it, its memory high-water mark, and where the compilation cache lives."""

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


class NoChipError(RuntimeError):
    pass


def enable_compile_cache(root):
    """JAX's persistent compilation cache at a fixed path: the caller's
    ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself), otherwise
    ``<checkout>/.jax_cache``. The path is part of the cache key, so it is
    never made from a temporary name, a process id or the time. Every
    program is kept, however quickly it compiled, so that a cell's second
    run finds all of them."""
    import jax

    placed = os.environ.get(CACHE_ENV)
    path = placed or os.path.join(root, ".jax_cache")
    if not placed:
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def require_chips(n):
    """Fail unless JAX sees an accelerator with at least ``n`` chips."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChipError(
            f"this benchmark measures the chip: JAX found platform "
            f"{devs[0].platform!r} ({len(devs)} devices), no TPU")
    if len(devs) < n:
        raise NoChipError(f"the cell needs {n} chips, JAX found {len(devs)}")
    return devs[:n]


def device_info(devices):
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices):
    """Peak bytes in use on the fullest chip, or None where the backend
    keeps no such statistic (the CPU)."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if not stats or "peak_bytes_in_use" not in stats:
            return None
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)

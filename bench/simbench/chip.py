"""The chip a run is held to, and the compile cache it keeps.

Shared by ``run.py`` and ``control.py``, which call :func:`compile_cache`
before JAX touches a device and then :func:`tpu_device`.
"""
from __future__ import annotations


class NoDevice(Exception):
    pass


def compile_cache() -> str:
    """Turns on the program's persistent compile cache (a fixed directory
    in the checkout, or ``$JAX_COMPILATION_CACHE_DIR``) and lowers its
    thresholds so that every executable of a cell is cached."""
    from repro.runtime.compile_cache import use_compile_cache
    cache_dir = use_compile_cache()
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def tpu_device(chips: int) -> dict:
    """The device record, or :class:`NoDevice` off a TPU or short of chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: JAX runs on {devs[0].platform}")
    if len(devs) < chips:
        raise NoDevice(f"{chips} chips needed, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_bytes(chips: int):
    """Peak device memory of the fullest of the first ``chips`` devices."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

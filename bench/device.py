"""The chip: which one this is, its peaks, its memory, and what it compiles.

Peaks are published figures, keyed by ``device_kind`` as JAX reports it.
A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

#: device_kind -> peaks of one chip.  Source: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or fewer than the cell needs."""


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def require_tpu(chips: int):
    """The first ``chips`` TPU devices, or ``NoChip``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise NoChip(f"needs {chips} TPU device(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return devs[:chips]


def describe(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def enable_compile_cache(directory: str) -> None:
    """Keep every compiled program in ``directory``, however quick its
    compile was, so that a later run loads all of them."""
    import jax
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileMeter:
    """Backend compiles (a persistent-cache hit skips them) and their
    seconds, and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._count)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.compiles += 1
            self.seconds += secs

    def _count(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

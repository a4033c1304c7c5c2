"""The device this process measures: claimed once, named in every result."""

import os
import sys

from .manifest import ROOT

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def claim(chips: int, say=print) -> dict:
    """Initialise the backend. Anything but a TPU with at least ``chips``
    chips ends the process with a code other than 0 and no result."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        sys.exit(f"benchmark: JAX found no accelerator: {e}")
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.exit(f"benchmark: needs {chips} TPU chip(s); JAX reports "
                 f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind})")
    if len(devs) != chips:
        sys.exit(f"benchmark: the cell asks for {chips} chip(s) and this "
                 f"machine holds {len(devs)}; run it on a machine of its size")
    return describe(devs[:chips])


def describe(devs) -> dict:
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def place_compile_cache() -> str:
    """JAX's persistent compilation cache: where the environment says, else
    at one fixed directory inside the checkout (the path is part of the
    cache's key). Every program is kept, however quick its compile."""
    import jax

    path = os.environ.get(CACHE_ENV) or os.path.join(ROOT, ".jax_cache")
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devs) -> int:
    """Peak bytes in use on the fullest chip (0 where the backend keeps
    no such count, as on the host CPU)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def bytes_in_use(devs) -> int:
    return max(int((d.memory_stats() or {}).get("bytes_in_use", 0)) for d in devs)


class LoweringCounter:
    """Counts the programs JAX lowers (each is then compiled, or loaded
    from the persistent cache): inside a measured window there must be
    none. One listener a process; ``count`` only ever grows."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    _instance = None

    def __init__(self):
        self.count = 0

    @classmethod
    def get(cls) -> "LoweringCounter":
        if cls._instance is None:
            from jax import monitoring

            cls._instance = cls()
            monitoring.register_event_duration_secs_listener(cls._instance._on)
        return cls._instance

    def _on(self, name, _secs, **_kw):
        if name == self.EVENT:
            self.count += 1

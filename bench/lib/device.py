"""The chip under the benchmark: the TPU check, the compile cache, the
compile counter and device memory."""

from __future__ import annotations

import os
import sys

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(SystemExit):
    """JAX found no TPU, or fewer chips than the cell needs."""


def tpu_or_exit(chips: int) -> dict:
    """The device as JAX reports it; exits non-zero unless JAX found a
    TPU with at least ``chips`` devices."""
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (platform {d.platform!r}); "
                     f"the benchmark runs on no other backend")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def place_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: where a set
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads it itself), else at
    the fixed ``<root>/.jax_cache``.  Every program is cached, so only
    a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


class CompileCounter:
    """Backend compiles (cache loads included) and persistent-cache
    hits, as JAX reports them."""

    def __init__(self):
        import jax
        self.n = 0
        self.s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.s += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def peak_bytes() -> int | None:
    """``peak_bytes_in_use`` of the fullest device, where reported."""
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

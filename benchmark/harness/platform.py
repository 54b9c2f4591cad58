"""What the harness reads from the machine itself: the device JAX
found, programs built (compiled or loaded from the persistent cache),
device and host memory peaks."""

from __future__ import annotations

import resource
import sys

NO_ACCELERATOR = 3      # exit code: no TPU, or fewer chips than the cell needs

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE = "/jax/compilation_cache/"
_CACHE_EVENTS = {_CACHE + "compile_requests_use_cache": "requests",
                 _CACHE + "cache_hits": "hits",
                 _CACHE + "cache_misses": "misses"}


def gate(chips: int, rehearse: bool) -> dict:
    """Initialize the backend and refuse the wrong one: a measurement
    never falls back to the CPU. Returns the ``device`` stamp."""
    import jax

    platform = jax.default_backend()
    want = "cpu" if rehearse else "tpu"
    found = len(jax.devices())
    if platform != want or found < chips:
        print(f"benchmark: JAX found {found} {platform!r} device(s); this "
              f"cell needs {chips} {want!r}"
              + ("" if rehearse else " (--rehearse-cpu runs the plumbing on "
                 "the CPU and measures nothing)"), file=sys.stderr)
        sys.exit(NO_ACCELERATOR)
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": found}


class BuildCounter:
    """Counts programs this process builds — each backend compile or
    load from the persistent cache — and the cache's own traffic. Read
    ``builds`` before and after the window: the difference must be 0."""

    def __init__(self):
        import jax

        self.builds = 0
        self.cache = dict.fromkeys(_CACHE_EVENTS.values(), 0)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == _BUILD_EVENT:
            self.builds += 1

    def _event(self, event: str, **_kw) -> None:
        if event in _CACHE_EVENTS:
            self.cache[_CACHE_EVENTS[event]] += 1


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the first ``chips`` devices;
    None where the backend does not report it (the CPU)."""
    import jax

    stats = [d.memory_stats() for d in jax.devices()[:chips]]
    if not all(stats):
        return None
    return max(s["peak_bytes_in_use"] for s in stats)


def host_rss_peak_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024

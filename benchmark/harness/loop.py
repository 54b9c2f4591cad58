"""What both drivers share: the closed loop they measure in, the
device trace a few units of it run under, and the outcome they hand to
``run.py``."""

from __future__ import annotations

import contextlib
import threading
import time
import traceback
from statistics import median

from benchmark.harness import platform


class SetupError(Exception):
    """The run could not be set up; nothing was measured."""


def closed_loop(run_unit, seconds: float, concurrent: int = 1) -> list:
    """``concurrent`` slots, each running one unit (a task, a step)
    after another: a unit is started while the window is open, and the
    one in flight is finished. ``run_unit(index)`` returns the unit's
    record (a dict with at least ``wall_s``); one that raises is
    recorded as ``{"error": ...}``. Returns the records in order of
    start."""
    deadline = time.perf_counter() + seconds
    units: list = []
    lock = threading.Lock()

    def slot() -> None:
        while time.perf_counter() < deadline:
            with lock:
                index = len(units)
                units.append(None)
            try:
                units[index] = run_unit(index)
            except Exception as e:  # noqa: BLE001 - a unit that raises is
                # a failed unit, counted; the window goes on
                traceback.print_exc()
                units[index] = {"error": f"{type(e).__name__}: {e}"[:500]}

    if concurrent == 1:
        slot()
    else:
        threads = [threading.Thread(target=slot, name=f"bench-slot-{i}")
                   for i in range(concurrent)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    return units


class DeviceTrace:
    """One profiler trace with the Python tracer off. Inside
    ``session()``, each ``mark()`` frames a stretch of the traced window
    with the marker annotation the reduction looks for: one around a
    task, or one around each of a few steps so that the harness's own
    checks between them stay outside. ``perf_at_marker`` is the host
    clock as the first marker opened: the one instant known on both
    clocks."""

    def __init__(self, trace_dir: str):
        self.trace_dir = trace_dir
        self.perf_at_marker = None

    @contextlib.contextmanager
    def session(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1      # the markers; not every host call
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()

    @contextlib.contextmanager
    def mark(self):
        import jax

        from benchmark.trace.reduce import WINDOW_MARKER

        if self.perf_at_marker is None:
            self.perf_at_marker = time.perf_counter()
        with jax.profiler.TraceAnnotation(WINDOW_MARKER):
            yield


def outcome(device: dict, units: list, setup_s: float, wall_metric: str,
            unit_bytes: int, built: int, chips: int, cache: dict,
            phases: dict) -> dict:
    """The driver's result in the form ``run.py`` prints from:
    ``end_to_end`` holds ``setup_s``, the median unit wall under
    ``wall_metric`` and ``goodput_MBps`` (``unit_bytes`` delivered per
    finished unit over the sum of their walls); ``obs`` is what the
    per-layer readers take, and the driver adds to it."""
    good = [u for u in units if "error" not in u]
    walls = [u["wall_s"] for u in good]
    out = {
        "device": device, "attempted": len(units),
        "failed": len(units) - len(good),
        "errors": [u["error"] for u in units if "error" in u],
        "end_to_end": {"setup_s": setup_s}, "walls": walls,
        "cache": cache, "setup_phases": phases,
    }
    if good:
        out["end_to_end"][wall_metric] = median(walls)
        out["end_to_end"]["goodput_MBps"] = (
            len(good) * unit_bytes / sum(walls) / 1e6)
    harness = {"compiles_in_window": built,
               "host_rss_peak_MB": platform.host_rss_peak_bytes() / 1e6}
    peak = platform.memory_peak_bytes(chips)
    if peak:
        device["memory_peak_bytes"] = peak
        harness["hbm_peak_MB"] = peak / 1e6
    out["obs"] = {"units": good, "harness": harness, "counters": {}}
    return out

"""High-water mark over the window of one of the program's gauges, as
the metrics hub keeps it (``utils/metrics.py``: the hub restarts the
marks when the window opens and hands them over when it closes):
``{"reader": "gauge_peak", "gauge": <name>}``, optionally ``"scale"``.
None where the program keeps no mark for that gauge."""


def read(spec: dict, obs: dict):
    value = obs.get("gauge_peaks", {}).get(spec["gauge"])
    if value is None:
        return None
    return value * spec.get("scale", 1)

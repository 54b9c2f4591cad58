"""Median over the window's tasks or steps of a number the driver took
for each: ``{"reader": "unit_median", "field": <name>}``."""

from statistics import median


def read(spec: dict, obs: dict):
    values = [u[spec["field"]] for u in obs["units"] if spec["field"] in u]
    return median(values) if values else None

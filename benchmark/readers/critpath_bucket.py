"""Median per task of one host stage's critical seconds (the wall
partition of ``trace/critpath.py`` over the program's spans):
``{"reader": "critpath_bucket", "buckets": [<name>, ...]}`` (summed;
``idle`` is the part of the wall in which no span was open)."""

from statistics import median


def read(spec: dict, obs: dict):
    values = [sum(task[b] for b in spec["buckets"])
              for task in obs.get("critical", [])]
    return median(values) if values else None

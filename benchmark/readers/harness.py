"""A number the harness takes from the machine itself (memory peaks,
programs built in the window, the admission model against the measured
peak): ``{"reader": "harness", "field": <name>}``."""


def read(spec: dict, obs: dict):
    return obs["harness"].get(spec["field"])

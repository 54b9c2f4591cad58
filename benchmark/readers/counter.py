"""A counter of the program's metrics hub, as it grew over the window:
``{"reader": "counter", "counter": <name>, "per": "unit" | "window"}``.
``per: unit`` divides by the tasks or steps finished in the window."""


def read(spec: dict, obs: dict):
    value = obs["counters"].get(spec["counter"])
    if value is None:
        return None
    if spec.get("per", "window") == "unit":
        return value / len(obs["units"]) if obs["units"] else None
    return value

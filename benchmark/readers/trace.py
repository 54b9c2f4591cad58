"""A number of the device trace's summary (``trace/reduce.py:
summarize``): ``{"reader": "trace", "field": <name>}``, optionally
``"over": <field>`` for a ratio of two and ``"scale"`` (100 for a
share in %)."""


def read(spec: dict, obs: dict):
    summary = obs.get("trace")
    if summary is None:
        return None
    value = summary[spec["field"]]
    if "over" in spec:
        if not summary[spec["over"]]:
            return None
        value /= summary[spec["over"]]
    return value * spec.get("scale", 1)

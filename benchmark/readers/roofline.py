"""A kernel family's share of its roofline: the least time the chip
could take for one task or step — the bytes ``trace/bytes.py:<bytes>``
computes from the cell's shapes over the peak HBM bandwidth of the
peaks table — over the device-busy time per task or step, in %:
``{"reader": "roofline", "bytes": <function>}``. Bandwidth-bound by
construction: a sort does no matrix arithmetic."""

from benchmark.trace import bytes as bytes_fns


def read(spec: dict, obs: dict):
    summary = obs.get("trace")
    if summary is None or not summary["busy_per_unit_s"]:
        return None
    least_bytes = getattr(bytes_fns, spec["bytes"])(obs["shapes"])
    least_s = least_bytes / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / summary["busy_per_unit_s"]

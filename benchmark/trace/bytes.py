"""The least bytes a kernel family must move for one task or step,
computed from the cell's shapes (``obs["shapes"]``, set by the driver).
Kept with the benchmark so that no later PR can move the yardstick."""

RECORD_BYTES = 104          # uint32[26]: the 100-byte record in words


def exchange_sort_min_bytes(shapes: dict) -> int:
    """One distributed sort step, per chip: every record read and
    written once before the exchange (bucketing) and once after it (the
    local sort) — 4 x the shard's bytes. Any real sort makes more
    passes; that is what the share measures."""
    return 4 * shapes["records_per_chip"] * RECORD_BYTES

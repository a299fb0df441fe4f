"""``alloc_peak_gib`` (GiB): ``memory_stats()["peak_bytes_in_use"]`` of
the fullest chip after the window — the allocator's own counter, a
cross-check of ``step_hbm_gib``. Absent where the backend reports no
memory statistics. Layer: device."""


def read(record):
    peak = record.get("memory", {}).get("alloc_peak_bytes")
    return peak / 2 ** 30 if peak else None

"""model: the largest ``memory_analysis().temp_size_in_bytes`` among the
compiled step programs the cell runs, in GB."""


def read(rec):
    t = rec.get("temp_bytes")
    return max(t.values()) / 1e9 if t else None

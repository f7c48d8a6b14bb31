"""`memory_stats()["peak_bytes_in_use"]` of the device after the DART cell's
window, before the reference runs, in GiB: the rows, their state and the
whole leaf bank (made once, at its bound)."""

def read(record: dict):
    if record.get("peak_bytes") is None:
        return None
    return record["peak_bytes"] / 2.0 ** 30

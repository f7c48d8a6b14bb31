"""`memory_stats()["peak_bytes_in_use"]` of the device after the class-wise
cell's window, before the reference runs, in GiB: the bins, the [10, N]
scores, the label row and the re-sort step's stack."""

def read(record: dict):
    if record.get("peak_bytes") is None:
        return None
    return record["peak_bytes"] / 2.0 ** 30

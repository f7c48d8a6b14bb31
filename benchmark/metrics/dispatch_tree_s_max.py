"""The slowest period's seconds per tree: the benchmark's own span around
each period (first enqueue to the device's answer), over its trees."""


def read(record: dict):
    rates = [s / n for s, n in record.get("periods", ()) if n]
    return max(rates) if rates else None

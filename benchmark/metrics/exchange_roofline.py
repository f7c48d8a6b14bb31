"""The exchange's share of its roofline: the least time one chip's links
need for the window's all-reduces (harness/planes.py: 2 (S - 1) / S of one
[F, B, 3] float32 histogram a leaf, at the rate in interconnect.json) over
the device seconds under `lgbm.hist_exchange` on the chip where they are
most, in percent.  Messages of ~0.1 MB are latency's: it reads low."""

from harness import planes


def read(record: dict):
    seconds = planes.exchange_seconds(record)
    if seconds is None or not record.get("window_trees"):
        return None
    return 100.0 * planes.exchange_least_seconds(record) / seconds

"""Backend-compile seconds before the window (JAX's monitoring events;
loads from the persistent cache count)."""


def read(record: dict):
    return record.get("setup_compile_s")

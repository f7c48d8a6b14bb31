"""Backend-compile seconds before the DART cell's window (JAX's monitoring
events; loads from the persistent cache count): three executables, the
re-sorting step, K=8 and K=7."""

def read(record: dict):
    return record.get("setup_compile_s")

"""Backend-compile seconds before the bagged cell's window (JAX's monitoring
events; loads from the persistent cache count): four executables, the
re-sort step, K=4, K=5 and the arrangement."""

def read(record: dict):
    return record.get("setup_compile_s")

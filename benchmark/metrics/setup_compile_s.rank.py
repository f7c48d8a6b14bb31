"""Backend-compile seconds before a ranking cell's window (JAX's monitoring
events; loads from the persistent cache count): the K-scan executables
hold two argsorts inside a scan inside the tree scan."""

def read(record: dict):
    return record.get("setup_compile_s")

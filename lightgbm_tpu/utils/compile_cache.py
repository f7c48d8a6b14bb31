"""Persistent XLA compilation cache.

The fused training step costs tens of seconds of XLA compilation per
(shape, config) the first time it runs.  JAX's persistent compilation
cache makes that a one-time cost per cache directory: later processes
deserialize the compiled executable instead.

Where the directory is: `JAX_COMPILATION_CACHE_DIR`, when the
environment sets it — JAX reads it itself and this module sets no
directory in code; likewise one an embedding process already set
through `jax.config`.  Otherwise `<checkout>/.jax_cache` (gitignored): a
fixed path with no pid, time or tempdir component, inside the tree a
chip run copies.  In both cases the min-compile-time and
min-entry-size thresholds are dropped so sub-second jits are cached too.

Enabled by the modules that trace jits (ops/histogram, ops/split,
ops/predict, ops/hist_pallas, objectives) before their first compile —
NOT on package import, which stays jax-free so the native task=predict
fast path (predict_fast.py) skips the JAX startup cost entirely.  Opt
out with LGBM_TPU_NO_COMPILE_CACHE=1.
"""

__jax_free__ = True

import os

from . import log

#: <checkout>/.jax_cache — this file is lightgbm_tpu/utils/compile_cache.py
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")

_enabled = False


def enable_compilation_cache() -> None:
    """Idempotently turn on JAX's persistent compilation cache (see the
    module docstring for where it lives) with every executable
    eligible.  A cache that cannot be set up costs compile time, not
    correctness: the failure is logged and training goes on."""
    global _enabled
    if _enabled or os.environ.get("LGBM_TPU_NO_COMPILE_CACHE") == "1":
        return
    _enabled = True
    import jax
    try:
        # the environment's directory, or one an embedding process
        # already set through jax.config, is left alone
        if not (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                or jax.config.jax_compilation_cache_dir):
            os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    except (OSError, AttributeError) as ex:
        log.warning("Persistent compilation cache not enabled: %s" % ex)

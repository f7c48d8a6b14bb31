"""Cross-process persistent compilation cache proof (ROADMAP claim:
"repeat shapes pay zero compile" across RUNS, not just in-process).

Two FRESH python processes train the identical tiny model with
JAX_COMPILATION_CACHE_DIR pointing at a shared temporary directory —
the way the cache is placed from outside (utils/compile_cache.py sets
no directory in code then, only drops the size/time thresholds).  The
first run populates the cache (backend compiles > 0); the second
process must lower (tracing always happens) but pay ZERO backend XLA
compiles — every executable deserializes from the persistent cache —
and produce byte-identical model text.

The in-process zero-compile test lives in test_compile_guard.py; THIS
is the cross-run half the ROADMAP claims.  tests/conftest.py disables
the persistent cache in the tier-1 process itself (hermetic compile
counts); the subprocesses opt back in deliberately.
"""

import json
import os
import subprocess
import sys

import pytest

_CHILD = r"""
import json, os, sys
sys.path.insert(0, os.environ["LGBM_TPU_REPO"])
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

import numpy as np
from lightgbm_tpu.analysis.guards import track_compiles
from lightgbm_tpu.api import Dataset, train
from lightgbm_tpu.utils.compile_cache import enable_compilation_cache

enable_compilation_cache()
assert (jax.config.jax_compilation_cache_dir
        == os.environ["JAX_COMPILATION_CACHE_DIR"]), "env dir must win"

x = np.sin(np.linspace(0.0, 1.0, 240 * 5) * 17.0).reshape(240, 5)
y = (x.sum(axis=1) > 0).astype(np.float32)
params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
          "min_sum_hessian_in_leaf": 1e-3, "num_iterations": 4,
          "verbose": 0, "iter_batch": "4"}
with track_compiles() as stats:
    booster = train(params, Dataset(x, label=y, params=params),
                    num_boost_round=4, verbose_eval=False)
    text = booster.model_to_string()
import hashlib
print(json.dumps({"lowerings": stats.compiles,
                  "cache_hits": stats.cache_hits,
                  "cache_misses": stats.cache_misses,
                  "model_sha": hashlib.sha256(
                      text.encode()).hexdigest()}))
"""


def _run_child(tmp_path, cache_dir):
    script = tmp_path / "cache_child.py"
    script.write_text(_CHILD)
    env = {k: v for k, v in os.environ.items()
           # the tier-1 parent disables the cache (conftest); children
           # opt back in with their own directory
           if k not in ("LGBM_TPU_NO_COMPILE_CACHE", "XLA_FLAGS")}
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    env["LGBM_TPU_REPO"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, ("cache child failed:\n%s\n%s"
                                  % (proc.stdout, proc.stderr))
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.slow
def test_second_fresh_process_pays_zero_cache_misses(tmp_path):
    cache_dir = tmp_path / "jax_cache"
    first = _run_child(tmp_path, cache_dir)
    assert first["cache_misses"] > 0, first     # cold: everything misses
    entries = os.listdir(str(cache_dir))
    assert entries, "first run must populate the persistent cache"

    second = _run_child(tmp_path, cache_dir)
    assert second["lowerings"] > 0, second      # tracing always happens
    assert second["cache_misses"] == 0, (
        "a fresh process of the same shape/config must deserialize "
        "every executable from the persistent cache: %r" % (second,))
    assert second["cache_hits"] > 0, second
    assert second["model_sha"] == first["model_sha"]

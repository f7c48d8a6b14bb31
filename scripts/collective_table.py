#!/usr/bin/env python
"""Per-split ICI collective-byte accounting at 8/64/256 virtual devices
(VERDICT r2 #7): compiles the data-parallel grower under hist_agg=psum,
hist_agg=scatter (owner-computes ReduceScatter protocol) and
tree_learner=voting, and sums the collective output bytes in the
OPTIMIZED HLO — the same methodology as
tests/test_parallel.py::test_scatter_halves_collective_bytes, not a
hand-derived formula.

Each device count needs its own process (the virtual CPU device count is
fixed at backend init), so the script re-execs itself per row.  Prints a
markdown table + one JSON line (counts from compiled HLO, not a device
measurement).
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

F = 28
MAX_BIN = 256
LEAVES = 63


def measure(ndev: int) -> dict:
    import jax
    # lock the backend to THIS process's forced device count BEFORE the
    # tests import below pulls in conftest (which appends its own
    # 8-device XLA flag — harmless once the backend exists)
    assert len(jax.devices()) == ndev, jax.devices()
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.mesh import ShardedGrower, make_mesh
    sys.path.insert(0, os.path.join(REPO, "tests"))
    from test_parallel import _collective_bytes

    params = SplitParams(5, 1e-3, 0.0, 0.0, 0.0)
    n = 64 * ndev
    rng = np.random.RandomState(0)
    bins_t = rng.randint(0, MAX_BIN, size=(F, n)).astype(np.uint8)
    res = {}
    for mode, kw in (("psum", dict(hist_agg="psum")),
                     ("scatter", dict(hist_agg="scatter")),
                     ("voting", dict(voting_top_k=8))):
        mesh = make_mesh(ndev)
        g = ShardedGrower(mesh, max_leaves=LEAVES, max_bin=MAX_BIN,
                          params=params, **kw)
        args = (g.shard_bins(bins_t),
                g.shard_rows(rng.randn(n), n),
                g.shard_rows(rng.rand(n) + 0.5, n),
                g.shard_rows(np.ones(n, dtype=bool), n),
                jnp.ones(F, dtype=bool))
        text = g._grow.lower(*args).compile().as_text()
        total, per_op = _collective_bytes(text)
        res[mode] = {"bytes": total, "per_op": per_op}
    # feature-parallel: per-split traffic is the candidate all-gather +
    # the owner's packed [N/8] go_right broadcast (VERDICT r3 weak #4 —
    # was a [N] i32 psum, 32x heavier)
    from lightgbm_tpu.parallel.mesh import (FEATURE_AXIS,
                                            FeatureShardedGrower)
    mesh = make_mesh(ndev, FEATURE_AXIS)
    g = FeatureShardedGrower(mesh, max_leaves=LEAVES, max_bin=MAX_BIN,
                             params=params)
    fpad = g.padded_features(F)
    bins_p = np.pad(bins_t, ((0, fpad - F), (0, 0)))
    fmask = np.pad(np.ones(F, dtype=bool), (0, fpad - F))
    args = (g.shard_bins(bins_p),
            g.shard_rows(rng.randn(n), n),
            g.shard_rows(rng.rand(n) + 0.5, n),
            g.shard_rows(np.ones(n, dtype=bool), n),
            g._put_feature_sharded(fmask))
    text = g._grow.lower(*args).compile().as_text()
    total, per_op = _collective_bytes(text)
    res["feature"] = {"bytes": total, "per_op": per_op}
    return res


def main() -> int:
    if len(sys.argv) > 1:           # child: one device count
        ndev = int(sys.argv[1])
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=%d" % ndev)
        import jax
        jax.config.update("jax_platforms", "cpu")
        print(json.dumps({"ndev": ndev, **measure(ndev)}))
        return 0

    rows = []
    for ndev in (8, 64, 256):
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), str(ndev)],
            capture_output=True, text=True, timeout=3600,
            env={k: v for k, v in os.environ.items()
                 if k not in ("XLA_FLAGS", "JAX_PLATFORMS")})
        if out.returncode != 0:
            sys.stderr.write(out.stdout + out.stderr)
            return 1
        rows.append(json.loads(out.stdout.strip().splitlines()[-1]))

    print("| devices | psum MB | scatter MB | voting MB | feature MB "
          "| scatter/psum |")
    print("|---|---|---|---|---|---|")
    for r in rows:
        p, s, v, fe = (r[m]["bytes"] / 1e6
                       for m in ("psum", "scatter", "voting", "feature"))
        print("| %d | %.2f | %.2f | %.2f | %.2f | %.2f |"
              % (r["ndev"], p, s, v, fe, s / p))
    print(json.dumps(rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())

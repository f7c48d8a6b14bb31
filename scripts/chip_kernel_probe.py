#!/usr/bin/env python3
"""Kernel x {lowered, compiled, matched XLA} table, taken on the chip.

`python scripts/chip_kernel_probe.py` (through the chip tool; one process,
fails without a TPU like chip_smoke.py).  For every Pallas histogram
kernel and accumulator mode it records, step by step, whether the kernel
lowers (Pallas -> Mosaic front end), compiles (Mosaic back end) and
matches the XLA one-hot histogram on chip_smoke.py's 65,536-row case.
Unlike chip_smoke.py this script's job is to RECORD refusals, so each
row catches its own failure and prints it; only a failing default-path
row (f32 masked / blocklist) makes the exit code non-zero.

Also: one 2-tree `hist_compact=on` training (a grow_tree mode, not a
kernel), the `hist_fused=on` config-time refusal, and the time of one
full sweep at the r05 cell's shape (1,007,616 x 28, max_bin 255) for
`hist_acc=f32` as shipped, `bf16`, and f32 with `precision=HIGHEST`
patched in — what exact-f32 products would cost, since the shipped f32
dot reaches the MXU with its operand rounded to bfloat16.

The last lines are the table, one JSON object per row, and the sweep
timings.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

# "matched" = counts exact, and max |diff| / sum|x| per bin against the
# full-precision XLA one-hot histogram within the mode's bound: f32 and
# bf16 both reach the MXU with grad/hess rounded to bfloat16 on the chip
# (chip_smoke.BF16_OPERAND_TOL); i32 quantizes them to 2**30 / N steps
# of the largest magnitude.  `vs_xla_run` is the distance to the XLA
# histogram as the program runs it (default matmul precision).
TOLERANCE = {"f32": chip_smoke.BF16_OPERAND_TOL,
             "bf16": chip_smoke.BF16_OPERAND_TOL, "i32": 1e-3}
DEFAULT_PATH = {("masked", "f32"), ("blocklist", "f32")}


def kernel_rows(c):
    """(kernel, mode, jitted fn, args, statics) for every sweep kernel."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops import hist_pallas as hp
    from lightgbm_tpu.ops.split import SplitParams

    nblocks = c.bins.shape[1] // hp.PALLAS_ROW_BLOCK
    blist = jnp.arange(nblocks, dtype=jnp.int32)
    for mode in hp.HIST_ACC_MODES:
        gh2, inv = hp.make_gh2_acc(c.grad, c.hess, mode)
        st = {"max_bin": c.max_bin, "hist_acc": mode}
        yield ("masked", mode, hp.leaf_histogram_masked,
               (c.bins, gh2, c.leaf_eff, c.target, inv), st)
        yield ("blocklist", mode, hp.leaf_histogram_blocklist,
               (c.bins, gh2, c.leaf_eff, c.target, blist,
                jnp.int32(nblocks), inv), dict(st, grid_blocks=nblocks))
        yield ("ranged", mode, hp.leaf_histogram_ranged,
               (c.bins, gh2, c.leaf_eff, c.target, jnp.int32(0),
                jnp.int32(nblocks), inv), st)
    # the fused variants (f32): expected to be refused at lowering
    params = SplitParams(100, 1e-3, 0.0, 0.0, 0.0)
    f = c.bins.shape[0]
    parent = jnp.asarray(c.xla)
    fmask = jnp.ones(f, dtype=bool)
    stats = (jnp.int32(1), jnp.float32(0.0), jnp.float32(1.0))
    st = {"max_bin": c.max_bin, "params": params}
    gh2 = hp.make_gh2(c.grad, c.hess)
    yield ("masked_fused", "f32", hp.leaf_histogram_masked_fused,
           (c.bins, gh2, c.leaf_eff, c.target, parent, fmask, stats,
            stats), st)
    yield ("blocklist_fused", "f32", hp.leaf_histogram_blocklist_fused,
           (c.bins, gh2, c.leaf_eff, c.target, blist, jnp.int32(nblocks),
            parent, fmask, stats, stats), dict(st, grid_blocks=nblocks))


def _outcome(ex: Exception) -> str:
    return ("%s: %s" % (type(ex).__name__, ex))[:300].replace("\n", " ")


def probe(c, kernel, mode, fn, args, statics) -> dict:
    row = {"kernel": kernel, "hist_acc": mode, "lowered": False,
           "compiled": False, "matched": False}
    try:
        lowered = fn.trace(*args, **statics).lower()
        row["lowered"] = True
        t0 = time.time()
        compiled = lowered.compile()
        row["compiled"] = True
        row["compile_s"] = round(time.time() - t0, 2)
        out = compiled(*args)
        hist = out[0] if isinstance(out, (tuple, list)) else out
        row["max_rel_err"] = float(
            "%.3g" % chip_smoke.kernel_error(c, hist, c.xla))
        row["vs_xla_run"] = float(
            "%.3g" % chip_smoke.kernel_error(c, hist, c.xla_default))
        row["matched"] = row["max_rel_err"] <= TOLERANCE[mode]
    except Exception as ex:   # the outcome IS the record (module docstring)
        row["error"] = _outcome(ex)
    return row


def probe_training(data: str, name: str, extra: list, trees: int) -> dict:
    """A short serial training with a non-default mode switched on."""
    from lightgbm_tpu import cli
    row = {"kernel": name, "hist_acc": "f32", "trained": False}
    try:
        t0 = time.time()
        cli.Application(
            ["task=train", "data=" + data, "device_type=tpu",
             "num_trees=%d" % trees,
             "output_model=" + os.path.join(chip_smoke.OUT,
                                            "probe_%s.txt" % name)]
            + chip_smoke.MODEL_ARGS + extra).run()
        row["trained"] = True
        row["wall_s"] = round(time.time() - t0, 2)
    except Exception as ex:   # the outcome IS the record
        row["error"] = _outcome(ex)
    return row


def sweep_timing() -> None:
    """ms per full sweep of leaf_histogram_masked, 5 x 10 calls after a
    warm-up call, block_until_ready per 10."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.ops import hist_pallas as hp

    n, f, max_bin = 123 * hp.PALLAS_ROW_BLOCK, 28, 255
    rng = np.random.RandomState(chip_smoke.SEED)
    bins = jnp.asarray(rng.randint(0, max_bin, size=(f, n)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    leaf = jnp.asarray(rng.randint(0, 2, size=n).astype(np.int32))

    def time_mode(label: str, mode: str):
        gh2, inv = hp.make_gh2_acc(grad, hess, mode)

        def sweep():
            return hp.leaf_histogram_masked(bins, gh2, leaf, jnp.int32(1),
                                            inv, max_bin=max_bin,
                                            hist_acc=mode)
        out = jax.block_until_ready(sweep())
        ms = []
        for _ in range(5):
            t0 = time.time()
            for _ in range(10):
                out = sweep()
            jax.block_until_ready(out)
            ms.append((time.time() - t0) * 100.0)
        print("sweep %-12s ms per sweep: min %.3f median %.3f max %.3f"
              % (label, min(ms), sorted(ms)[2], max(ms)), flush=True)
        return np.asarray(out)

    as_shipped = time_mode("f32", "f32")
    print("sweep bf16 bitwise equal to f32: %s"
          % np.array_equal(as_shipped, time_mode("bf16", "bf16")))
    dot = jax.lax.dot_general
    jax.lax.dot_general = lambda *a, **k: dot(
        *a, precision=jax.lax.Precision.HIGHEST, **k)
    jax.clear_caches()      # the jit key does not see the patch
    try:
        time_mode("f32 HIGHEST", "f32")
    finally:
        jax.lax.dot_general = dot
        jax.clear_caches()


def main() -> int:
    chip_smoke.device_or_exit(1)
    data = chip_smoke.make_data(2 * chip_smoke.SLICE_ROWS)
    c = chip_smoke.kernel_case(data)
    rows = [probe(c, *spec) for spec in kernel_rows(c)]
    rows.append(probe_training(data, "hist_compact_on",
                               ["hist_compact=on"], trees=2))
    rows.append(probe_training(data, "hist_fused_on",
                               ["hist_fused=on"], trees=2))
    fmt = "%-18s %-5s %-8s %-9s %-8s %s"
    print(fmt % ("kernel", "acc", "lowered", "compiled", "matched",
                 "detail"))
    for r in rows:     # a training row has run or not: "trained"
        detail = {k: v for k, v in r.items()
                  if k in ("max_rel_err", "vs_xla_run", "compile_s",
                           "wall_s")}
        print(fmt % (r["kernel"], r["hist_acc"], r.get("lowered", "-"),
                     r.get("compiled", r.get("trained")),
                     r.get("matched", "-"), r.get("error", detail)))
    for r in rows:
        print(json.dumps(r))
    sweep_timing()
    bad = [r for r in rows
           if (r["kernel"], r["hist_acc"]) in DEFAULT_PATH
           and not r.get("matched")]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

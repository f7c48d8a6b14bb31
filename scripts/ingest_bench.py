#!/usr/bin/env python
"""Out-of-core ingest benchmark: two-round (streaming) loading of a
multi-GB synthetic TSV with bounded memory.

The reference's precedent is two-round loading + PipelineReader
(dataset_loader.cpp:170-185, utils/pipeline_reader.h): stream the file
twice instead of materializing text + parsed floats.  This script
measures our equivalent at real scale and reports ONE JSON line:

  {"bytes": ..., "rows": ..., "wall_s": ..., "mb_per_s": ...,
   "max_rss_mb": ..., "import_rss_mb": ...}

Usage:
  python scripts/ingest_bench.py --mb 150          # quick
  python scripts/ingest_bench.py --gb 5            # the VERDICT-scale run
  python scripts/ingest_bench.py --mb 150 --one-round   # comparison

The synthetic file tiles a ~4 MB block of random rows (content variety
only matters for bin finding, which samples anyway); generation is
IO-bound and the file is cached in .bench_cache/ by size."""

import argparse
import json
import os
import resource
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
CACHE = os.path.join(REPO, ".bench_cache")
N_FEAT = 28

# ingest is host-only: pin the CPU platform before any backend init so
# the measurement neither takes the chip nor carries its client's RSS
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")


def ensure_file(target_bytes: int) -> str:
    os.makedirs(CACHE, exist_ok=True)
    path = os.path.join(CACHE, "ingest_%d.tsv" % target_bytes)
    if os.path.exists(path) and os.path.getsize(path) >= target_bytes:
        return path
    rng = np.random.RandomState(0)
    rows = 20000
    x = rng.randn(rows, N_FEAT).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int64)
    block = "\n".join(
        "\t".join([str(y[i])] + ["%.4f" % v for v in x[i]])
        for i in range(rows)) + "\n"
    block_b = block.encode()
    with open(path, "wb") as f:
        written = 0
        while written < target_bytes:
            f.write(block_b)
            written += len(block_b)
    return path


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mb", type=float, default=0)
    ap.add_argument("--gb", type=float, default=0)
    ap.add_argument("--one-round", action="store_true")
    ap.add_argument("--shards", default="",
                    help="out-of-core mode: ingest into this shard "
                         "directory (lightgbm_tpu/ingest) instead of "
                         "loading an in-memory Dataset")
    ap.add_argument("--budget-mb", type=int, default=0,
                    help="ingest_memory_budget_mb for --shards")
    ap.add_argument("--workers", type=int, default=1,
                    help="ingest_workers for --shards (1 = inline, "
                         "so --trace-peak sees every allocation)")
    ap.add_argument("--trace-peak", action="store_true",
                    help="tracemalloc the load and report peak_py_mb: the "
                         "loader's OWN allocation high-water (numpy buffers "
                         "register with tracemalloc), immune to the "
                         "allocator-arena / suite-load noise that makes an "
                         "OS-RSS assertion flaky.  Off by default — tracing "
                         "slows the throughput numbers.")
    args = ap.parse_args()
    target = int(args.gb * (1 << 30) + args.mb * (1 << 20)) or (150 << 20)
    path = ensure_file(target)

    import_rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import load_dataset
    import_rss = max(import_rss,
                     resource.getrusage(resource.RUSAGE_SELF).ru_maxrss)

    params = {
        "is_save_binary_file": "false",
        "use_two_round_loading": "false" if args.one_round else "true"}
    if args.shards:
        params["ingest_workers"] = str(args.workers)
        if args.budget_mb:
            params["ingest_memory_budget_mb"] = str(args.budget_mb)
    cfg = Config.from_params(params)
    if args.trace_peak:
        import tracemalloc
        tracemalloc.start()
    t0 = time.time()
    if args.shards:
        from lightgbm_tpu.ingest.writer import ingest
        rows = ingest([path], args.shards, cfg).num_rows
        mode = "ingest_shards"
    else:
        rows = load_dataset(path, cfg).num_data
        mode = "one_round" if args.one_round else "two_round"
    wall = time.time() - t0
    size = os.path.getsize(path)
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    rss = max(rss, resource.getrusage(resource.RUSAGE_CHILDREN).ru_maxrss)
    rec = {
        "bytes": size, "rows": rows,
        "wall_s": round(wall, 2),
        "mb_per_s": round(size / (1 << 20) / wall, 2),
        "max_rss_mb": round(rss / 1024, 1),
        "import_rss_mb": round(import_rss / 1024, 1),
        "mode": mode,
    }
    if args.trace_peak:
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()
        rec["peak_py_mb"] = round(peak / (1 << 20), 1)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())

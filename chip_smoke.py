#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, one chip (a chip belongs to one process: this script never
starts a child that needs JAX).  It fails — non-zero exit, no result line —
unless `jax.devices()[0].platform == "tpu"`, and any phase that raises
ends the run: nothing is caught and summarized.

Default run, at the full width of the model this repo was built around
(binary, 1,000,000 x 28, num_leaves=63, max_bin=255, min_data_in_leaf=100,
learning_rate=0.1):

  1. data    `ingest.synth.generate` writes the rows from a seed; the
             native parser must have built (g++) and loaded.
  2. train   `cli.main(["task=train", ..., "device_type=tpu"])`, 16 trees,
             every kernel/batching option at its default; training
             log-loss must fall, the model must parse with 16 multi-leaf
             trees.
  3. kernel  the compiled Pallas `leaf_histogram_masked` against the XLA
             one-hot histogram on a 65,536-row slice, on this chip:
             counts equal, grad/hess within f32 summation tolerance (and
             within the bf16 operand bound of the full-precision one).
  4. serve   `ServingServer` on a thread with the trained model: one
             256-row request (device gather descent) and one 4,096-row
             request (matmul route); response bytes must equal the host
             path's (`predict_fast`), with no circuit-breaker activity.

`--chips 4` instead trains 8 trees under `tree_learner=data
num_shards=4` and 8 trees serially in this one process, and checks the
shards sit on four distinct devices, every device's memory grew, and the
two final log-losses agree within 1e-3.

Data, model and outputs go to `.chip_smoke/` beside this file; the
compile cache goes where utils/compile_cache.py puts it
(`JAX_COMPILATION_CACHE_DIR`, else `.jax_cache/` beside this file), so a
second run in the same tree reports near-zero compile seconds.

The last line of standard output is one JSON object:
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.metadata
import json
import os
import re
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, ".chip_smoke")

# The contract's values.
ROWS = 1_000_000
SEED = 21
SLICE_ROWS = 65_536
MODEL_ARGS = ["objective=binary", "num_leaves=63", "max_bin=255",
              "min_data_in_leaf=100", "learning_rate=0.1",
              "metric=binary_logloss", "is_training_metric=true",
              # a multiple of iter_batch=auto's K=8, so metric output
              # does not shrink the scanned segments
              "metric_freq=8",
              # every run parses the text (no .bin shortcut on re-runs)
              "is_save_binary_file=false"]
LN2 = 0.6931471805599453   # log-loss of the all-zero initial score

def check(cond: bool, msg: str) -> None:
    if not cond:
        raise RuntimeError("chip_smoke: " + msg)


@contextlib.contextmanager
def phase(name: str):
    """Wall and compile accounting of one phase, the compile from the
    program's own ledger (lightgbm_tpu/utils/compile_cache.py): compile is
    set-up time, reported apart from the wall it is part of.  On a cache
    hit `compile_s` is the executable's load, the warm run's 'near-zero'."""
    from lightgbm_tpu.utils import compile_cache
    before = compile_cache.totals()
    t0 = time.time()
    yield
    after = compile_cache.totals()
    spent = {k: after[k] - before[k] for k in after}
    print("chip_smoke: phase %s %s" % (name, json.dumps(
        {"wall_s": round(time.time() - t0, 2),
         "trace_lower_s": round(spent["trace_s"] + spent["lower_s"], 2),
         "compile_s": round(spent["backend_s"] + spent["retrieval_s"], 2),
         "executables": spent["executables"],
         "cache_hits": spent["hits"], "cache_misses": spent["misses"]})),
          flush=True)


class Tee:
    """stdout pass-through that keeps a copy (the training metric lines
    are the program's log output, as a user reads them)."""

    def __init__(self, stream) -> None:
        self.stream = stream
        self.parts: list = []

    def write(self, s: str) -> int:
        self.parts.append(s)
        return self.stream.write(s)

    def flush(self) -> None:
        self.stream.flush()

    def text(self) -> str:
        return "".join(self.parts)


def device_or_exit(min_devices: int):
    """The gate: a TPU, or a non-zero exit before any work."""
    import jax
    import jaxlib
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print("chip_smoke: platform=%s device_kind=%s count=%d jax=%s "
          "jaxlib=%s libtpu=%s python=%s"
          % (dev["platform"], dev["kind"], dev["count"], jax.__version__,
             jaxlib.__version__, libtpu, sys.version.split()[0]),
          flush=True)
    if dev["platform"] != "tpu":
        sys.exit("chip_smoke: FAILED — JAX found platform=%s, not a TPU "
                 "(JAX_PLATFORMS=%r); nothing was run"
                 % (dev["platform"], os.environ.get("JAX_PLATFORMS")))
    if dev["count"] < min_devices:
        sys.exit("chip_smoke: FAILED — need %d TPU devices, found %d"
                 % (min_devices, dev["count"]))
    return dev


def make_data(rows: int) -> str:
    from lightgbm_tpu import native
    from lightgbm_tpu.ingest.synth import NUM_FEATURES, generate
    # a failed g++ build must not turn into a quiet pure-Python parse
    check(native.get_lib() is not None,
          "native library did not build/load (g++ on ingest.cpp)")
    check(NUM_FEATURES == 28, "synth width changed: %d" % NUM_FEATURES)
    os.makedirs(OUT, exist_ok=True)
    path = os.path.join(OUT, "train_%d.tsv" % rows)
    got = generate(path, rows=rows, fmt="tsv", seed=SEED)
    check(got == rows, "generated %d rows, wanted %d" % (got, rows))
    return path


def logloss_by_iter(log_text: str) -> dict:
    return {int(m.group(1)): float(m.group(2)) for m in re.finditer(
        r"Iteration: (\d+), training's : log loss : ([0-9.eE+-]+)",
        log_text)}


def check_model(path: str, trees: int) -> None:
    from lightgbm_tpu.models.tree import parse_model_text
    with open(path) as f:
        _, parsed = parse_model_text(f.read())
    check(len(parsed) == trees,
          "model holds %d trees, wanted %d" % (len(parsed), trees))
    leaves = [t.num_leaves for t in parsed]
    check(min(leaves) > 1, "a stump in the model: leaves=%r" % leaves)
    print("chip_smoke: model %d trees, leaves min=%d max=%d"
          % (trees, min(leaves), max(leaves)), flush=True)


def check_logloss(ll: dict, trees: int) -> float:
    check(sorted(ll) == list(range(8, trees + 1, 8)),
          "metric lines at iterations %r" % sorted(ll))
    seq = [LN2] + [ll[i] for i in sorted(ll)]
    check(all(b < a for a, b in zip(seq, seq[1:])),
          "training log-loss did not fall: %r" % seq)
    return seq[-1]


def train_one_chip(data: str, trees: int) -> str:
    """The normal entry point, default kernel settings."""
    from lightgbm_tpu import cli
    model = os.path.join(OUT, "model.txt")
    tee = Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        rc = cli.main(["task=train", "data=" + data,
                       "device_type=tpu", "num_trees=%d" % trees,
                       "output_model=" + model] + MODEL_ARGS)
    check(rc == 0, "cli.main returned %d" % rc)
    log_text = tee.text()
    check("hist_impl=pallas kernels=compiled" in log_text,
          "start-up line does not say the compiled Pallas path")
    final = check_logloss(logloss_by_iter(log_text), trees)
    print("chip_smoke: train log-loss %.6f -> %.6f over %d trees"
          % (LN2, final, trees), flush=True)
    check_model(model, trees)
    return model


def kernel_case(data: str):
    """Inputs and references of the histogram check: the real bins of a
    SLICE_ROWS slice, random gradients, a random 4-leaf assignment under
    an 80% bag, and for leaf 1 the XLA one-hot histogram
    (ops/histogram.py) on the same device plus the f64 host scale."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import load_dataset
    from lightgbm_tpu.ops.hist_pallas import fold_leaf_mask
    from lightgbm_tpu.ops.histogram import leaf_histogram, make_gvals

    slice_path = os.path.join(OUT, "slice_%d.tsv" % SLICE_ROWS)
    with open(data) as src, open(slice_path, "w") as dst:
        for _ in range(SLICE_ROWS):
            dst.write(src.readline())
    ds = load_dataset(slice_path, Config.from_params(
        {"max_bin": "255", "is_save_binary_file": "false"}))
    bins = np.asarray(ds.bins)
    f, n = bins.shape
    max_bin = int(ds.max_num_bin)
    check((f, n) == (28, SLICE_ROWS) and bins.dtype == np.uint8,
          "slice bins %r %s" % (bins.shape, bins.dtype))

    rng = np.random.RandomState(SEED)
    grad = rng.randn(n).astype(np.float32)
    hess = (0.05 + rng.rand(n)).astype(np.float32)
    leaf_id = rng.randint(0, 4, size=n).astype(np.int32)
    in_bag = rng.rand(n) < 0.8
    target = 1
    sel = (leaf_id == target) & in_bag

    c = types.SimpleNamespace(
        bins=jnp.asarray(bins), grad=jnp.asarray(grad),
        hess=jnp.asarray(hess), target=jnp.int32(target), max_bin=max_bin,
        leaf_eff=fold_leaf_mask(jnp.asarray(leaf_id), jnp.asarray(in_bag)),
        selected=int(sel.sum()))
    gv = make_gvals(c.grad, c.hess, jnp.asarray(sel), jnp.float32)
    # the one-hot einsum as the program's hist_impl=xla runs it (the
    # backend's default matmul precision), and at full f32 precision
    c.xla_default = np.asarray(leaf_histogram(c.bins, gv, max_bin=max_bin))
    with jax.default_matmul_precision("highest"):
        c.xla = np.asarray(leaf_histogram(c.bins, gv, max_bin=max_bin))
    # per-bin sum of |x| in f64 on the host: the scale that f32
    # summation error is relative to (its count plane is the exact count)
    w = np.stack([np.abs(grad) * sel, hess * sel, sel]).astype(np.float64)
    c.scale = np.stack([np.stack([
        np.bincount(bins[j], weights=w[k], minlength=max_bin)[:max_bin]
        for k in range(3)], axis=-1) for j in range(f)])
    return c


def kernel_error(c, hist, ref) -> float:
    """Counts must equal the XLA histogram's and the host's exactly;
    returns max over bins of |grad/hess diff| / sum|x| against `ref`."""
    import numpy as np
    hist = np.asarray(hist)
    check(hist.shape == ref.shape == c.scale.shape,
          "histogram shapes %r %r" % (hist.shape, ref.shape))
    check(np.array_equal(hist[..., 2], c.xla[..., 2])
          and np.array_equal(hist[..., 2], c.scale[..., 2])
          and int(hist[0, :, 2].sum()) == c.selected,
          "histogram counts differ (kernel vs xla vs host)")
    return float(np.max(np.abs(hist - ref)[..., :2]
                        / (c.scale[..., :2] + 1e-30)))


# On the chip the MXU takes f32 operands at the backend's default matmul
# precision: grad/hess are rounded to bfloat16 (relative error <= 2**-8)
# before the exact one-hot product, in the Pallas kernels AND in the XLA
# one-hot path alike (measured, PR 21 — PERF.md Findings).  So the
# kernel must agree with the XLA histogram the program would run to f32
# summation error, and with the full-precision one to the operand bound.
F32_SUM_TOL = 1e-5
BF16_OPERAND_TOL = 2.0 ** -8 + F32_SUM_TOL


def check_kernel(data: str) -> None:
    """Compiled Pallas histogram == XLA one-hot histogram, same chip.
    A new Mosaic can accept a kernel and lay it out differently, so
    compiling is not enough."""
    from lightgbm_tpu.ops.hist_pallas import (leaf_histogram_masked,
                                              make_gh2)
    c = kernel_case(data)
    pal = leaf_histogram_masked(
        c.bins, make_gh2(c.grad, c.hess), c.leaf_eff, c.target,
        max_bin=c.max_bin, interpret=False)
    err_run = kernel_error(c, pal, c.xla_default)
    err_full = kernel_error(c, pal, c.xla)
    print("chip_smoke: kernel leaf_histogram_masked: counts equal; max "
          "|diff|/sum|x| vs the XLA one-hot histogram as the program "
          "runs it = %.3g, vs the same at precision=highest = %.3g "
          "(XLA default vs highest: %.3g)"
          % (err_run, err_full, kernel_error(c, c.xla_default, c.xla)),
          flush=True)
    check(err_run < F32_SUM_TOL,
          "pallas vs XLA histogram: %.3g of sum|x|" % err_run)
    check(err_full <= BF16_OPERAND_TOL,
          "pallas vs full-precision histogram: %.3g of sum|x|, beyond "
          "the bf16 operand bound" % err_full)


def serve_leg(model: str, data: str) -> None:
    import numpy as np
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.parser import parse_predict_rows
    from lightgbm_tpu.predict_fast import try_fast_predict
    from lightgbm_tpu.serving.server import ServingServer

    cfg = Config.from_params({
        "task": "serve", "input_model": model, "serve_port": "0",
        "device_type": "tpu"})
    server = ServingServer(cfg)
    forest = server.state.forest
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        check(forest.engine == "jax", "engine is %s" % forest.engine)
        for nrows, want_mm in ((256, False), (4096, True)):
            check(forest.matmul_routed(nrows) == want_mm,
                  "%d rows: matmul_routed != %s" % (nrows, want_mm))
            with open(data) as f:
                lines = [f.readline() for _ in range(nrows)]
            req_path = os.path.join(OUT, "serve_%d.tsv" % nrows)
            with open(req_path, "w") as f:
                f.writelines(lines)
            want_path = os.path.join(OUT, "serve_%d.want" % nrows)
            check(try_fast_predict(Config.from_params({
                "task": "predict", "data": req_path, "input_model": model,
                "output_result": want_path})), "predict_fast declined")
            with open(want_path, "rb") as f:
                want = f.read()
            feats, _ = parse_predict_rows(
                [ln.rstrip("\n") for ln in lines], forest.label_idx,
                forest.max_feature_idx + 1)
            body = json.dumps({"rows": np.asarray(feats).tolist()}).encode()
            req = urllib.request.Request(
                server.url + "/predict", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.time()
            with urllib.request.urlopen(req, timeout=300) as r:
                status, got = r.status, r.read()
            check(status == 200, "HTTP %d" % status)
            check(got == want,
                  "%d-row response differs from the host path (%d vs %d "
                  "bytes)" % (nrows, len(got), len(want)))
            print("chip_smoke: served %d rows via %s in %.3f s, bytes "
                  "equal predict_fast's"
                  % (nrows, "matmul" if want_mm else "descent",
                     time.time() - t0), flush=True)
        fails = server.state.metrics.dispatch_failures_total
        check(fails == 0 and not forest.degraded
              and not forest.matmul_disabled,
              "device path unhealthy: dispatch_failures=%d degraded=%s "
              "matmul_disabled=%s"
              % (fails, forest.degraded, forest.matmul_disabled))
    finally:
        server.shutdown()
        thread.join(10)
    check(not thread.is_alive(), "serve thread did not stop")


def bytes_in_use(dev) -> int:
    return dev.memory_stats()["bytes_in_use"]


def train_four_chips(data: str, shards: int, trees: int) -> None:
    """Data-parallel and serial training of the same rows in ONE
    process; Application is driven directly (cli.main is exactly
    Application(argv).run() plus an error report) so the booster's
    device state can be inspected afterwards."""
    import jax
    from lightgbm_tpu import cli

    def train(tag: str, extra: list):
        model = os.path.join(OUT, "model_%s.txt" % tag)
        app = cli.Application(
            ["task=train", "data=" + data, "device_type=tpu",
             "num_trees=%d" % trees, "output_model=" + model]
            + MODEL_ARGS + extra)
        tee = Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            app.run()
        final = check_logloss(logloss_by_iter(tee.text()), trees)
        check_model(model, trees)
        return app, final

    devs = jax.devices()[:shards]
    before = [bytes_in_use(d) for d in devs]
    app, sharded = train("data%d" % shards,
                         ["tree_learner=data", "num_shards=%d" % shards])
    on = {s.device for s in app.boosting.bins_dev.addressable_shards}
    check(len(on) == shards and on == set(devs),
          "bins_dev shards sit on %r" % sorted(d.id for d in on))
    after = [bytes_in_use(d) for d in devs]
    check(all(a > b for a, b in zip(after, before)),
          "bytes_in_use did not grow on every device: %r -> %r"
          % (before, after))
    print("chip_smoke: %d shards on devices %r; bytes_in_use grew by %r"
          % (shards, sorted(d.id for d in on),
             [a - b for a, b in zip(after, before)]), flush=True)
    del app
    _, serial = train("serial", [])
    print("chip_smoke: log-loss after %d trees: serial %.6f, %d-shard "
          "%.6f" % (trees, serial, shards, sharded), flush=True)
    check(abs(serial - sharded) <= 1e-3,
          "log-loss differs: serial %.6f vs %d-shard %.6f"
          % (serial, shards, sharded))


def run(chips: int, dev: dict) -> None:
    from lightgbm_tpu.utils import compile_cache
    compile_cache.enable_compilation_cache()    # the ledger, from here on
    t_all = time.time()
    with phase("data"):
        data = make_data(ROWS)
    if chips > 1:
        with phase("train_%dchip" % chips):
            train_four_chips(data, chips, trees=8)
    else:
        with phase("train"):
            model = train_one_chip(data, trees=16)
        with phase("kernel"):
            check_kernel(data)
        with phase("serve"):
            serve_leg(model, data)
    print("chip_smoke: wall %.1f s; %s" % (time.time() - t_all,
                                           compile_cache.startup_line()),
          flush=True)
    print("chip_smoke: compile ledger %s" % json.dumps(
        {k: round(v, 2) for k, v in compile_cache.totals().items()}),
          flush=True)
    print(json.dumps({"ok": True, "device": dev}), flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: the data-parallel leg on a four-chip host")
    args = ap.parse_args()
    run(args.chips, device_or_exit(args.chips))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Native (C++) ingest parity: the ctypes-loaded parser/binner must agree
exactly with the pure-Python fallbacks on the reference example files and
on synthetic edge cases (na/nan tokens, CRLF, short rows, libsvm gaps)."""

import os

import numpy as np
import pytest

from lightgbm_tpu import native
from lightgbm_tpu.io import parser as pyparser


pytestmark = pytest.mark.skipif(native.get_lib() is None,
                                reason="native toolchain unavailable")


def read_lines(path):
    with open(path) as f:
        return [ln for ln in f.read().splitlines() if ln.strip()]


@pytest.mark.parametrize("example,fname", [
    ("binary_classification", "binary.train"),
    ("regression", "regression.test"),
    ("lambdarank", "rank.test"),
])
def test_native_matches_python_on_examples(examples, example, fname):
    lines = read_lines(os.path.join(examples, example, fname))
    fmt = pyparser.detect_format(lines)
    nat = pyparser._native_parse(lines, 0, fmt)
    assert nat is not None, "native parse declined"
    if fmt == "libsvm":
        py_label, py_feats = pyparser.parse_libsvm(lines, 0)
    else:
        py_label, py_feats = pyparser.parse_dense(
            lines, "\t" if fmt == "tsv" else ",", 0)
    np.testing.assert_array_equal(nat[0], py_label)
    np.testing.assert_array_equal(nat[1], py_feats)


def test_native_dense_token_edge_cases():
    lines = ["1.5,na,3", "nan,2.25,-inf", "0,null,1e3", "2,,7"]
    nat = pyparser._native_parse(lines, 0, "csv")
    assert nat is not None
    label, feats = nat
    np.testing.assert_array_equal(label, [1.5, 0.0, 0.0, 2.0])
    np.testing.assert_array_equal(
        feats, [[0.0, 3.0], [2.25, -1e308], [0.0, 1e3], [0.0, 7.0]])


def test_native_dense_short_rows():
    lines = ["1\t2\t3", "4\t5"]
    nat = pyparser._native_parse(lines, 0, "tsv")
    label, feats = nat
    np.testing.assert_array_equal(label, [1.0, 4.0])
    np.testing.assert_array_equal(feats, [[2.0, 3.0], [5.0, 0.0]])


def test_native_libsvm_gaps_and_malformed():
    lines = ["1 0:1.5 3:2.5", "0 1:7", "-1 2:0.5 junk 4:1"]
    nat = pyparser._native_parse(lines, 0, "libsvm")
    label, feats = nat
    np.testing.assert_array_equal(label, [1.0, 0.0, -1.0])
    assert feats.shape == (3, 5)
    np.testing.assert_array_equal(
        feats, [[1.5, 0, 0, 2.5, 0], [0, 7, 0, 0, 0], [0, 0, 0.5, 0, 1]])


def test_native_bin_values_matches_searchsorted():
    rng = np.random.RandomState(0)
    bounds = np.sort(rng.randn(63))
    bounds = np.concatenate([bounds, [np.inf]])
    vals = np.concatenate([rng.randn(10_000) * 2, bounds[:-1],  # exact hits
                           [-1e30, 1e30]])
    got = native.bin_values(vals, bounds)
    assert got is not None and got.dtype == np.uint8
    want = np.searchsorted(bounds, vals, side="left")
    np.testing.assert_array_equal(got, want)


def test_env_kill_switch(monkeypatch):
    import importlib
    monkeypatch.setenv("LGBM_TPU_NO_NATIVE", "1")
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_lib", None)
    assert native.get_lib() is None
    monkeypatch.setattr(native, "_tried", False)  # restore for later tests


def test_native_rejects_numeric_prefixed_garbage():
    """'2.5abc' must be a fatal parse error, matching _clean_token."""
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError):
        pyparser._native_parse(["1,2.5abc,3"], 0, "csv")
    with pytest.raises(LightGBMError):
        pyparser.parse_dense(["1,2.5abc,3"], ",", 0)  # python fallback too


def test_python_fallback_short_rows_zero_filled():
    label, feats = pyparser.parse_dense(["1,na,3", "4,5"], ",", 0)
    np.testing.assert_array_equal(label, [1.0, 4.0])
    np.testing.assert_array_equal(feats, [[0.0, 3.0], [5.0, 0.0]])


def test_header_skips_leading_blank_lines(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import load_dataset
    rng = np.random.RandomState(0)
    body = "\n".join("%d,%f,%f" % (i % 2, rng.randn(), rng.randn())
                     for i in range(50))
    f = tmp_path / "h.csv"
    f.write_text("\nlabel,f0,f1\n" + body + "\n")
    cfg = Config.from_params({"header": "true", "label_column": "name:label",
                              "is_save_binary_file": "false"})
    ds = load_dataset(str(f), cfg)
    assert ds.num_data == 50
    assert ds.feature_names == ["label", "f0", "f1"]


def test_native_lambdarank_matches_python_fallback():
    """Native reference-order gradients vs the vectorized Python path:
    same math, so agreement to fp32 tolerance on untied scores (ties are
    exactly where they legitimately differ)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import LambdarankNDCG

    rng = np.random.RandomState(0)
    n, nq = 200, 10
    qb = np.sort(rng.choice(np.arange(1, n), nq - 1, replace=False))
    qb = np.concatenate([[0], qb, [n]]).astype(np.int32)
    label = rng.randint(0, 4, size=n).astype(np.float32)
    score = rng.randn(n).astype(np.float32)  # untied with prob 1

    cfg = Config.from_params({"objective": "lambdarank",
                              "rank_impl": "native"})
    obj = LambdarankNDCG(cfg)
    obj.init(Metadata(label=label, query_boundaries=qb), n)
    obj.pad_to(n)

    lam_n, hes_n = (np.asarray(a) for a in obj.get_gradients(score))
    os.environ["LGBM_TPU_NO_NATIVE"] = "1"
    try:
        # reset the module cache so the kill switch takes effect
        native._lib, native._tried = None, False
        assert native.lambdarank_grads(
            score, label, qb, obj.inverse_max_dcgs, obj.label_gain,
            obj.discount, obj.sigmoid_table, obj.min_in, obj.max_in,
            obj.idx_factor, None, n) is None
        lam_p, hes_p = (np.asarray(a) for a in obj.get_gradients(score))
    finally:
        del os.environ["LGBM_TPU_NO_NATIVE"]
        native._lib, native._tried = None, False
    np.testing.assert_allclose(lam_n, lam_p, rtol=2e-5, atol=1e-7)
    np.testing.assert_allclose(hes_n, hes_p, rtol=2e-5, atol=1e-7)


def test_device_lambdarank_matches_fallback():
    """Default device (jnp) lambdarank gradients vs the vectorized numpy
    fallback: same math over padded query blocks, so fp32-tolerance
    agreement on untied scores, weighted and unweighted."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import LambdarankNDCG

    rng = np.random.RandomState(3)
    n, nq = 400, 17
    qb = np.sort(rng.choice(np.arange(1, n), nq - 1, replace=False))
    qb = np.concatenate([[0], qb, [n]]).astype(np.int32)
    label = rng.randint(0, 4, size=n).astype(np.float32)
    score = rng.randn(n).astype(np.float32)
    w = rng.rand(n).astype(np.float32)
    n_pad = 512
    pad_score = np.concatenate([score, np.zeros(n_pad - n, np.float32)])

    os.environ["LGBM_TPU_NO_NATIVE"] = "1"
    try:
        native._lib, native._tried = None, False
        for weights in (None, w):
            md = Metadata(label=label, query_boundaries=qb, weights=weights)
            dev = LambdarankNDCG(Config.from_params(
                {"objective": "lambdarank"}))
            dev.init(md, n)
            dev.pad_to(n_pad)
            assert dev.jax_traceable and dev.fused_key() is not None
            fal = LambdarankNDCG(Config.from_params(
                {"objective": "lambdarank", "rank_impl": "native"}))
            fal.init(md, n)
            fal.pad_to(n_pad)
            ld, hd = (np.asarray(a) for a in dev.get_gradients(pad_score))
            lf, hf = (np.asarray(a) for a in fal.get_gradients(pad_score))
            # the device path computes the sigmoid exactly; the fallback
            # keeps the reference's quantized 1M-entry table (~2.5e-5
            # input resolution), so agreement is to table precision
            np.testing.assert_allclose(ld, lf, rtol=2e-3, atol=2e-4)
            np.testing.assert_allclose(hd, hf, rtol=2e-3, atol=2e-4)
    finally:
        del os.environ["LGBM_TPU_NO_NATIVE"]
        native._lib, native._tried = None, False


def test_native_ndcg_matches_python_fallback():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metrics import NDCGMetric

    rng = np.random.RandomState(1)
    n, nq = 300, 12
    qb = np.sort(rng.choice(np.arange(1, n), nq - 1, replace=False))
    qb = np.concatenate([[0], qb, [n]]).astype(np.int32)
    label = rng.randint(0, 4, size=n).astype(np.float32)
    score = rng.randn(n)

    cfg = Config.from_params({"metric": "ndcg", "ndcg_eval_at": "1,3,5"})
    m = NDCGMetric(cfg)
    md = Metadata(label=label, query_boundaries=qb)
    md.finish_queries()
    m.init("t", md, n)
    vals_native = m.eval(score)
    os.environ["LGBM_TPU_NO_NATIVE"] = "1"
    try:
        native._lib, native._tried = None, False
        vals_py = m.eval(score)
    finally:
        del os.environ["LGBM_TPU_NO_NATIVE"]
        native._lib, native._tried = None, False
    np.testing.assert_allclose(vals_native, vals_py, rtol=1e-5)


def test_rank_label_out_of_range_is_fatal():
    """Negative / oversized ranking labels must fail fast in Python before
    reaching the native kernels (which index label_gain unchecked)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metrics import NDCGMetric
    from lightgbm_tpu.objectives import LambdarankNDCG
    from lightgbm_tpu.utils.log import LightGBMError

    qb = np.array([0, 3], dtype=np.int32)
    for bad in (np.array([-1.0, 0, 1]), np.array([0.0, 1, 99])):
        md = Metadata(label=bad.astype(np.float32), query_boundaries=qb)
        md.finish_queries()
        obj = LambdarankNDCG(Config.from_params({"objective": "lambdarank"}))
        with pytest.raises(LightGBMError):
            obj.init(md, 3)
        m = NDCGMetric(Config.from_params({"metric": "ndcg"}))
        with pytest.raises(LightGBMError):
            m.init("t", md, 3)


def test_ndcg_all_negative_query_unweighted_quirk():
    """All-negative queries add 1.0 regardless of query weight in BOTH the
    native and Python paths (rank_metric.hpp:120-123 quirk)."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.metrics import NDCGMetric

    qb = np.array([0, 2, 4], dtype=np.int32)
    label = np.array([0, 0, 2, 1], dtype=np.float32)  # query 0 all-negative
    weights = np.array([3.0, 1.0, 1.0, 1.0], dtype=np.float32)
    score = np.array([0.5, 0.1, 0.9, 0.2])
    md = Metadata(label=label, query_boundaries=qb, weights=weights)
    md.finish_queries()
    m = NDCGMetric(Config.from_params({"metric": "ndcg",
                                       "ndcg_eval_at": "2"}))
    m.init("t", md, 4)
    got_native = m.eval(score)
    os.environ["LGBM_TPU_NO_NATIVE"] = "1"
    try:
        native._lib, native._tried = None, False
        got_py = m.eval(score)
    finally:
        del os.environ["LGBM_TPU_NO_NATIVE"]
        native._lib, native._tried = None, False
    np.testing.assert_allclose(got_native, got_py, rtol=1e-6)
    # query weights are per-query means of row weights -> [2, 1], sum 3.
    # query 0 (all-negative) contributes 1.0 (NOT its weight 2); query 1 is
    # perfectly ranked -> weighted 1*1.0.  (1.0 + 1.0) / 3.
    assert abs(got_native[0] - 2.0 / 3.0) < 1e-6


def test_parse_bin_dense_mt_threads_equivalent(monkeypatch):
    """The fused multithreaded parse+bin must produce identical output at
    any thread count (threads split at line boundaries; outputs land at
    prefix-summed offsets)."""
    from lightgbm_tpu import native
    from lightgbm_tpu.io.binning import find_bin
    if native.get_lib() is None:
        pytest.skip("native unavailable")
    rng = np.random.RandomState(3)
    rows = 4097
    vals = rng.randn(rows, 5)
    y = (rng.rand(rows) > 0.5).astype(int)
    text = "\n".join(
        "\t".join([str(y[i])] + ["%.5f" % v for v in vals[i]])
        for i in range(rows)).encode() + b"\n"
    mappers = [find_bin(vals[:500, j], 500, 63) for j in range(5)]
    spec = native.BinSpec(mappers)
    col_map = np.array([-2, 0, 1, 2, 3, 4], dtype=np.int32)

    outs = []
    for nt in ("1", "4"):
        # explicit LGBM_TPU_NUM_THREADS is honored exactly (no small-
        # buffer clamp), so nt=4 genuinely exercises the cross-thread
        # split + prefix-offset logic on this 4097-row chunk
        monkeypatch.setenv("LGBM_TPU_NUM_THREADS", nt)
        bins = np.zeros((5, rows), dtype=np.uint8)
        label = np.zeros(rows, dtype=np.float32)
        got = native.parse_bin_dense_chunk(text, "\t", 6, col_map, spec,
                                           None, bins, rows, rows, label,
                                           None, None)
        assert got == (rows, rows)
        outs.append((bins, label))
    np.testing.assert_array_equal(outs[0][0], outs[1][0])
    np.testing.assert_array_equal(outs[0][1], outs[1][1])
    # keep-mask path at 4 threads agrees with numpy-selected rows
    keep = (rng.rand(rows) < 0.4).astype(np.uint8)
    bins = np.zeros((5, rows), dtype=np.uint8)
    label = np.zeros(rows, dtype=np.float32)
    kk, seen = native.parse_bin_dense_chunk(text, "\t", 6, col_map, spec,
                                            keep, bins, rows, rows, label,
                                            None, None)
    assert seen == rows and kk == int(keep.sum())
    sel = np.flatnonzero(keep)
    np.testing.assert_array_equal(bins[:, :kk], outs[0][0][:, sel])
    np.testing.assert_array_equal(label[:kk], outs[0][1][sel])
    # stale row expectations fatal instead of writing out of bounds
    from lightgbm_tpu.utils.log import LightGBMError
    with pytest.raises(LightGBMError, match="changed between loading"):
        native.parse_bin_dense_chunk(text, "\t", 6, col_map, spec, None,
                                     bins, rows, rows - 1, label,
                                     None, None)
    with pytest.raises(LightGBMError, match="changed between loading"):
        native.parse_bin_dense_chunk(text, "\t", 6, col_map, spec,
                                     keep[:rows - 1], bins, rows, rows,
                                     label, None, None)


@pytest.mark.slow
def test_native_sanitizer_fuzz(tmp_path):
    """ASan+UBSan pass over every text-facing native entry point with
    mutated/malformed inputs (SURVEY.md §5 sanitizer CI; the harness is
    native/fuzz_ingest.cpp).  Skips without a toolchain."""
    import shutil
    import subprocess
    if shutil.which("g++") is None:
        pytest.skip("no g++")
    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "lightgbm_tpu", "native")
    exe = str(tmp_path / "fuzz_ingest")
    build = subprocess.run(
        ["g++", "-O1", "-g", "-std=c++17",
         "-fsanitize=address,undefined", "-fno-sanitize-recover=all",
         os.path.join(here, "fuzz_ingest.cpp"), "-o", exe, "-pthread"],
        capture_output=True, text=True, timeout=300)
    assert build.returncode == 0, build.stderr
    run = subprocess.run([exe, "2000"], capture_output=True, text=True,
                         timeout=600)
    assert run.returncode == 0, run.stdout + run.stderr
    assert "fuzz ok" in run.stdout


def test_sort_importance_fallback_stable_sort():
    """sort_importance reproduces libstdc++ introsort's tie permutation
    ONLY when built against the same libstdc++ (documented dependency);
    without native the caller's documented fallback is a stable
    descending sort — pin that contract here."""
    from lightgbm_tpu import native
    counts = np.asarray([5, 3, 5, 1, 3, 5], dtype=np.uint64)
    native_perm = native.sort_importance(counts)
    if native_perm is not None:
        # same keys descending regardless of tie order
        assert list(counts[native_perm]) == sorted(counts, reverse=True)
    # the no-native fallback path used by GBDT.feature_importance_footer:
    pairs = sorted(enumerate(counts), key=lambda p: -int(p[1]))
    assert [counts[i] for i, _ in pairs] == sorted(counts, reverse=True)

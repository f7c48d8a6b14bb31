"""Native (C++) ingest runtime, built on demand and loaded via ctypes.

The reference's IO layer is C++ (src/io/parser.*, dataset_loader.cpp); this
is its native-equivalent here: a single-pass text parser + binning kernel
compiled from ingest.cpp with the system g++ the first time it is needed.
No pybind11 in this image, so the binding is plain ctypes over an
extern "C" surface.

Set LGBM_TPU_NO_NATIVE=1 to force the pure-Python fallbacks (io/parser.py).
"""

from __future__ import annotations

__jax_free__ = True

import ctypes
import os
import subprocess
from typing import Optional, Tuple

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "ingest.cpp")
_SO = os.path.join(_HERE, "_ingest.so")
_STAMP = _SO + ".src-sha256"

_lib = None
_tried = False


def _src_digest() -> str:
    import hashlib
    with open(_SRC, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def _build(digest: str) -> bool:
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
           _SRC, "-o", _SO]
    try:
        out = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    if out.returncode != 0 or not os.path.exists(_SO):
        return False
    with open(_STAMP, "w") as f:
        f.write(digest)
    return True


def get_lib() -> Optional[ctypes.CDLL]:
    """The loaded native library, building it if stale/absent; None when
    disabled or the toolchain is unavailable (callers fall back to numpy).

    Staleness is tracked by a content hash of ingest.cpp stamped next to
    the .so (mtimes are unreliable after checkout); a load failure of an
    existing .so (wrong arch, corrupt) falls back to rebuilding once."""
    global _lib, _tried
    if _tried:
        return _lib
    _tried = True
    if os.environ.get("LGBM_TPU_NO_NATIVE"):
        return None
    try:
        digest = _src_digest()
    except OSError:
        return None
    lib = None
    try:
        stamp = ""
        if os.path.exists(_STAMP):
            with open(_STAMP) as f:
                stamp = f.read().strip()
        if os.path.exists(_SO) and stamp == digest:
            lib = ctypes.CDLL(_SO)
    except OSError:
        lib = None
    if lib is None:
        if not _build(digest):
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            return None

    i64 = ctypes.c_int64
    pi64 = ctypes.POINTER(ctypes.c_int64)
    pd = ctypes.POINTER(ctypes.c_double)
    pu8 = ctypes.POINTER(ctypes.c_uint8)
    lib.lgt_scan_dense.argtypes = [ctypes.c_char_p, i64, ctypes.c_char,
                                   pi64, pi64]
    lib.lgt_scan_dense.restype = None
    lib.lgt_parse_dense.argtypes = [ctypes.c_char_p, i64, ctypes.c_char,
                                    pd, i64, i64]
    lib.lgt_parse_dense.restype = i64
    lib.lgt_scan_libsvm.argtypes = [ctypes.c_char_p, i64, pi64, pi64]
    lib.lgt_scan_libsvm.restype = None
    lib.lgt_parse_libsvm.argtypes = [ctypes.c_char_p, i64, pd, pd, i64, i64]
    lib.lgt_parse_libsvm.restype = i64
    lib.lgt_bin_values.argtypes = [pd, i64, pd, ctypes.c_int32, pu8]
    lib.lgt_bin_values.restype = None
    lib.lgt_sort_importance.argtypes = [
        ctypes.POINTER(ctypes.c_uint64), i64, ctypes.POINTER(ctypes.c_int32)]
    lib.lgt_sort_importance.restype = None
    pf = ctypes.POINTER(ctypes.c_float)
    pi32 = ctypes.POINTER(ctypes.c_int32)
    lib.lgt_lambdarank_grads.argtypes = [
        pf, pf, pi32, i64, pf, pf, pf, pf, i64,
        ctypes.c_float, ctypes.c_float, ctypes.c_float, pf, pf, pf]
    lib.lgt_lambdarank_grads.restype = None
    lib.lgt_ndcg_eval.argtypes = [pf, pf, pi32, i64, pi32, i64, pf, i64,
                                  pf, pd]
    lib.lgt_ndcg_eval.restype = None
    lib.lgt_parse_doubles.argtypes = [ctypes.c_char_p, i64, pd, i64]
    lib.lgt_parse_doubles.restype = i64
    i32 = ctypes.c_int32
    lib.lgt_count_lines.argtypes = [ctypes.c_char_p, i64, i32]
    lib.lgt_count_lines.restype = i64
    lib.lgt_line_spans.argtypes = [ctypes.c_char_p, i64, pi64, pi64, i64]
    lib.lgt_line_spans.restype = i64
    lib.lgt_parse_bin_dense_mt.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_char, i64, pi32, pd, pi64, pi32,
        pu8, i64, pu8, i64, i64, pf, pf, pi64, i32, pi64]
    lib.lgt_parse_bin_dense_mt.restype = i64
    lib.lgt_parse_bin_libsvm_mt.argtypes = [
        ctypes.c_char_p, i64, i64, pi32, pd, pi64, pi32, pu8, i64, pu8,
        i64, pu8, i64, i64, pf, i32, pi64]
    lib.lgt_parse_bin_libsvm_mt.restype = i64
    lib.lgt_parse_dense_mt.argtypes = [ctypes.c_char_p, i64, ctypes.c_char,
                                       pd, i64, i64, i32]
    lib.lgt_parse_dense_mt.restype = i64
    lib.lgt_selection_mask.argtypes = [pd, i64, i64, pu8]
    lib.lgt_selection_mask.restype = None
    lib.lgt_mt_selection_mask.argtypes = [
        ctypes.POINTER(ctypes.c_uint32), pi64, i64, i64, pu8]
    lib.lgt_mt_selection_mask.restype = None
    lib.lgt_format_g.argtypes = [pd, i64, i64, ctypes.c_char_p]
    lib.lgt_format_g.restype = i64
    lib.lgt_predict_dense_mt.argtypes = [
        ctypes.c_char_p, i64, ctypes.c_char, i64, i64, pi32, pd, pi32,
        pi32, pd, pi64, pi64, i64, i64, ctypes.c_double, i32,
        ctypes.c_char_p, i64, i32, pi64]
    lib.lgt_predict_dense_mt.restype = i64
    lib.lgt_predict_libsvm_mt.argtypes = [
        ctypes.c_char_p, i64, i64, pi32, pd, pi32, pi32, pd, pi64, pi64,
        i64, i64, ctypes.c_double, i32, ctypes.c_char_p, i64, i32, pi64]
    lib.lgt_predict_libsvm_mt.restype = i64
    lib.lgt_lottery_new.argtypes = [i32, i64, i64, i64]
    lib.lgt_lottery_new.restype = ctypes.c_void_p
    lib.lgt_lottery_free.argtypes = [ctypes.c_void_p]
    lib.lgt_lottery_free.restype = None
    lib.lgt_lottery_chunk.argtypes = [ctypes.c_void_p, i64, pu8, pu8, pi64]
    lib.lgt_lottery_chunk.restype = None
    lib.lgt_lottery_doubles.argtypes = [ctypes.c_void_p, i64, pd]
    lib.lgt_lottery_doubles.restype = None
    _lib = lib
    return _lib


def default_threads() -> int:
    """Parse/bin thread count: LGBM_TPU_NUM_THREADS, else all cores (the
    reference's OpenMP default)."""
    v = os.environ.get("LGBM_TPU_NUM_THREADS")
    if v:
        try:
            return max(1, int(v))
        except ValueError:
            pass
    return os.cpu_count() or 1


def _dbl_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_double))


def parse_dense(text: bytes, sep: str,
                cols: Optional[int] = None) -> Optional[np.ndarray]:
    """text -> [rows, cols] f64, or None when native is unavailable.
    Thread-parallel across row blocks (the reference parses with OpenMP
    the same way, dataset_loader.cpp:715-790).  Raises on malformed
    tokens (reference Atof Log::Fatal, common.h:283-286).  `cols`
    overrides the first-row schema width (prediction parses at the
    MODEL's width, io/parser.parse_dense)."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    sc_cols = ctypes.c_int64()
    lib.lgt_scan_dense(text, len(text), sep.encode()[0],
                       ctypes.byref(rows), ctypes.byref(sc_cols))
    ncol = cols if cols is not None else sc_cols.value
    if rows.value == 0:
        return np.zeros((0, ncol or 0), dtype=np.float64)
    out = np.empty((rows.value, ncol), dtype=np.float64)
    got = lib.lgt_parse_dense_mt(text, len(text), sep.encode()[0],
                                 _dbl_ptr(out), rows.value, ncol,
                                 default_threads())
    if got < 0:
        from ..utils import log
        log.fatal("Unknown token in data file at row %d" % (-got - 1))
    return out[:got]


def count_lines(text: bytes) -> Optional[int]:
    """Non-empty line count, thread-parallel; None without native."""
    lib = get_lib()
    if lib is None:
        return None
    return lib.lgt_count_lines(text, len(text), default_threads())


def line_spans(text: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """(starts, lens) int64 arrays of the non-empty lines, or None."""
    lib = get_lib()
    if lib is None:
        return None
    cap = lib.lgt_count_lines(text, len(text), default_threads())
    starts = np.empty(cap, dtype=np.int64)
    lens = np.empty(cap, dtype=np.int64)
    pi = ctypes.POINTER(ctypes.c_int64)
    n = lib.lgt_line_spans(text, len(text), starts.ctypes.data_as(pi),
                           lens.ctypes.data_as(pi), cap)
    return starts[:n], lens[:n]


def _i32_ptr(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32))


def _u8_ptr(a):
    return (a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            if a is not None else None)


class BinSpec:
    """Flattened per-feature bin bounds for the fused parse+bin kernels
    (built once per load from the BinMapper list)."""

    def __init__(self, bin_mappers):
        bounds = [np.asarray(m.bin_upper_bound, dtype=np.float64)
                  for m in bin_mappers]
        self.offs = np.zeros(len(bounds) + 1, dtype=np.int64)
        np.cumsum([len(b) for b in bounds], out=self.offs[1:])
        self.flat = (np.concatenate(bounds) if bounds
                     else np.zeros(0, dtype=np.float64))
        self.num_bins = np.asarray([len(b) for b in bounds],
                                   dtype=np.int32)
        self.ok = bool(len(bounds) == 0
                       or (self.num_bins <= 256).all())


_OVERFLOW = -(1 << 63)


def _check_parse_rc(got: int) -> None:
    from ..utils import log
    if got == _OVERFLOW:
        log.fatal("Data file changed between loading passes "
                  "(more rows than round 1 counted)")
    if got < 0:
        log.fatal("Unknown token in data file at row %d" % (-got - 1))


def parse_bin_dense_chunk(text: bytes, sep: str, ncols: int,
                          col_map: np.ndarray, spec: "BinSpec",
                          keep: Optional[np.ndarray], bins_view: np.ndarray,
                          stride: int, out_cap: int, label_out: np.ndarray,
                          weight_out: Optional[np.ndarray],
                          qid_out: Optional[np.ndarray]):
    """Fused parse+quantize of one dense chunk straight into the
    feature-major bin matrix (col_map semantics in ingest.cpp).
    bins_view must be the [F, stride] array offset so row 0 is this
    chunk's first output slot; out_cap bounds the rows written (stale
    round-1 row counts fatal instead of writing out of bounds).
    Returns (rows_written, rows_seen) or None when native is
    unavailable / bins are not uint8."""
    lib = get_lib()
    if lib is None or not spec.ok or bins_view.dtype != np.uint8:
        return None
    seen = ctypes.c_int64()
    col_map = np.ascontiguousarray(col_map, dtype=np.int32)
    keep_arr = (np.ascontiguousarray(keep, dtype=np.uint8)
                if keep is not None else None)
    got = lib.lgt_parse_bin_dense_mt(
        text, len(text), sep.encode()[0], ncols, _i32_ptr(col_map),
        _dbl_ptr(spec.flat),
        spec.offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _i32_ptr(spec.num_bins), _u8_ptr(keep_arr),
        0 if keep_arr is None else len(keep_arr), _u8_ptr(bins_view),
        stride, out_cap,
        label_out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        (weight_out.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
         if weight_out is not None else None),
        (qid_out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))
         if qid_out is not None else None),
        default_threads(), ctypes.byref(seen))
    _check_parse_rc(got)
    return got, seen.value


def parse_bin_libsvm_chunk(text: bytes, max_idx: int, feat_map: np.ndarray,
                           spec: "BinSpec", zero_bin: np.ndarray,
                           keep: Optional[np.ndarray],
                           bins_view: np.ndarray, stride: int,
                           out_cap: int, label_out: np.ndarray):
    """Fused parse+quantize of one libsvm chunk (see ingest.cpp)."""
    lib = get_lib()
    if lib is None or not spec.ok or bins_view.dtype != np.uint8:
        return None
    seen = ctypes.c_int64()
    feat_map = np.ascontiguousarray(feat_map, dtype=np.int32)
    zero_bin = np.ascontiguousarray(zero_bin, dtype=np.uint8)
    keep_arr = (np.ascontiguousarray(keep, dtype=np.uint8)
                if keep is not None else None)
    got = lib.lgt_parse_bin_libsvm_mt(
        text, len(text), max_idx, _i32_ptr(feat_map), _dbl_ptr(spec.flat),
        spec.offs.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        _i32_ptr(spec.num_bins), _u8_ptr(zero_bin), len(zero_bin),
        _u8_ptr(keep_arr), 0 if keep_arr is None else len(keep_arr),
        _u8_ptr(bins_view), stride, out_cap,
        label_out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        default_threads(), ctypes.byref(seen))
    _check_parse_rc(got)
    return got, seen.value


def parse_doubles(text: bytes, n: int) -> Optional[np.ndarray]:
    """Whitespace-separated doubles via the reference's Atof arithmetic
    (common.h:229-247), or None when native is unavailable / a token is
    malformed.  Fast path for model-file float arrays."""
    lib = get_lib()
    if lib is None:
        return None
    out = np.empty(n, dtype=np.float64)
    got = lib.lgt_parse_doubles(text, len(text), _dbl_ptr(out), n)
    if got != n:
        return None
    return out


def parse_libsvm(text: bytes) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """text -> (label [N], feats [N, max_idx+1]) f64, or None."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    max_idx = ctypes.c_int64()
    lib.lgt_scan_libsvm(text, len(text), ctypes.byref(rows),
                        ctypes.byref(max_idx))
    n, ncols = rows.value, max_idx.value + 1
    label = np.empty(n, dtype=np.float64)
    feats = np.zeros((n, max(ncols, 0)), dtype=np.float64)
    if n:
        got = lib.lgt_parse_libsvm(text, len(text), _dbl_ptr(label),
                                   _dbl_ptr(feats), n, ncols)
        if got < 0:
            from ..utils import log
            log.fatal("Unknown token in data file at row %d" % (-got - 1))
        label, feats = label[:got], feats[:got]
    return label, feats


def lambdarank_grads(score, label, query_boundaries, inv_max_dcg, label_gain,
                     discount, sigmoid_table, min_input, max_input,
                     idx_factor, weights, n_out
                     ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Reference-order lambdarank gradients (rank_objective.hpp:76-164);
    None when the native library is unavailable."""
    lib = get_lib()
    if lib is None:
        return None

    def f32(a):
        return np.ascontiguousarray(a, dtype=np.float32)

    def fp(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    score = f32(score)
    label = f32(label)
    qb = np.ascontiguousarray(query_boundaries, dtype=np.int32)
    inv = f32(inv_max_dcg)
    gain = f32(label_gain)
    disc = f32(discount)
    table = f32(sigmoid_table)
    w = f32(weights) if weights is not None else None
    lambdas = np.zeros(n_out, dtype=np.float32)
    hessians = np.zeros(n_out, dtype=np.float32)
    lib.lgt_lambdarank_grads(
        fp(score), fp(label),
        qb.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), len(qb) - 1,
        fp(inv), fp(gain), fp(disc), fp(table), len(table),
        np.float32(min_input), np.float32(max_input), np.float32(idx_factor),
        fp(w) if w is not None else None, fp(lambdas), fp(hessians))
    return lambdas, hessians


def ndcg_eval(score, label, query_boundaries, ks, label_gain, query_weights
              ) -> Optional[np.ndarray]:
    """Sum of per-query NDCG@ks in reference fp32/sort order, or None.
    Caller divides by the query-weight sum."""
    lib = get_lib()
    if lib is None:
        return None

    def fp(a):
        return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))

    score = np.ascontiguousarray(score, dtype=np.float32)
    label = np.ascontiguousarray(label, dtype=np.float32)
    qb = np.ascontiguousarray(query_boundaries, dtype=np.int32)
    ks = np.ascontiguousarray(ks, dtype=np.int32)
    gain = np.ascontiguousarray(label_gain, dtype=np.float32)
    w = (np.ascontiguousarray(query_weights, dtype=np.float32)
         if query_weights is not None else None)
    out = np.zeros(len(ks), dtype=np.float64)
    lib.lgt_ndcg_eval(fp(score), fp(label),
                      qb.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                      len(qb) - 1,
                      ks.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                      len(ks), fp(gain), len(gain),
                      fp(w) if w is not None else None, _dbl_ptr(out))
    return out


def scan_libsvm(text: bytes) -> Optional[Tuple[int, int]]:
    """(rows, max feature index) of a libsvm buffer, or None."""
    lib = get_lib()
    if lib is None:
        return None
    rows = ctypes.c_int64()
    max_idx = ctypes.c_int64()
    lib.lgt_scan_libsvm(text, len(text), ctypes.byref(rows),
                        ctypes.byref(max_idx))
    return rows.value, max_idx.value


def format_g(vals: np.ndarray) -> Optional[bytes]:
    """[nrows, ncols] f64 -> the bytes of '\\t'-joined %g rows with
    trailing newlines (identical to Python's '%g' for finite doubles);
    None without native."""
    lib = get_lib()
    if lib is None:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    nrows, ncols = vals.shape
    buf = ctypes.create_string_buffer(int(nrows * ncols * 26 + 1))
    got = lib.lgt_format_g(_dbl_ptr(vals), nrows, ncols, buf)
    return ctypes.string_at(buf, got)


def selection_mask(draws: np.ndarray, k: int) -> Optional[np.ndarray]:
    """Selection-sampling acceptance mask over a NextDouble stream
    (reference random.h:55-67), or None without native."""
    lib = get_lib()
    if lib is None:
        return None
    draws = np.ascontiguousarray(draws, dtype=np.float64)
    mask = np.empty(len(draws), dtype=np.uint8)
    lib.lgt_selection_mask(_dbl_ptr(draws), len(draws), int(k),
                           mask.ctypes.data_as(
                               ctypes.POINTER(ctypes.c_uint8)))
    return mask.astype(bool)


def selection_walk(draws: np.ndarray, k: int) -> np.ndarray:
    """Selection-sampling acceptance mask over a pre-drawn NextDouble
    stream (reference Random::Sample, random.h:55-67: accept i when
    draw_i < (k - taken)/(n - i)) — the native kernel when available,
    else the identical IEEE walk in Python.  The single home of this
    loop; Mt19937Random and ShardLottery both replay through it."""
    mask = selection_mask(draws, k)
    if mask is not None:
        return mask
    n = len(draws)
    mask = np.zeros(n, dtype=bool)
    taken = 0
    for i in range(n):
        if draws[i] < (k - taken) / (n - i):
            mask[i] = True
            taken += 1
    return mask


def mt_selection_mask(key: np.ndarray, pos: int, n: int, k: int,
                      out: np.ndarray) -> Optional[int]:
    """selection_walk over n NextDouble draws made natively from a
    std::mt19937's raw state: `key` (uint32[624], contiguous, advanced IN
    PLACE) and `pos` (the next word to temper), as numpy's MT19937 holds
    them; the mask is written into `out` (bool[n], contiguous).  -> the
    new pos, or None without native (nothing touched)."""
    lib = get_lib()
    if lib is None:
        return None
    assert key.dtype == np.uint32 and key.flags.c_contiguous
    assert out.dtype == np.bool_ and out.flags.c_contiguous and len(out) == n
    new_pos = ctypes.c_int64(int(pos))
    lib.lgt_mt_selection_mask(
        key.ctypes.data_as(ctypes.POINTER(ctypes.c_uint32)),
        ctypes.byref(new_pos), int(n), int(k),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return int(new_pos.value)


class ShardLottery:
    """Stateful replay of the reference's multi-machine row lottery and
    (two-round) bin-sample reservoir: one seeded-mt19937
    NextInt(0, num_machines) draw per row or query decides the owning
    rank, and locally-kept rows feed the streaming reservoir with
    NextInt(0, local_count) draws on the SAME stream (reference
    DatasetLoader::LoadTextDataToMemory / SampleTextDataFromFile,
    src/io/dataset_loader.cpp:467-572 + text_reader.h:174-211).

    Uses the native lgt_lottery kernel (built by the same libstdc++ as
    the reference binary — identical downscaling/rejection behavior)
    when available, else a scalar walk on the Mt19937Random replica.

    sample_cnt < 0 disables the reservoir (the one-round path's
    ReadAndFilterLines draws the lottery only; Random::Sample then
    continues the stream via doubles()).
    """

    def __init__(self, seed: int, num_machines: int, rank: int,
                 sample_cnt: int):
        self._m = int(num_machines)
        self._rank = int(rank)
        self._sample_cnt = int(sample_cnt)
        self._lib = get_lib()
        if self._lib is not None:
            self._h = self._lib.lgt_lottery_new(
                int(seed), self._m, self._rank, self._sample_cnt)
        else:
            from ..utils.mt19937 import Mt19937Random
            self._rng = Mt19937Random(seed)
            self._local_cnt = 0
            self._filled = 0
            self._keep_cur = False

    def chunk(self, k: int, new_unit: Optional[np.ndarray] = None
              ) -> Tuple[np.ndarray, np.ndarray]:
        """Advance over k rows; new_unit[i] truthy starts a new lottery
        unit (None: every row draws).  Returns (keep bool [k],
        reservoir slot int64 [k], -1 = none); fill slots arrive in
        order, so `append if slot == len(kept) else replace` rebuilds
        the reservoir exactly."""
        k = int(k)
        if self._lib is not None:
            keep = np.empty(k, dtype=np.uint8)
            slot = np.empty(k, dtype=np.int64)
            nu = None
            if new_unit is not None:
                nu = np.ascontiguousarray(new_unit, dtype=np.uint8)
                nu = nu.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8))
            self._lib.lgt_lottery_chunk(
                self._h, k, nu,
                keep.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
                slot.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
            return keep.astype(bool), slot
        keep = np.zeros(k, dtype=bool)
        slot = np.full(k, -1, dtype=np.int64)
        if self._sample_cnt < 0 and new_unit is None:
            # lottery-only row mode: no reservoir draws interleave, so
            # the whole chunk batches into one vectorized replay
            draws = self._rng.next_ints(np.full(k, self._m, dtype=np.int64))
            keep = draws == self._rank
            self._local_cnt += int(np.count_nonzero(keep))
            if k:
                self._keep_cur = bool(keep[-1])
            return keep, slot
        start = 0
        if new_unit is None and self._sample_cnt >= 0:
            # reservoir FILL phase consumes no draws, so rows stay
            # lottery-only until a kept row would pass the fill: draw the
            # chunk vectorized, accept the prefix that stays in fill, and
            # rewind/replay for the rest (the scalar walk below).  At the
            # default bin_construct_sample_cnt this covers whole files.
            while start < k and self._filled < self._sample_cnt:
                rem = k - start
                saved = self._rng.get_state()
                draws = self._rng.next_ints(
                    np.full(rem, self._m, dtype=np.int64))
                kv = draws == self._rank
                room = self._sample_cnt - self._filled
                over = np.cumsum(kv) > room
                j = int(np.argmax(over)) if over.any() else rem
                if j < rem:
                    # row start+j needs a reservoir draw: rewind, replay
                    # only the accepted prefix (identical draws, identical
                    # rejection consumption), fall through to the walk
                    self._rng.set_state(saved)
                    if j:
                        self._rng.next_ints(
                            np.full(j, self._m, dtype=np.int64))
                kj = kv[:j]
                keep[start:start + j] = kj
                fills = np.flatnonzero(kj)
                slot[start + fills] = self._filled + np.arange(len(fills))
                self._filled += len(fills)
                self._local_cnt += len(fills)
                if j:
                    self._keep_cur = bool(kj[-1])
                start += j
                if j < rem:
                    break
        for i in range(start, k):
            if new_unit is None or new_unit[i]:
                draw = int(self._rng.next_ints([self._m])[0])
                self._keep_cur = draw == self._rank
            keep[i] = self._keep_cur
            if not self._keep_cur:
                continue
            self._local_cnt += 1
            if self._sample_cnt < 0:
                continue
            if self._filled < self._sample_cnt:
                slot[i] = self._filled
                self._filled += 1
            else:
                idx = int(self._rng.next_ints([self._local_cnt])[0])
                if idx < self._sample_cnt:
                    slot[i] = idx
        return keep, slot

    def doubles(self, n: int) -> np.ndarray:
        """n NextDouble draws continuing the same stream (the one-round
        Random::Sample replay, dataset_loader.cpp:514-526)."""
        n = int(n)
        if self._lib is not None:
            out = np.empty(n, dtype=np.float64)
            self._lib.lgt_lottery_doubles(self._h, n, _dbl_ptr(out))
            return out
        return self._rng.next_doubles(n)

    def sample(self, n: int, k: int) -> np.ndarray:
        """Random::Sample(n, k) on the continued stream (random.h:55-67):
        consumes exactly n NextDouble draws."""
        if k > n or k < 0:
            return np.zeros(0, dtype=np.int32)
        mask = selection_walk(self.doubles(n), k)
        return np.flatnonzero(mask).astype(np.int32)

    def __del__(self):
        lib = getattr(self, "_lib", None)
        if lib is not None:
            lib.lgt_lottery_free(self._h)


def sort_importance(counts: np.ndarray) -> Optional[np.ndarray]:
    """std::sort permutation of importance counts, descending by count
    with the reference's introsort tie order (gbdt.cpp:466-477); None
    when the native library is unavailable (callers fall back to a
    stable sort, which can differ on ties among >16 entries)."""
    lib = get_lib()
    if lib is None:
        return None
    counts = np.ascontiguousarray(counts, dtype=np.uint64)
    perm = np.empty(len(counts), dtype=np.int32)
    lib.lgt_sort_importance(
        counts.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)), len(counts),
        perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    return perm


def bin_values(vals: np.ndarray, bounds: np.ndarray
               ) -> Optional[np.ndarray]:
    """Binary-search binning (BinMapper::ValueToBin) -> uint8 bins."""
    lib = get_lib()
    if lib is None or len(bounds) > 256:
        return None
    vals = np.ascontiguousarray(vals, dtype=np.float64)
    bounds = np.ascontiguousarray(bounds, dtype=np.float64)
    out = np.empty(len(vals), dtype=np.uint8)
    lib.lgt_bin_values(_dbl_ptr(vals), len(vals), _dbl_ptr(bounds),
                       np.int32(len(bounds)),
                       out.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)))
    return out


class ForestSpec:
    """Flattened forest for the native predict kernels (the warm-process
    Predictor fast path, reference predictor.hpp:82-130): per-model inner
    node arrays at node_off[m], leaf values at leaf_off[m].  Models are
    the USED models in reference order i*num_class+j."""

    def __init__(self, trees, num_class: int, sigmoid: float):
        self.num_class = int(num_class)
        self.sigmoid = float(sigmoid)
        self.num_models = len(trees)
        nl = [t.num_leaves for t in trees]
        self.node_off = np.zeros(len(trees) + 1, dtype=np.int64)
        np.cumsum([max(n - 1, 0) for n in nl], out=self.node_off[1:])
        self.leaf_off = np.zeros(len(trees) + 1, dtype=np.int64)
        np.cumsum(nl, out=self.leaf_off[1:])

        def cat(key, dtype):
            arrs = [np.asarray(getattr(t, key), dtype=dtype) for t in trees]
            return (np.ascontiguousarray(np.concatenate(arrs))
                    if arrs else np.zeros(0, dtype=dtype))

        self.sf = cat("split_feature_real", np.int32)
        self.thr = cat("threshold", np.float64)
        self.lc = cat("left_child", np.int32)
        self.rc = cat("right_child", np.int32)
        self.lv = cat("leaf_value", np.float64)


def predict_chunk(text: bytes, fmt: str, sep: str, label_idx: int,
                  num_feat: int, forest: "ForestSpec", mode: int,
                  nthreads: int = 0, row0: int = 0
                  ) -> Optional[Tuple[bytes, int]]:
    """One fused parse->descend->transform->format pass over a chunk of
    prediction input (lines only, header already stripped).  mode: 0
    transformed score, 1 raw score, 2 leaf index.  row0 is the data-row
    index of the chunk's first line so parse errors report FILE rows, not
    chunk-relative ones.  Returns (formatted output bytes, rows in this
    chunk), or None when native is unavailable.  Raises via log.fatal on
    malformed tokens like every other native parse path."""
    lib = get_lib()
    if lib is None:
        return None
    if mode == 2:
        per_row = forest.num_models * 13 + 2
    else:
        per_row = forest.num_class * 27 + 2
    # output sizing without a dedicated line-count pass (the kernel's own
    # plan already counts rows): estimate rows from the average line
    # length over the chunk's first 64 KB (a single blank/short first
    # line must not inflate the estimate into a GB-scale allocation),
    # and if the guess undershoots (ragged line lengths) retry once with
    # the exact count the kernel reported
    head = text[:65536]
    avg_len = max(2, len(head) // max(head.count(b"\n"), 1))
    rows_est = len(text) // avg_len + 16
    cap = int(rows_est * per_row * 9 // 8 + 16)
    seen = ctypes.c_int64()
    pi = ctypes.POINTER(ctypes.c_int64)

    def run(cap):
        buf = ctypes.create_string_buffer(cap)
        common = (_i32_ptr(forest.sf), _dbl_ptr(forest.thr),
                  _i32_ptr(forest.lc), _i32_ptr(forest.rc),
                  _dbl_ptr(forest.lv),
                  forest.node_off.ctypes.data_as(pi),
                  forest.leaf_off.ctypes.data_as(pi),
                  forest.num_models, forest.num_class,
                  ctypes.c_double(forest.sigmoid), np.int32(mode),
                  buf, cap, nthreads or default_threads(),
                  ctypes.byref(seen))
        if fmt == "libsvm":
            got = lib.lgt_predict_libsvm_mt(text, len(text), num_feat,
                                            *common)
        else:
            got = lib.lgt_predict_dense_mt(text, len(text),
                                           sep.encode()[0], label_idx,
                                           num_feat, *common)
        return got, buf

    got, buf = run(cap)
    if got == _OVERFLOW:
        got, buf = run(int(seen.value * per_row + 16))
    if got == _OVERFLOW:  # exact-count cap exceeded: cannot happen for
        return None       # finite "%g" output — fall back to the slow path
    if got < 0:
        from ..utils import log
        log.fatal("Unknown token in data file at row %d"
                  % (row0 + (-got - 1)))
    return ctypes.string_at(buf, got), int(seen.value)

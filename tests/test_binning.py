"""Unit tests for BinMapper parity (reference src/io/bin.cpp:40-156)."""

import numpy as np

from lightgbm_tpu.io.binning import find_bin


def test_distinct_values_fast_path():
    # <= max_bin distinct values: midpoint boundaries, last = +inf
    vals = np.array([1.0, 2.0, 2.0, 3.0])
    m = find_bin(vals, total_sample_cnt=4, max_bin=255)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound[:2], [1.5, 2.5])
    assert np.isinf(m.bin_upper_bound[2])
    assert not m.is_trivial


def test_zero_insertion_between_signs():
    # negative and positive values, no zeros sampled: reference still
    # inserts a distinct 0 (bin.cpp:65-68)
    vals = np.array([-1.0, 1.0])
    m = find_bin(vals, total_sample_cnt=2, max_bin=255)
    assert m.num_bin == 3
    np.testing.assert_allclose(m.bin_upper_bound[:2], [-0.5, 0.5])


def test_zero_front_insertion_only_with_zero_cnt():
    vals = np.array([1.0, 2.0])
    m = find_bin(vals, total_sample_cnt=2, max_bin=255)
    assert m.num_bin == 2          # no zero inserted
    m2 = find_bin(vals, total_sample_cnt=5, max_bin=255)  # 3 implied zeros
    assert m2.num_bin == 3
    np.testing.assert_allclose(m2.bin_upper_bound[:2], [0.5, 1.5])


def test_trivial_feature():
    m = find_bin(np.array([]), total_sample_cnt=10, max_bin=255)
    assert m.is_trivial and m.num_bin == 1
    m = find_bin(np.full(10, 3.25), total_sample_cnt=10, max_bin=255)
    assert m.is_trivial


def test_greedy_binning_bounded():
    rng = np.random.RandomState(0)
    vals = rng.randn(10000)
    m = find_bin(vals, total_sample_cnt=10000, max_bin=255)
    assert 2 <= m.num_bin <= 255
    assert np.isinf(m.bin_upper_bound[-1])
    # boundaries strictly increasing
    b = m.bin_upper_bound
    assert (np.diff(b[:-1]) > 0).all()


def test_value_to_bin_roundtrip():
    vals = np.array([1.0, 2.0, 3.0])
    m = find_bin(vals, total_sample_cnt=3, max_bin=255)
    assert list(m.value_to_bin(np.array([0.5, 1.0, 1.6, 2.9, 100.0]))) == \
        [0, 0, 1, 2, 2]


def test_sparse_rate():
    vals = np.array([5.0])
    m = find_bin(vals, total_sample_cnt=10, max_bin=255)  # 9 zeros
    assert abs(m.sparse_rate - 0.9) < 1e-12


class TestTwoRoundLoading:
    """use_two_round_loading: the streaming loader must produce the same
    Dataset as one-round when the bin sample covers every row."""

    def _cfg(self, extra=None):
        from lightgbm_tpu.config import Config
        p = {"is_save_binary_file": "false",
             "enable_load_from_binary_file": "false"}
        p.update(extra or {})
        return Config.from_params(p)

    def test_matches_one_round_on_example(self, examples):
        import os
        from lightgbm_tpu.io.dataset import load_dataset
        path = os.path.join(examples,
                            "binary_classification/binary.train")
        one = load_dataset(path, self._cfg())
        two = load_dataset(path, self._cfg({"use_two_round_loading": "true"}))
        np.testing.assert_array_equal(one.bins, two.bins)
        np.testing.assert_array_equal(one.metadata.label, two.metadata.label)
        np.testing.assert_array_equal(one.metadata.weights,
                                      two.metadata.weights)
        assert one.num_total_features == two.num_total_features
        for a, b in zip(one.bin_mappers, two.bin_mappers):
            np.testing.assert_array_equal(a.bin_upper_bound,
                                          b.bin_upper_bound)

    def test_chunk_boundaries(self, tmp_path, monkeypatch):
        """Tiny chunks force many boundary crossings mid-line."""
        import lightgbm_tpu.io.dataset as dsmod
        from lightgbm_tpu.io.dataset import load_dataset
        rng = np.random.RandomState(0)
        n = 257
        f = tmp_path / "t.csv"
        f.write_text("\n".join(
            "%d,%f,%f,%f" % (i % 2, rng.randn(), rng.randn(), rng.randn())
            for i in range(n)) + "\n")
        one = load_dataset(str(f), self._cfg())
        orig = dsmod._stream_line_chunks
        monkeypatch.setattr(dsmod, "_stream_line_chunks",
                            lambda fobj, chunk_bytes=97: orig(fobj, 97))
        two = load_dataset(str(f), self._cfg({"use_two_round_loading":
                                              "true"}))
        np.testing.assert_array_equal(one.bins, two.bins)
        np.testing.assert_array_equal(one.metadata.label, two.metadata.label)

    def test_sharded_matches_one_round(self, tmp_path):
        from lightgbm_tpu.io.dataset import load_dataset
        rng = np.random.RandomState(1)
        n = 101
        f = tmp_path / "t.tsv"
        f.write_text("\n".join(
            "%d\t%f\t%f" % (i % 2, rng.randn(), rng.randn())
            for i in range(n)) + "\n")
        for r in range(2):
            one = load_dataset(str(f), self._cfg(), rank=r, num_shards=2)
            two = load_dataset(str(f), self._cfg(
                {"use_two_round_loading": "true"}), rank=r, num_shards=2)
            np.testing.assert_array_equal(one.metadata.label,
                                          two.metadata.label)
            np.testing.assert_array_equal(one.bins, two.bins)

    def test_subsample_binning_still_trains(self, tmp_path):
        """Sample smaller than the file: mappers differ from full-sample
        binning but training must work end to end."""
        import lightgbm_tpu as lgb
        from lightgbm_tpu.io.dataset import load_dataset
        rng = np.random.RandomState(2)
        n = 3000
        f = tmp_path / "t.csv"
        xs = rng.randn(n, 3)
        ys = (xs[:, 0] > 0).astype(int)
        f.write_text("\n".join(
            "%d,%f,%f,%f" % (ys[i], *xs[i]) for i in range(n)) + "\n")
        cfg = self._cfg({"use_two_round_loading": "true",
                         "bin_construct_sample_cnt": "500"})
        ds = load_dataset(str(f), cfg)
        assert ds.num_data == n
        assert 0 < ds.num_features <= 3

    def test_libsvm_schema_from_full_file(self, tmp_path):
        """A libsvm feature the bin sample never sees must still occupy
        its column (trivial mapper, ignored with a warning) — the schema
        comes from a whole-file scan, not the random sample."""
        from lightgbm_tpu.io.dataset import load_dataset
        rng = np.random.RandomState(3)
        n = 2000
        lines = []
        for i in range(n):
            toks = ["%d" % (i % 2), "0:%f" % rng.randn(), "1:%f" % rng.randn()]
            if i == n - 1:
                toks.append("7:1.5")   # feature 7 exists in ONE row only
            lines.append(" ".join(toks))
        f = tmp_path / "t.svm"
        f.write_text("\n".join(lines) + "\n")
        one = load_dataset(str(f), self._cfg())
        two = load_dataset(str(f), self._cfg(
            {"use_two_round_loading": "true",
             "bin_construct_sample_cnt": "100"}))
        assert two.num_total_features == one.num_total_features == 8

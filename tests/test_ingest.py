"""Out-of-core ingestion subsystem (lightgbm_tpu/ingest/).

Tier-1 core: shard bytes equal the in-memory loader's bins
bit-for-bit (the reservoir sample pass replays `_load_two_round`'s
exact mt19937 stream), shard-fed training is byte-identical to the
text path, a killed ingest resumes at the first missing shard into a
byte-identical directory, and every manifest/rank-cache staleness
class is rejected NAMING the moved keys.  The full objective x
learner parity matrix, the multi-process-worker ingest and the
SIGKILL/memory-budget proofs are slow-marked (test_ingest_scale.py
holds the budget proof)."""

import os

import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.ingest import manifest as man
from lightgbm_tpu.ingest.shards import load_sharded_dataset
from lightgbm_tpu.ingest.writer import ingest
from lightgbm_tpu.io.dataset import load_dataset
from lightgbm_tpu.resilience import faults


@pytest.fixture(autouse=True)
def _clean_fault_registry():
    faults.reset()
    yield
    faults.reset()


def _write_tsv(tmp_path, n=400, ncol=6, seed=3, name="train.tsv"):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, ncol)
    y = (x[:, 0] + 0.5 * x[:, 1] * x[:, 2] > 0).astype(int)
    p = str(tmp_path / name)
    with open(p, "w") as f:
        for i in range(n):
            f.write("%d\t" % y[i]
                    + "\t".join("%.6g" % v for v in x[i]) + "\n")
    return p


def _write_libsvm(tmp_path, n=300, ncol=6, seed=5):
    rng = np.random.RandomState(seed)
    x = rng.randn(n, ncol)
    x[rng.rand(n, ncol) < 0.3] = 0.0
    y = (x[:, 0] > 0).astype(int)
    p = str(tmp_path / "train.libsvm")
    with open(p, "w") as f:
        for i in range(n):
            toks = ["%d" % y[i]] + ["%d:%.6g" % (j, v)
                                    for j, v in enumerate(x[i]) if v]
            f.write(" ".join(toks) + "\n")
    return p


def _icfg(extra=None):
    params = {"ingest_workers": "1", "ingest_shard_rows": "96"}
    if extra:
        params.update(extra)
    return Config.from_params(params)


def _train_model(data_path, tmp_path, tag, extra=None):
    """Train via the production segment loop and return the saved
    model TEXT (the byte-parity artifact)."""
    from lightgbm_tpu.models.gbdt import NO_LIMIT, create_boosting
    from lightgbm_tpu.objectives import create_objective

    params = {"objective": "binary", "num_leaves": "7",
              "min_data_in_leaf": "5", "min_sum_hessian_in_leaf": "1",
              "metric": "", "num_iterations": "8",
              "bagging_fraction": "0.8", "bagging_freq": "2",
              "feature_fraction": "0.9", "is_save_binary_file": "false",
              "ingest_workers": "1", "ingest_shard_rows": "96"}
    if extra:
        params.update(extra)
    cfg = Config.from_params(params)
    ds = load_dataset(data_path, cfg)
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    booster = create_boosting(cfg, ds, obj)
    it = 0
    while it < cfg.num_iterations:
        fin, done = booster.train_segment(cfg.num_iterations - it)
        it += done
        if fin:
            break
    out = str(tmp_path / ("model_%s.txt" % tag))
    booster.save_model_to_file(NO_LIMIT, True, out)
    with open(out) as f:
        return f.read()


# ---------------------------------------------------------------------------
# bins parity vs the in-memory loaders
# ---------------------------------------------------------------------------

def test_ingest_matches_two_round_loader(tmp_path):
    p = _write_tsv(tmp_path)
    cfg = _icfg()
    out = str(tmp_path / "shards")
    m = ingest([p], out, cfg)
    assert m.num_shards > 2   # several shards, last one short
    ds = load_sharded_dataset(out, cfg)
    ref = load_dataset(p, Config.from_params(
        {"use_two_round_loading": "true"}))
    assert np.array_equal(ds.bins, ref.bins)
    assert np.array_equal(ds.metadata.label, ref.metadata.label)
    assert ds.feature_names == ref.feature_names
    assert ds.num_total_features == ref.num_total_features
    # the one-round loader finds the same bins at sub-sample-count n
    ref1 = load_dataset(p, Config.from_params({}))
    assert np.array_equal(ds.bins, ref1.bins)


def test_ingest_libsvm_matches_loader(tmp_path):
    p = _write_libsvm(tmp_path)
    cfg = _icfg({"ingest_shard_rows": "64"})
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    ds = load_sharded_dataset(out, cfg)
    ref = load_dataset(p, Config.from_params(
        {"use_two_round_loading": "true"}))
    assert np.array_equal(ds.bins, ref.bins)
    assert np.array_equal(ds.metadata.label, ref.metadata.label)


def test_ingest_query_and_weight_sidecars(tmp_path):
    p = _write_tsv(tmp_path, n=300)
    rs = np.random.RandomState(5)
    qc = []
    while sum(qc) < 300:
        qc.append(int(min(rs.randint(3, 12), 300 - sum(qc))))
    with open(p + ".query", "w") as f:
        f.write("\n".join(map(str, qc)) + "\n")
    with open(p + ".weight", "w") as f:
        f.write("\n".join("%.4f" % w for w in rs.rand(300)) + "\n")
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    ds = load_sharded_dataset(out, cfg)
    ref = load_dataset(p, Config.from_params(
        {"use_two_round_loading": "true"}))
    assert np.array_equal(ds.metadata.query_boundaries,
                          ref.metadata.query_boundaries)
    assert np.allclose(ds.metadata.weights, ref.metadata.weights)
    assert np.allclose(ds.metadata.query_weights,
                       ref.metadata.query_weights)


def test_rank_slices_match_text_lottery(tmp_path):
    """tree_learner=data ranks read only their manifest slice — and
    that slice IS the reference row-lottery partition the text loader
    replays (the shards compose with the same partition machinery)."""
    p = _write_tsv(tmp_path, n=700, ncol=5)
    cfg = _icfg({"ingest_shard_rows": "150"})
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    rows = []
    for r in range(2):
        sd = load_sharded_dataset(out, cfg, rank=r, num_shards=2)
        td = load_dataset(p, Config.from_params({}), rank=r,
                          num_shards=2)
        assert np.array_equal(sd.local_rows, td.local_rows)
        assert np.array_equal(sd.metadata.label, td.metadata.label)
        # NOTE bins deliberately differ: manifest bins are GLOBAL
        # (rank-count-independent), while the text mh path bins each
        # rank from its local sample — PARITY.md "ingest" row
        rows.append(sd.local_rows)
        # second load reuses the cached rank sidecar
        sd2 = load_sharded_dataset(out, cfg, rank=r, num_shards=2)
        assert np.array_equal(sd.local_rows, sd2.local_rows)
    # the rank sets PARTITION the global rows
    merged = np.sort(np.concatenate(rows))
    assert np.array_equal(merged, np.arange(700))


# ---------------------------------------------------------------------------
# shard-fed training byte parity (full matrix is slow-marked)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("learner", ["serial", "data"])
def test_shard_fed_training_byte_identical(tmp_path, learner):
    p = _write_tsv(tmp_path)
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    text_model = _train_model(p, tmp_path, "text_" + learner,
                              {"tree_learner": learner})
    shard_model = _train_model(out, tmp_path, "shard_" + learner,
                               {"tree_learner": learner})
    assert shard_model == text_model


@pytest.mark.slow
@pytest.mark.parametrize("objective,learner", [
    ("regression", "serial"), ("regression", "data"),
    ("binary", "serial"), ("binary", "data"),
    ("multiclass", "serial"), ("multiclass", "data"),
    ("lambdarank", "serial"), ("lambdarank", "data"),
])
def test_shard_fed_parity_matrix(tmp_path, objective, learner):
    """The full bit-parity gate: every objective x serial/data trains
    byte-identically from shards and from text."""
    rng = np.random.RandomState(7)
    n, ncol = 360, 6
    x = rng.randn(n, ncol)
    s = x[:, 0] + 0.5 * x[:, 1] * x[:, 2]
    extra = {"objective": objective, "tree_learner": learner}
    if objective == "multiclass":
        edges = np.quantile(s, [1 / 3, 2 / 3])
        y = np.digitize(s, edges)
        extra.update({"num_class": "3"})
    elif objective == "regression":
        y = s
    else:
        y = (s > 0).astype(int)
    p = str(tmp_path / "train.tsv")
    with open(p, "w") as f:
        for i in range(n):
            lab = "%.6g" % y[i] if objective == "regression" \
                else "%d" % y[i]
            f.write(lab + "\t"
                    + "\t".join("%.6g" % v for v in x[i]) + "\n")
    if objective == "lambdarank":
        rs = np.random.RandomState(9)
        qc = []
        while sum(qc) < n:
            qc.append(int(min(rs.randint(4, 14), n - sum(qc))))
        with open(p + ".query", "w") as f:
            f.write("\n".join(map(str, qc)) + "\n")
        # ranking labels: small non-negative grades
        with open(p, "w") as f:
            for i in range(n):
                f.write("%d\t" % int(np.clip(s[i] + 1.5, 0, 3))
                        + "\t".join("%.6g" % v for v in x[i]) + "\n")
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    a = _train_model(p, tmp_path, "text", extra)
    b = _train_model(out, tmp_path, "shard", extra)
    assert a == b


def test_feature_learner_from_shards(tmp_path):
    """tree_learner=feature from an ingest dir: the feature-sharded
    grower splits F (every rank holds all rows), so it takes the
    materializing fallback — and must TRAIN, byte-identical to the
    text path (regression: the streamed-shard path used to call a
    row-sharding method the feature grower does not have)."""
    p = _write_tsv(tmp_path)
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    a = _train_model(p, tmp_path, "feat_text",
                     {"tree_learner": "feature"})
    b = _train_model(out, tmp_path, "feat_shard",
                     {"tree_learner": "feature"})
    assert a == b


def test_mis_sized_weight_sidecar_fatals(tmp_path):
    """A .weight sidecar that does not match the row count must fatal
    (Metadata::LoadWeights' rule) — not write shards whose metas
    disagree with their weight payloads."""
    from lightgbm_tpu.utils.log import LightGBMError
    p = _write_tsv(tmp_path, n=300)
    with open(p + ".weight", "w") as f:
        f.write("\n".join("0.5" for _ in range(120)) + "\n")
    with pytest.raises(LightGBMError, match="Weights file"):
        ingest([p], str(tmp_path / "shards"), _icfg())


def test_corrupt_bins_pack_reingests(tmp_path, capsys):
    """A completed directory whose bins.npz was damaged externally is
    re-ingested with a warning naming the pack — both at ingest()
    reuse time and at load time — never a raw traceback."""
    p = _write_tsv(tmp_path, n=300)
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    pack = os.path.join(out, man.BINS_NAME)
    blob = bytearray(open(pack, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(pack, "wb") as f:    # external damage, deliberately bare
        f.write(blob)
    ds = load_sharded_dataset(out, cfg)
    outp = capsys.readouterr().out
    assert "bins.npz" in outp
    ref = load_dataset(p, Config.from_params(
        {"use_two_round_loading": "true"}))
    assert np.array_equal(ds.bins, ref.bins)


def test_ingest_then_predict_matches_text_path(tmp_path):
    """ingest -> train -> task=predict output bytes == the text-trained
    model's predictions on the same file."""
    from lightgbm_tpu import cli

    p = _write_tsv(tmp_path)
    cfg = _icfg()
    out = str(tmp_path / "shards")
    ingest([p], out, cfg)
    mt = _train_model(p, tmp_path, "ptext")
    ms = _train_model(out, tmp_path, "pshard")
    assert mt == ms
    for tag in ("ptext", "pshard"):
        rc = cli.main(["task=predict", "data=" + p,
                       "input_model=" + str(tmp_path / ("model_%s.txt"
                                                        % tag)),
                       "output_result=" + str(tmp_path / (tag + ".out"))])
        assert rc == 0
    a = (tmp_path / "ptext.out").read_bytes()
    b = (tmp_path / "pshard.out").read_bytes()
    assert a == b and len(a) > 0


# ---------------------------------------------------------------------------
# resume + fault injection
# ---------------------------------------------------------------------------

def test_fault_then_resume_is_byte_identical(tmp_path):
    """An ingest killed at the `ingest.shard_write` seam resumes at the
    first missing shard and reproduces a byte-identical shard
    directory (shard payloads, metas AND manifest)."""
    p = _write_tsv(tmp_path, n=500)
    cfg = _icfg({"ingest_shard_rows": "128"})
    clean = str(tmp_path / "clean")
    ingest([p], clean, cfg)
    out = str(tmp_path / "killed")
    faults.configure("ingest.shard_write@2=raise")
    with pytest.raises(faults.FaultInjected):
        ingest([p], out, cfg)
    assert faults.fired("ingest.shard_write") == 1
    faults.reset()
    # the kill left a valid shard prefix + plan, no manifest
    assert not os.path.exists(os.path.join(out, man.MANIFEST_NAME))
    assert os.path.exists(os.path.join(out, man.PLAN_NAME))
    ingest([p], out, cfg)
    names = sorted(n for n in os.listdir(clean)
                   if n.startswith("shard_") or n == man.MANIFEST_NAME)
    assert names == sorted(n for n in os.listdir(out)
                           if n.startswith("shard_")
                           or n == man.MANIFEST_NAME)
    for n in names:
        with open(os.path.join(clean, n), "rb") as fa, \
                open(os.path.join(out, n), "rb") as fb:
            assert fa.read() == fb.read(), n


def test_resume_revalidates_damaged_prefix(tmp_path):
    """Resume deep-verifies the shard prefix: an externally bit-flipped
    shard is re-binned, not trusted."""
    p = _write_tsv(tmp_path, n=400)
    cfg = _icfg({"ingest_shard_rows": "128"})
    out = str(tmp_path / "shards")
    m = ingest([p], out, cfg)
    # simulate a killed ingest with a damaged committed shard
    man.save_manifest(out, m, man.PLAN_NAME)
    os.remove(os.path.join(out, man.MANIFEST_NAME))
    sh1 = os.path.join(out, man.shard_name(1))
    blob = bytearray(open(sh1, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    with open(sh1, "wb") as f:    # external damage, deliberately bare
        f.write(blob)
    os.remove(os.path.join(out, man.shard_name(3)))
    ingest([p], out, cfg)
    ds = load_sharded_dataset(out, cfg)
    ref = load_dataset(p, Config.from_params(
        {"use_two_round_loading": "true"}))
    assert np.array_equal(ds.bins, ref.bins)


def test_ingest_workers_pool_matches_inline(tmp_path):
    """N parallel parse workers (multiprocessing) produce the same
    shard bytes as the inline path."""
    p = _write_tsv(tmp_path, n=600)
    a = str(tmp_path / "inline")
    b = str(tmp_path / "pooled")
    ingest([p], a, _icfg({"ingest_shard_rows": "150"}))
    ingest([p], b, _icfg({"ingest_shard_rows": "150",
                          "ingest_workers": "2",
                          # small chunks => several tasks per worker
                          "ingest_memory_budget_mb": "8"}))
    for name in sorted(os.listdir(a)):
        if name.startswith("shard_"):
            with open(os.path.join(a, name), "rb") as fa, \
                    open(os.path.join(b, name), "rb") as fb:
                assert fa.read() == fb.read(), name


def test_multi_file_source_list(tmp_path):
    """A sharded file list ingests as the concatenation, equal to the
    single-file ingest of the concatenated text."""
    p1 = _write_tsv(tmp_path, n=250, seed=3, name="part0.tsv")
    p2 = _write_tsv(tmp_path, n=230, seed=4, name="part1.tsv")
    whole = str(tmp_path / "whole.tsv")
    with open(whole, "w") as f:
        f.write(open(p1).read() + open(p2).read())
    cfg = _icfg()
    a = str(tmp_path / "parts")
    b = str(tmp_path / "whole_sh")
    ingest([p1, p2], a, cfg)
    ingest([whole], b, cfg)
    da = load_sharded_dataset(a, cfg)
    db = load_sharded_dataset(b, cfg)
    assert np.array_equal(da.bins, db.bins)
    assert np.array_equal(da.metadata.label, db.metadata.label)


# ---------------------------------------------------------------------------
# manifest validation: every staleness class names its keys
# ---------------------------------------------------------------------------

class TestManifestValidation:
    def _ingested(self, tmp_path, n=300):
        p = _write_tsv(tmp_path, n=n)
        cfg = _icfg()
        out = str(tmp_path / "shards")
        ingest([p], out, cfg)
        return p, out

    def test_source_size_change_reingests(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        with open(p, "a") as f:
            f.write("1\t" + "\t".join(["0.5"] * 6) + "\n")
        m = ingest([p], out, _icfg())
        assert m.num_rows == 301
        err = capsys.readouterr().out
        assert "Re-ingesting" in err and "size" in err

    def test_source_mtime_change_reingests(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        st = os.stat(p)
        os.utime(p, (st.st_atime, st.st_mtime + 100))
        ingest([p], out, _icfg())
        err = capsys.readouterr().out
        assert "Re-ingesting" in err and "mtime" in err

    def test_max_bin_drift_reingests(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        cfg2 = _icfg({"max_bin": "31"})
        ingest([p], out, cfg2)
        err = capsys.readouterr().out
        assert "Re-ingesting" in err and "max_bin" in err
        ds = load_sharded_dataset(out, cfg2)
        ref = load_dataset(p, Config.from_params(
            {"use_two_round_loading": "true", "max_bin": "31"}))
        assert np.array_equal(ds.bins, ref.bins)

    def test_label_spec_drift_reingests(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        ingest([p], out, _icfg({"label_column": "1"}))
        err = capsys.readouterr().out
        assert "Re-ingesting" in err and "label_column" in err

    def test_seed_drift_reingests(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        ingest([p], out, _icfg({"data_random_seed": "7"}))
        err = capsys.readouterr().out
        assert "Re-ingesting" in err and "data_random_seed" in err

    def test_load_reingests_on_config_drift(self, tmp_path, capsys):
        """load_sharded_dataset (the training entry) re-ingests a
        mismatched manifest when the sources still exist..."""
        p, out = self._ingested(tmp_path)
        cfg2 = _icfg({"max_bin": "31"})
        ds = load_sharded_dataset(out, cfg2)
        err = capsys.readouterr().out
        assert "max_bin" in err
        assert ds.max_num_bin <= 31

    def test_load_reingests_on_source_drift(self, tmp_path, capsys):
        """The TRAINING load path (not just task=ingest) must reject a
        manifest whose source file changed — stale shards must never
        feed a training run silently."""
        p, out = self._ingested(tmp_path)
        with open(p, "a") as f:
            f.write("1\t" + "\t".join(["0.5"] * 6) + "\n")
        ds = load_sharded_dataset(out, _icfg())
        assert ds.num_data == 301
        outp = capsys.readouterr().out
        assert "source drift" in outp and "size" in outp

    def test_sidecar_edit_invalidates_manifest(self, tmp_path, capsys):
        """.weight/.query sidecar values are BAKED into shard metas, so
        an edited sidecar must re-ingest like an edited data file."""
        p, out = self._ingested(tmp_path)
        os.remove(os.path.join(out, man.MANIFEST_NAME))
        # ...shards exist but manifest gone is a different case; use a
        # fresh dir with a sidecar baked in
        p2 = _write_tsv(tmp_path, n=200, name="wtrain.tsv")
        with open(p2 + ".weight", "w") as f:
            f.write("\n".join("0.5" for _ in range(200)) + "\n")
        out2 = str(tmp_path / "wshards")
        ingest([p2], out2, _icfg())
        capsys.readouterr()
        with open(p2 + ".weight", "w") as f:
            f.write("\n".join("0.75" for _ in range(200)) + "\n")
        st = os.stat(p2 + ".weight")
        os.utime(p2 + ".weight", (st.st_atime, st.st_mtime + 100))
        ingest([p2], out2, _icfg())
        outp = capsys.readouterr().out
        assert "Re-ingesting" in outp and "weight" in outp
        ds = load_sharded_dataset(out2, _icfg())
        assert np.allclose(ds.metadata.weights, 0.75)

    def test_killed_dir_routes_to_ingest_diagnostic(self, tmp_path):
        """A killed ingest (plan + shards, no manifest) given as data=
        must hit the 're-run task=ingest' diagnostic, not the text
        parser choking on a directory."""
        from lightgbm_tpu.utils.log import LightGBMError
        p = _write_tsv(tmp_path, n=300)
        out = str(tmp_path / "shards")
        faults.configure("ingest.shard_write@2=raise")
        with pytest.raises(faults.FaultInjected):
            ingest([p], out, _icfg())
        faults.reset()
        with pytest.raises(LightGBMError, match="task=ingest"):
            load_dataset(out, _icfg())

    def test_load_fatals_when_sources_gone(self, tmp_path):
        """...and refuses, naming the keys, when they do not."""
        from lightgbm_tpu.utils.log import LightGBMError
        p, out = self._ingested(tmp_path)
        os.remove(p)
        with pytest.raises(LightGBMError, match="max_bin"):
            load_sharded_dataset(out, _icfg({"max_bin": "31"}))

    def test_stale_plan_discarded(self, tmp_path, capsys):
        p, out = self._ingested(tmp_path)
        m = man.load_manifest(out)
        os.remove(os.path.join(out, man.MANIFEST_NAME))
        m.complete = False
        man.save_manifest(out, m, man.PLAN_NAME)
        with open(p, "a") as f:
            f.write("0\t" + "\t".join(["0.25"] * 6) + "\n")
        m2 = ingest([p], out, _icfg())
        assert m2.num_rows == 301
        err = capsys.readouterr().out
        assert "stale ingest plan" in err


# ---------------------------------------------------------------------------
# .bin rank-cache sidecar: source/config fingerprint staleness
# ---------------------------------------------------------------------------

class TestRankCacheFingerprint:
    def _cached(self, tmp_path, params=None):
        p = _write_tsv(tmp_path, n=300, ncol=5)
        base = {"tree_learner": "data", "is_save_binary_file": "true"}
        if params:
            base.update(params)
        cfg = Config.from_params(base)
        ds = load_dataset(p, cfg, rank=0, num_shards=2)
        cache = p + ".r0of2.bin"
        assert os.path.isfile(cache) and os.path.isfile(
            cache + ".rows.npz")
        return p, cfg, ds

    def _reload(self, p, params, capsys):
        cfg = Config.from_params(dict({"tree_learner": "data"},
                                      **params))
        ds = load_dataset(p, cfg, rank=0, num_shards=2)
        return ds, capsys.readouterr().out

    def test_cache_reused_when_unchanged(self, tmp_path, capsys):
        p, cfg, ds = self._cached(tmp_path)
        ds2, err = self._reload(p, {}, capsys)
        assert "Ignoring rank-tagged binary cache" not in err
        assert np.array_equal(ds.local_rows, ds2.local_rows)

    def test_cache_rejects_source_size_change(self, tmp_path, capsys):
        p, cfg, ds = self._cached(tmp_path)
        with open(p, "a") as f:
            f.write("1\t" + "\t".join(["0.5"] * 5) + "\n")
        ds2, err = self._reload(p, {}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "size" in err
        assert ds2.num_data != ds.num_data or \
            len(ds2.local_rows) != len(ds.local_rows) or True
        # reloaded from TEXT: rows reflect the 301-row lottery
        assert int(ds2.local_rows[-1]) <= 300

    def test_cache_rejects_mtime_change(self, tmp_path, capsys):
        p, cfg, _ = self._cached(tmp_path)
        st = os.stat(p)
        os.utime(p, (st.st_atime, st.st_mtime + 100))
        _, err = self._reload(p, {}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "mtime" in err

    def test_cache_rejects_max_bin_drift(self, tmp_path, capsys):
        p, cfg, _ = self._cached(tmp_path)
        ds2, err = self._reload(p, {"max_bin": "31"}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "max_bin" in err
        assert ds2.max_num_bin <= 31

    def test_cache_rejects_ignore_column_drift(self, tmp_path, capsys):
        p, cfg, _ = self._cached(tmp_path)
        ds2, err = self._reload(p, {"ignore_column": "1"}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "ignore_column" in err
        assert ds2.num_features == 4

    def test_cache_rejects_label_spec_drift(self, tmp_path, capsys):
        p, cfg, _ = self._cached(tmp_path)
        _, err = self._reload(p, {"label_column": "1"}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "label_column" in err

    def test_cache_rejects_seed_drift(self, tmp_path, capsys):
        p, cfg, ds = self._cached(tmp_path)
        ds2, err = self._reload(p, {"data_random_seed": "9"}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "data_random_seed" in err
        assert not np.array_equal(ds.local_rows, ds2.local_rows)

    def test_legacy_sidecar_without_fields_rejected(self, tmp_path,
                                                    capsys):
        from lightgbm_tpu.resilience.atomic import read_npz, write_npz
        p, cfg, _ = self._cached(tmp_path)
        side = p + ".r0of2.bin.rows.npz"
        with read_npz(side) as z:
            old = {k: z[k] for k in ("rows", "n_global", "seed",
                                     "query_lottery")}
        write_npz(side, old)   # strip the fingerprint fields
        _, err = self._reload(p, {}, capsys)
        assert "Ignoring rank-tagged binary cache" in err
        assert "predates" in err


@pytest.mark.slow
def test_multihost_shard_fed_two_process(tmp_path):
    """REAL 2-process multi-host run fed from ONE shard directory:
    each rank reads only its manifest slice (lottery over the global
    row order), both ranks save identical models, and the structure
    matches a single-process 8-shard run fed from the same manifest
    with the mh row order replicated."""
    import socket as socketlib
    import subprocess
    import sys

    from lightgbm_tpu.io.dataset import Dataset, Metadata
    from lightgbm_tpu.models.gbdt import create_boosting
    from lightgbm_tpu.objectives import create_objective

    p = _write_tsv(tmp_path, n=600, ncol=5, seed=0)
    sh = str(tmp_path / "shards")
    ingest([p], sh, _icfg({"ingest_shard_rows": "128"}))

    s = socketlib.socket()
    s.bind(("localhost", 0))
    port = str(s.getsockname()[1])
    s.close()
    outs = [str(tmp_path / ("model_%d.txt" % r)) for r in range(2)]
    worker = os.path.join(os.path.dirname(__file__),
                          "mh_ingest_worker.py")
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    procs = [subprocess.Popen(
        [sys.executable, worker, str(r), "2", port, sh, outs[r]],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for r in range(2)]
    logs = [pr.communicate(timeout=600)[0].decode() for pr in procs]
    for r, pr in enumerate(procs):
        assert pr.returncode == 0, "worker %d failed:\n%s" % (r,
                                                              logs[r])
    m0 = open(outs[0]).read()
    m1 = open(outs[1]).read()
    assert m0 == m1, "ranks saved different models"
    assert m0.count("Tree=") == 3

    # single-process 8-shard comparator from the SAME manifest, with
    # the mh global row order (rank 0's lottery block, then rank 1's)
    cfg = Config.from_params({
        "objective": "binary", "tree_learner": "data",
        "num_leaves": "8", "min_data_in_leaf": "5",
        "min_sum_hessian_in_leaf": "1", "hist_dtype": "float64",
        "metric": "", "is_save_binary_file": "false"})
    parts = [load_sharded_dataset(sh, cfg, rank=r, num_shards=2)
             for r in range(2)]
    bins = np.concatenate([d.bins for d in parts], axis=1)
    label = np.concatenate([d.metadata.label for d in parts])
    full = load_sharded_dataset(sh, cfg)
    ds = Dataset(bins=bins, bin_mappers=full.bin_mappers,
                 used_feature_map=full.used_feature_map,
                 real_feature_index=full.real_feature_index,
                 num_total_features=full.num_total_features,
                 feature_names=full.feature_names,
                 metadata=Metadata(label=label))
    obj = create_objective(cfg)
    obj.init(ds.metadata, ds.num_data)
    booster = create_boosting(cfg, ds, obj)
    for _ in range(3):
        booster.train_one_iter(None, None, False)
    mh_trees = m0.split("Tree=")[1:]
    for i, tree in enumerate(booster.models):
        ours = {ln.split("=")[0]: ln.split("=", 1)[1]
                for ln in tree.to_string().splitlines() if ln}
        want = {ln.split("=")[0]: ln.split("=", 1)[1]
                for ln in mh_trees[i].splitlines()[1:] if "=" in ln}
        for key in ("num_leaves", "split_feature", "threshold"):
            assert ours[key] == want[key], "tree %d %s differs" % (i,
                                                                   key)


def test_cli_task_ingest_roundtrip(tmp_path):
    """`task=ingest` end to end through the CLI, then train from the
    produced directory."""
    from lightgbm_tpu import cli

    p = _write_tsv(tmp_path)
    out = str(tmp_path / "cli_shards")
    rc = cli.main(["task=ingest", "data=" + p, "ingest_dir=" + out,
                   "ingest_workers=1", "ingest_shard_rows=96"])
    assert rc == 0
    assert os.path.isfile(os.path.join(out, man.MANIFEST_NAME))
    a = _train_model(p, tmp_path, "cli_text")
    b = _train_model(out, tmp_path, "cli_shard")
    assert a == b


# ---------------------------------------------------------------------------
# IO/compute-overlapped shard streaming (config.ingest_prefetch)
# ---------------------------------------------------------------------------

def test_config_rejects_negative_ingest_prefetch():
    from lightgbm_tpu.utils.log import LightGBMError

    with pytest.raises(LightGBMError, match="ingest_prefetch"):
        Config.from_params({"ingest_prefetch": "-1"})


@pytest.mark.usefixtures("no_leaked_threads")
def test_prefetch_windows_preserves_order_and_bytes():
    from lightgbm_tpu.ingest.shards import prefetch_windows

    rng = np.random.RandomState(0)
    src = [rng.randint(0, 255, size=(4, k)).astype(np.uint8)
           for k in (96, 96, 17)]
    want = [w.copy() for w in src]
    for depth in (0, 1, 3, 16):
        got = list(prefetch_windows(iter(src), depth))
        assert len(got) == len(want)
        for w, g in zip(want, got):
            np.testing.assert_array_equal(w, g)
            assert g.flags["C_CONTIGUOUS"]


@pytest.mark.usefixtures("no_leaked_threads")
def test_prefetch_windows_propagates_exceptions_and_aborts_clean():
    import threading

    from lightgbm_tpu.ingest.shards import prefetch_windows

    def bad():
        yield np.zeros((2, 8), np.uint8)
        raise IOError("shard vanished")

    it = prefetch_windows(bad(), 2)
    next(it)
    with pytest.raises(IOError, match="shard vanished"):
        next(it)

    # early consumer abandonment must not leave a producer thread
    # blocked on the bounded queue
    before = threading.active_count()

    def many():
        for _ in range(64):
            yield np.zeros((2, 8), np.uint8)

    it2 = prefetch_windows(many(), 1)
    next(it2)
    it2.close()
    deadline = 50
    while threading.active_count() > before and deadline:
        import time
        time.sleep(0.05)
        deadline -= 1
    assert threading.active_count() <= before, \
        "prefetch producer thread leaked after consumer close"


@pytest.mark.usefixtures("no_leaked_threads")
def test_shard_fed_training_byte_identical_with_prefetch(tmp_path):
    """The acceptance gate: shard-fed models are byte-identical to the
    in-memory text path with overlap ON (ingest_prefetch=3), and to the
    synchronous shard feed (ingest_prefetch=0) — the prefetcher may
    change timing, never bytes."""
    p = _write_tsv(tmp_path)
    out = str(tmp_path / "shards")
    ingest([p], out, Config.from_params(
        {"ingest_workers": "1", "ingest_shard_rows": "96"}))
    text = _train_model(p, tmp_path, "text")
    sync = _train_model(out, tmp_path, "sync",
                        extra={"ingest_prefetch": "0"})
    overlapped = _train_model(out, tmp_path, "pref",
                              extra={"ingest_prefetch": "3"})
    assert sync == text
    assert overlapped == text

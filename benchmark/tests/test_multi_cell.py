"""The class-wise driver end to end on the CPU at a tiny size (16,384 rows of
6 x 6 images, 7 leaves, the chip's ordered path) with K = 10 and K = 3
classes: a sound run is correct under the REAL cell's limits, the float8
control is not, and each fault planted under the timed path makes `correct`
come out false by the number named for it.  Beside them: the joint re-sort
key against the K-key sort it replaces, the one-class key against its
definition, the narrow label row against the one-hot it replaces, the new
stats of the spans, and the cell's readers.  `tests/test_multi_cell.py`
imports these, so that tier-1 runs them too."""

import glob
import json
import os
import time

import numpy as np
import pytest

import faults_multi
import multi_tiny
import run as bench_run
from harness import scopes, scopes_multi
from harness.cells import Cell


def forget_steps():
    """A fault planted in the program's module acts when a step is traced,
    and the program keeps its steps by a key that does not know of it:
    nothing traced with a fault may be reused, and nothing traced without."""
    import jax
    from lightgbm_tpu.models import gbdt
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


def _root(tmp_path_factory, classes):
    root = str(tmp_path_factory.mktemp("multi%d" % classes))
    return root, multi_tiny.make_root(root, classes)


def _run(root, name, plant=None, **kw):
    from lightgbm_tpu.models import gbdt
    cell = Cell(root, name)
    real = {f: getattr(gbdt, f) for f in faults_multi.PATCHED}
    forget_steps()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
            return cell.driver().run(cell, seed=2 ** 31 + 5, seconds=0.0,
                                     trace=False, t_process=time.time(),
                                     root=root, on_tpu=False,
                                     break_booster=plant, **kw)
    finally:
        for f, fn in real.items():
            setattr(gbdt, f, fn)
        forget_steps()


@pytest.fixture(scope="module")
def multi_root(tmp_path_factory):
    """A checkout-shaped directory that holds the tiny class-wise cell."""
    return _root(tmp_path_factory, 10)


@pytest.fixture(scope="module")
def sound_multi(multi_root):
    """The driver at K = 10 with the control computed beside it."""
    return _run(*multi_root, control=True)


@pytest.mark.parametrize("classes", [10, 3])
def test_sound_multi_run_is_correct_and_well_formed(classes, sound_multi,
                                                    tmp_path_factory):
    record = (sound_multi if classes == 10
              else _run(*_root(tmp_path_factory, classes)))
    assert record["correct"] is True, record["compared"]
    # the window is one period of two iterations whatever --seconds says
    assert record["attempted"] == 2 * classes and record["failed"] == 0
    assert record["window_tree_count"] == 2 * classes
    assert record["dispatches"] == 2
    # classes first, middle and last of each of the window's iterations
    want = sorted({0, (classes - 1) // 2, classes - 1})
    assert record["checked_trees"] == ([2 * classes + c for c in want]
                                       + [3 * classes + c for c in want])
    assert set(record["compared"]) == {"gain_loss", "leaf_update_gap",
                                       "leaf_count_gap", "score_gap",
                                       "trees_missing"}
    json.dumps({k: v for k, v in record.items() if k != "window_trees"})


def test_run_cell_prints_a_well_formed_result(multi_root):
    root, name = multi_root
    forget_steps()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
        result = bench_run.run_cell(root, name, seed=2 ** 31 + 77,
                                    seconds=0.0, trace=False,
                                    require_tpu=False)
    assert result["correct"] is True, result["compared"]
    assert set(result["metrics"]) == {"train_tree_s", "setup_s"}
    assert result["device"]["platform"] == "cpu"
    assert list(result)[-1] == "compared"
    json.dumps(result)


def test_float8_control_is_not_correct_multi(sound_multi):
    """The reference in the program's place, gradients rounded to float8
    before the histograms: its numbers, held to the cell's limits, fail, in
    the run whose own numbers pass."""
    assert sound_multi["correct"] is True, sound_multi["compared"]
    assert sound_multi["control_correct"] is False, \
        sound_multi["control_compared"]


@pytest.mark.parametrize("fault", sorted(faults_multi.FAULTS))
def test_planted_multi_fault_is_not_correct(multi_root, fault):
    record = _run(*multi_root, faults_multi.FAULTS[fault])
    assert record["correct"] is False, record["compared"]
    assert record["compared"]["trees_missing"][0] == 0.0
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    assert faults_multi.CAUGHT_BY[fault] in failing, record["compared"]


# -- the re-sort keys --------------------------------------------------------
def _sort(keys):
    """The permutation of one stable lax.sort of `keys` and an iota."""
    import jax
    import jax.numpy as jnp
    n = keys[0].shape[0]
    return np.asarray(jax.lax.sort(
        tuple(keys) + (jnp.arange(n, dtype=jnp.int32),),
        num_keys=len(keys), is_stable=True)[-1])


def _leaf_rows(classes, leaves, n=5000, seed=0):
    """[K, n] int32 leaf ids, few distinct ones a class, so that many rows
    tie on the first classes and the later ones decide."""
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, leaves, (classes, n)).astype(np.int32)
    ids[: classes // 2] %= 3
    return ids


@pytest.mark.parametrize("classes", [3, 5, 10])
def test_class_key_sorts_as_the_k_key_sort(classes):
    """At 63 leaves (6 bits) K x 6 <= 64 bits fit two words: the packed key
    and an iota give the K-key sort's permutation to the bit, in at most
    three operands."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt
    ids = jnp.asarray(_leaf_rows(classes, 63))
    key = gbdt._class_key(ids, ids.shape[1], 63)
    assert len(key) == (1 if classes <= 5 else 2)
    assert all(k.dtype == jnp.uint32 for k in key)
    want = _sort([ids[c] for c in range(classes)])
    assert np.array_equal(_sort(key), want)
    assert np.array_equal(want, np.lexsort(np.asarray(ids)[::-1]))


def test_class_key_keeps_ties_past_its_words():
    """Twelve classes at 6 bits: classes 10 and 11 fall out of the key, and
    rows the first ten tie on keep the order they had (a window shorter than
    the rows sorts its first m only)."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt
    ids = jnp.asarray(_leaf_rows(12, 63, seed=1))
    key = gbdt._class_key(ids, 4000, 63)
    assert len(key) == 2 and key[0].shape == (4000,)
    assert np.array_equal(_sort(key),
                          _sort([ids[c, :4000] for c in range(10)]))


def test_one_class_key_is_the_leaf_then_the_last_trees():
    """The single-class re-sort's key, which the class key leaves as it was:
    the tree's leaf in the high bits, then trees t-1's and t-2's, 6 bits
    each; its sort is the stable lexsort by those three."""
    import jax.numpy as jnp
    from lightgbm_tpu.models import gbdt
    ids = _leaf_rows(3, 63, seed=2)
    key = gbdt._leaf_key([jnp.asarray(i) for i in ids], 63)
    assert np.array_equal(np.asarray(key),
                          (ids[0] << 12 | ids[1] << 6 | ids[2])
                          .astype(np.uint32))
    assert np.array_equal(_sort([key]), np.lexsort(ids[::-1]))


# -- the label row -----------------------------------------------------------
def test_labels_ride_as_one_narrow_row_and_give_the_same_gradients():
    """The objective keeps ONE uint8 row of classes (padded rows: of no
    class); its gradients equal, to the bit, those of the [K, N] float
    one-hot it replaces, padded rows included."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.dataset import Metadata
    from lightgbm_tpu.objectives import MulticlassSoftmax
    k, n = 10, 3000
    rng = np.random.default_rng(4)
    y = rng.integers(0, k, n).astype(np.float32)
    obj = MulticlassSoftmax(Config.from_params({"objective": "multiclass",
                                                "num_class": str(k)}))
    obj.init(Metadata(label=y), n)
    obj.pad_to(3072)
    label, weights = obj.grad_state()
    assert label.dtype == jnp.uint8 and label.shape == (3072,)
    assert weights is None
    scores = jnp.asarray(rng.normal(0, 2, (k, 3072)).astype(np.float32))
    grad, hess = obj.make_grad_fn()(scores, obj.grad_state())
    onehot = np.zeros((k, 3072), np.float32)
    onehot[y.astype(int), np.arange(n)] = 1.0
    # the one-hot's formula, as it stood: the softmax in float64 where x64
    # is on, cast to float32
    p = jax.nn.softmax(scores.astype(jnp.float64), axis=0).astype(
        jnp.float32)
    assert np.array_equal(np.asarray(grad), np.asarray(p - onehot))
    assert np.array_equal(np.asarray(hess), np.asarray(2.0 * p * (1.0 - p)))


# -- the spans ---------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_multi(tmp_path_factory):
    """The program's host spans of 4 iterations of the tiny job at K = 10
    (two periods: two re-sorts), nested."""
    import jax
    from drivers import train_multi
    from harness.data_multi import make_rows
    cfg = multi_tiny.tiny_config()
    rows = make_rows(cfg["data"], cfg["num_data"], 255, 11)
    forget_steps()
    booster = train_multi.build_booster(cfg, rows, on_tpu=False)
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with jax.profiler.trace(trace_dir, profiler_options=options):
        train_multi.drive(booster, 4, jax.profiler.TraceAnnotation)
        booster._flush_pending()
    assert len(booster.models) == 40
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return scopes.nest(scopes.read_trace(path).host), rows.bins.shape[0]


def test_a_class_wise_dispatch_says_its_classes_and_what_moved(traced_multi):
    spans, features = traced_multi
    enq = [s.stats for s in spans if s.name == "lgbm.enqueue"]
    assert [s["kind"] for s in enq] == ["multi"] * 4
    assert all(s["classes"] == 10 for s in enq)
    sorting = [s for s in enq if "word_rows" in s]
    assert len(sorting) == 2
    # bins, bag masks, row order and the label row in the one gather; the
    # [10, N] float scores by a gather of their own
    for s in sorting:
        assert (s["carried"], s["taken"]) == (4, 1), s
        assert s["word_rows"] == -(-features // 4) + 3 + 1 + 1, s


def test_a_flush_counts_the_classes_sweeps(traced_multi):
    spans, _ = traced_multi
    flushes = [s.stats for s in spans if s.name == "lgbm.flush"]
    assert flushes and sum(s["trees"] for s in flushes) == 40
    for s in flushes:
        assert s["classes"] == 10
        assert 0 < s["class_blocks_min"] <= s["class_blocks_max"]
        assert s["class_blocks_max"] <= s["blocks_swept"]
        assert s["class_blocks_min"] * 10 <= s["blocks_swept"]


# -- the readers ---------------------------------------------------------------
def test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record():
    cell = Cell(multi_tiny.ROOT, multi_tiny.CELL)
    listed = [m["name"] for m in cell.spec["per_layer"]
              if m.get("workloads") == [multi_tiny.CELL]]
    assert len(listed) == 9
    got = cell.per_layer({"peak_bytes": 2 ** 31, "window_tree_count": 20})
    assert got == {"peak_hbm_gib.multi": {"value": 2.0, "unit": "GiB"}}


def test_multi_grouping_arithmetic(monkeypatch):
    """Every device scope of the program in exactly one group; a metric reads
    its group over the window's trees, the class key its part; the skew is
    the summed most over the summed fewest."""
    from lightgbm_tpu.utils import spans
    grouped = [s for g in scopes_multi.NAMES["device_groups"].values()
               for s in g]
    assert sorted(grouped) == sorted(spans.DEVICE_SCOPES)
    red = {"has_scopes": True,
           "device_s": {"lgbm.resort": 3.0, "lgbm.class_key": 1.0,
                        "lgbm.objective": 0.5, "lgbm.hist_sweep": 10.0,
                        "lgbm.hist_root": 2.0, "unscoped": 0.25},
           "spans_in_window": [
               scopes.Span("lgbm.flush", 0.0, 1.0,
                           {"classes": 10, "class_blocks_max": 30,
                            "class_blocks_min": 10}),
               scopes.Span("lgbm.flush", 2.0, 1.0,
                           {"classes": 10, "class_blocks_max": 30,
                            "class_blocks_min": 20})]}
    monkeypatch.setattr(scopes, "for_record", lambda record: red)
    record = {"trace": {}, "window_tree_count": 20}
    cell = Cell(multi_tiny.ROOT, multi_tiny.CELL)
    got = {k: v["value"] for k, v in cell.per_layer(record).items()}
    assert got["resort_tree_s.multi"] == 4.0 / 20
    assert got["class_key_tree_s"] == 1.0 / 20
    assert got["objective_tree_s.multi"] == 0.5 / 20
    assert got["hist_tree_s.multi"] == 12.0 / 20
    assert got["class_sweep_skew"] == 60 / 30
    # a key that XLA fused into the sort's own operation reads 0
    del red["device_s"]["lgbm.class_key"]
    assert cell.per_layer(record)["class_key_tree_s"]["value"] == 0.0

"""The DART driver end to end on the CPU at a tiny size (30,000 rows x 39
features, 15 leaves, three of ten trees dropped, the chip's ordered path): a
sound run is correct under the REAL cell's limits with upstream's drop lists
to the letter, through a bank that holds every tree and through one that
replays; the float8 control is not; and each fault planted under the timed
path makes `correct` come out false by the number named for it.
`tests/test_dart_cell.py` imports these, so that tier-1 runs them too."""

import json
import time

import pytest

import dart_tiny
import faults_dart
import run as bench_run
from harness import reference_dart
from harness.cells import Cell


def forget_steps():
    """A fault planted in the program's module acts when a step is traced,
    and the program keeps its steps by a key that does not know of it:
    nothing traced with a fault may be reused, and nothing traced without."""
    import jax
    from lightgbm_tpu.models import gbdt
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def dart_root(tmp_path_factory):
    """A checkout-shaped directory that holds the tiny DART cell."""
    root = str(tmp_path_factory.mktemp("dart"))
    name = dart_tiny.make_root(root)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
        yield root, name


@pytest.fixture(scope="module")
def sound_dart(dart_root):
    root, name = dart_root
    forget_steps()
    return bench_run.run_cell(root, name, seed=2 ** 31 + 77, seconds=0.0,
                              trace=False, require_tpu=False)


def test_sound_dart_run_is_correct_and_well_formed(sound_dart):
    sound = sound_dart
    assert sound["correct"] is True, sound["compared"]
    # the window is two periods of 6 trees whatever --seconds says
    assert sound["attempted"] == 12 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tree_s", "setup_s"}
    assert sound["device"]["platform"] == "cpu"
    # one checked tree from each executable of the last period 1 + 3 + 2
    assert sound["checked_trees"] == [12, 15, 17]
    assert set(sound["compared"]) == {"gain_loss", "leaf_update_gap",
                                      "leaf_count_gap", "score_gap",
                                      "trees_missing", "drop_gap"}
    assert sound["numbers"]["drop_gap"] == 0.0
    assert list(sound)[-1] == "compared"
    json.dumps(sound)


def _run(dart_root, plant, **kw):
    from lightgbm_tpu.models import gbdt
    root, name = dart_root
    cell = Cell(root, name)
    real = {f: getattr(gbdt, f) for f in faults_dart.PATCHED}
    forget_steps()
    try:
        return cell.driver().run(cell, seed=2 ** 31 + 5, seconds=0.0,
                                 trace=False, t_process=time.time(),
                                 root=root, on_tpu=False,
                                 break_booster=plant, **kw)
    finally:
        for f, fn in real.items():
            setattr(gbdt, f, fn)
        forget_steps()


def test_float8_control_is_not_correct_dart(dart_root):
    """The reference in the program's place, gradients rounded to float8
    before the histograms: its numbers, held to the cell's limits, fail, in
    the run whose own numbers pass; and the run dropped what upstream's
    stream drops."""
    record = _run(dart_root, None, control=True)
    assert record["correct"] is True, record["compared"]
    assert record["control_correct"] is False, record["control_compared"]
    params = dart_tiny.tiny_config()["params"]
    assert record["drops"] == [len(d) for d in
                               reference_dart.drop_lists(params, 18)]
    assert max(record["drops"]) >= 3 and record["bank_rows"] == 18


def test_a_bank_that_replays_is_correct_too(dart_root):
    """The window through a leaf bank of three trees: every later drop is
    replayed from the tree's splits, and the run is as correct."""
    record = _run(dart_root, faults_dart.SOUND["small_bank"])
    assert record["correct"] is True, record["compared"]
    assert (record["bank_rows"], record["bank_cap"]) == (3, 3)


@pytest.mark.parametrize("fault", sorted(faults_dart.FAULTS))
def test_planted_dart_fault_is_not_correct(dart_root, fault):
    record = _run(dart_root, faults_dart.FAULTS[fault])
    assert record["correct"] is False, record["compared"]
    assert record["compared"]["trees_missing"][0] == 0.0
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    assert faults_dart.CAUGHT_BY[fault] in failing, record["compared"]


def test_weights_follow_the_lists():
    """Born with 1 / (1 + k), times k / (1 + k) at every later drop."""
    w = reference_dart.weights([[], [0], [0, 1], [2]])
    assert w[0].tolist() == [0, 0, 0, 0]
    assert w[1].tolist() == [1, 0, 0, 0]
    assert w[2].tolist() == [0.5, 0.5, 0, 0]
    assert w[3].tolist() == [0.5 * 2 / 3, 0.5 * 2 / 3, 1 / 3, 0]
    assert w[4].tolist() == [0.5 * 2 / 3, 0.5 * 2 / 3, 1 / 3 * 0.5, 0.5]


def test_every_reader_of_the_cell_finds_nothing_in_an_untraced_record():
    cell = Cell(dart_tiny.ROOT, dart_tiny.CELL)
    listed = [m["name"] for m in cell.spec["per_layer"]
              if m.get("workloads") == [dart_tiny.CELL]]
    assert len(listed) == 24
    got = cell.per_layer({"peak_bytes": 2 ** 31, "setup_compile_s": 3.5,
                          "dispatches": 6, "window_tree_count": 32})
    assert got == {"peak_hbm_gib.dart": {"value": 2.0, "unit": "GiB"},
                   "setup_compile_s.dart": {"value": 3.5, "unit": "s"},
                   "trees_per_dispatch.dart": {"value": 32 / 6,
                                               "unit": "trees"}}

"""The bagged driver end to end on the CPU at a tiny size (30,000 rows x 39
features, 15 leaves, a bag of 24,000 redrawn every 3 trees, 31 features a
tree, the chip's ordered and compacted path): a sound run is correct under
the REAL cell's limits with upstream's bags and feature sets to the bit, the
float8 control is not, and each fault planted under the timed path makes
`correct` come out false by the number named for it.
`tests/test_bag_cell.py` imports these, so that tier-1 runs them too."""

import json
import time

import pytest

import bagged_tiny
import faults_bagged
import run as bench_run
from harness.cells import Cell


def forget_steps():
    """A fault planted in `grow_tree_bagged` acts when a step is traced,
    and the program keeps its steps by a key that does not know of it:
    nothing traced with a fault may be reused, and nothing traced without."""
    import jax
    from lightgbm_tpu.models import gbdt
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def bagged_root(tmp_path_factory):
    """A checkout-shaped directory that holds the tiny bagged cell."""
    root = str(tmp_path_factory.mktemp("bagged"))
    name = bagged_tiny.make_root(root)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
        yield root, name


@pytest.fixture(scope="module")
def sound(bagged_root):
    root, name = bagged_root
    forget_steps()
    return bench_run.run_cell(root, name, seed=2 ** 31 + 77, seconds=0.0,
                              trace=False, require_tpu=False)


def test_sound_bagged_run_is_correct_and_well_formed(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] == 9 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tree_s", "setup_s"}
    assert sound["device"]["platform"] == "cpu"
    # one checked tree from each executable of the period 1 + 2 + 3 + 3:
    # the re-sort step's, the K=2's, the second K=3's last
    assert sound["checked_trees"] == [9, 11, 17]
    assert set(sound["compared"]) == {"gain_loss", "leaf_update_gap",
                                      "leaf_count_gap", "score_gap",
                                      "trees_missing", "bag_gap",
                                      "feature_gap"}
    # the bags and the feature sets are upstream's, to the bit
    assert sound["numbers"]["bag_gap"] == 0.0
    assert sound["numbers"]["feature_gap"] == 0.0
    assert list(sound)[-1] == "compared"
    json.dumps(sound)


def _broken(bagged_root, plant, **kw):
    from lightgbm_tpu.models import gbdt
    root, name = bagged_root
    cell = Cell(root, name)
    grow = gbdt.grow_tree_bagged
    forget_steps()
    try:
        return cell.driver().run(cell, seed=2 ** 31 + 5, seconds=0.0,
                                 trace=False, t_process=time.time(),
                                 root=root, on_tpu=False,
                                 break_booster=plant, **kw)
    finally:
        gbdt.grow_tree_bagged = grow
        forget_steps()


def test_float8_control_is_not_correct_bagged(bagged_root):
    """The reference in the program's place, gradients rounded to float8
    before the bag's histograms: its numbers, held to the cell's limits,
    fail, in the run whose own numbers pass."""
    record = _broken(bagged_root, None, control=True)
    assert record["correct"] is True, record["compared"]
    assert record["control_correct"] is False, record["control_compared"]
    assert record["bag_epochs"] == 6        # trees 0, 3, ..., 15


@pytest.mark.parametrize("fault", sorted(faults_bagged.FAULTS))
def test_planted_sampling_fault_is_not_correct(bagged_root, fault):
    record = _broken(bagged_root, faults_bagged.FAULTS[fault])
    assert record["correct"] is False, record["compared"]
    assert record["compared"]["trees_missing"][0] == 0.0
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    assert faults_bagged.CAUGHT_BY[fault] in failing, record["compared"]

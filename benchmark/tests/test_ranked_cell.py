"""The ranked driver end to end on the CPU at a tiny size (300 queries of
1-60 documents x 12 features, 15 leaves, the chip's ordered path): a sound
run is correct under the REAL cell's limits, the float8 control is not,
and each fault planted under the timed path makes `correct` come out
false.  `tests/test_rank_cell.py` imports these, so that tier-1 runs them
too."""

import json
import time

import pytest

import faults_ranked
import ranked_tiny
import run as bench_run
from harness.cells import Cell


def forget_steps():
    """A fault planted in the objective acts when a step is traced, and
    the program keeps its steps by the objective's key: nothing traced
    with a fault may be reused, and nothing traced without it."""
    import jax
    from lightgbm_tpu.models import gbdt
    gbdt._FUSED_STEPS.clear()
    jax.clear_caches()


@pytest.fixture(scope="module")
def ranked_root(tmp_path_factory):
    """A checkout-shaped directory that holds the tiny ranking cell."""
    root = str(tmp_path_factory.mktemp("ranked"))
    name = ranked_tiny.make_root(root)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(bench_run, "keep_compile_cache", lambda root: None)
        yield root, name


@pytest.fixture(scope="module")
def sound(ranked_root):
    root, name = ranked_root
    forget_steps()
    return bench_run.run_cell(root, name, seed=2 ** 31 + 77, seconds=0.0,
                              trace=False, require_tpu=False)


def test_sound_ranked_run_is_correct_and_well_formed(sound):
    assert sound["correct"] is True, sound["compared"]
    assert sound["attempted"] == 4 and sound["failed"] == 0
    assert set(sound["metrics"]) == {"train_tree_s", "setup_s"}
    assert sound["device"]["platform"] == "cpu"
    # one checked tree from each dispatch of the period: the re-sort
    # step's, then the K=3 scan's
    assert sound["checked_trees"] == [4, 7]
    assert set(sound["compared"]) == {"gain_loss", "leaf_update_gap",
                                      "leaf_count_gap", "score_gap",
                                      "trees_missing"}
    assert list(sound)[-1] == "compared"
    json.dumps(sound)


def _broken(ranked_root, plant, **kw):
    root, name = ranked_root
    cell = Cell(root, name)
    forget_steps()
    try:
        return cell.driver().run(cell, seed=2 ** 31 + 5, seconds=0.0,
                                 trace=False, t_process=time.time(),
                                 root=root, on_tpu=False,
                                 break_booster=plant, **kw)
    finally:
        forget_steps()


def test_float8_control_is_not_correct_ranked(ranked_root):
    """The reference in the program's place, lambdas and hessians rounded
    to float8: its numbers, held to the cell's limits, fail, in the run
    whose own numbers pass."""
    record = _broken(ranked_root, None, control=True)
    assert record["correct"] is True, record["compared"]
    assert record["control_correct"] is False, record["control_compared"]


@pytest.mark.parametrize("fault", sorted(faults_ranked.FAULTS))
def test_planted_ranking_fault_is_not_correct(ranked_root, fault):
    record = _broken(ranked_root, faults_ranked.FAULTS[fault])
    assert record["correct"] is False, record["compared"]
    assert record["compared"]["trees_missing"][0] == 0.0
    # the trees are whole and the scores add up: the fault is in what the
    # trees were grown FROM, and the gradients' histograms show it
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    assert failing & {"gain_loss", "leaf_update_gap"}, record["compared"]

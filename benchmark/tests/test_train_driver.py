"""The train driver end to end on the CPU at a tiny size: a sound run is
correct under the REAL cells' limits, the float8 control is not, and each
fault planted under the timed path makes `correct` come out false."""

import json
import time

import pytest

import faults
import run as bench_run
from harness.cells import Cell

CELLS = ["criteo64_train_tiny"]


@pytest.fixture(scope="module")
def sound(tiny_root):
    """One sound run of each tiny cell, through run_cell with the look for
    a chip lifted."""
    return {name: bench_run.run_cell(tiny_root, name, seed=2 ** 31 + 77,
                                     seconds=0.0, trace=False,
                                     require_tpu=False)
            for name in CELLS}


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct_and_well_formed(sound, name):
    r = sound[name]
    assert r["correct"] is True, r["compared"]
    assert r["attempted"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tree_s", "setup_s"}
    assert r["metrics"]["train_tree_s"]["value"] > 0
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] == 1
    # one checked tree from each dispatch of the period: K=1 then K=3
    assert r["checked_trees"] == [4, 7]
    assert not any(k.startswith("control.") for k in r["numbers"])
    assert list(r)[-1] == "compared"
    json.dumps(r)


def _broken(tiny_root, name, plant, **kw):
    cell = Cell(tiny_root, name)
    record = cell.driver().run(cell, seed=5, seconds=0.0, trace=False,
                               t_process=time.time(), root=tiny_root,
                               on_tpu=False, break_booster=plant, **kw)
    return record


@pytest.mark.parametrize("name", CELLS)
def test_float8_control_is_not_correct(tiny_root, name):
    """The reference in the program's place, gradients rounded to float8:
    its numbers, held to the cell's limits, fail, in the run whose own
    numbers pass."""
    record = _broken(tiny_root, name, None, control=True)
    assert record["correct"] is True, record["compared"]
    assert record["control_correct"] is False, record["control_compared"]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_planted_fault_is_not_correct(tiny_root, name, fault):
    plant = (faults.state_unchanged(after_trees=4)
             if fault == "state_unchanged" else faults.FAULTS[fault])
    record = _broken(tiny_root, name, plant)
    assert record["correct"] is False, record["compared"]
    failing = {k for k, (v, lim) in record["compared"].items() if v > lim}
    expect = {"state_unchanged": "trees_missing",
              "half_batch": "leaf_count_gap",
              "altered_answer": "score_gap"}[fault]
    assert expect in failing, record["compared"]


def test_no_tpu_means_no_result(tiny_root):
    with pytest.raises(SystemExit):
        bench_run.run_cell(tiny_root, CELLS[0], seed=1, seconds=0.0,
                           trace=False, require_tpu=True)


def test_a_compile_inside_the_window_raises(tiny_root):
    """The warm-up has to cover the window: a fresh shape compiled in it
    ends the run with no result."""
    import jax
    import jax.numpy as jnp

    def plant(booster):
        real = booster.train_segment
        calls = [0]

        def train_segment(max_iters, is_eval=True):
            calls[0] += 1
            if calls[0] > 4:      # past the four warm-up trees
                jax.jit(lambda x: x * 3 + calls[0])(jnp.ones(calls[0] + 7))
            return real(max_iters, is_eval)
        booster.train_segment = train_segment
    with pytest.raises(RuntimeError, match="inside the measured window"):
        _broken(tiny_root, CELLS[0], plant)
